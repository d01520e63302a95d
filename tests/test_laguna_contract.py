"""``tiny-laguna`` as a case of the served contract (``model_contract.py``),
the served part; the part of its second class of page (preemption, no
prefix kept, the refusals, each rule left out) is
``test_laguna_state_contract.py``'s, its own mechanisms ``test_laguna.py``'s."""

from model_contract import Case, contract_of

CASE = Case(
    tiny="tiny-laguna",
    # kv_int8 rounds the cached K (rotated) and V of both classes of page
    controls=(("bf16", 3), ("int8", 3), ("fp8", 3), ("kv_int8", 3)))

globals().update(contract_of(CASE, part="served"))
