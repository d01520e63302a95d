"""``tiny-ouro`` as a case of the served contract (``model_contract.py``):
every part of it, prefix reuse included, since a looped model is served
like any model of one class of page; its own mechanisms are
``test_ouro.py``'s."""

from model_contract import Case, contract_of

CASE = Case(
    tiny="tiny-ouro",
    # kv_int8 rounds every pass's cached K (rotated) and V
    controls=(("bf16", 3), ("int8", 3), ("fp8", 3), ("kv_int8", 3)))

globals().update(contract_of(CASE))
