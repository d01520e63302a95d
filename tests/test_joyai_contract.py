"""``tiny-joyai`` as a case of the served contract (``model_contract.py``);
its own mechanisms are ``test_joyai.py``'s."""

from model_contract import Case, contract_of

CASE = Case(tiny="tiny-joyai",
            controls=(("bf16", 3), ("int8", 3), ("fp8", 3)))

globals().update(contract_of(CASE))
