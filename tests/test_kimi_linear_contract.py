"""``tiny-kimi-linear`` as a case of the served contract
(``model_contract.py``), the served part; the part of its recurrent state is
``test_kimi_linear_state_contract.py``'s, its own mechanisms
``test_kimi_linear.py``'s."""

from model_contract import Case, contract_of

CASE = Case(
    tiny="tiny-kimi-linear",
    controls=(("bf16", 3), ("int8", 3), ("kv_int8", 3)),
    # read and written: 6 layers x (4 heads x 32 x 32 state + a tail of 3
    # inputs x 3 x 4 x 32 channels), float32
    row_bytes=2 * (6 * 4 * 32 * 32 * 4 + 6 * 3 * 3 * 4 * 32 * 4))

globals().update(contract_of(CASE, part="served"))
