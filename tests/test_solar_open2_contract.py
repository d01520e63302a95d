"""``tiny-solar-open2`` as a case of the served contract
(``model_contract.py``), the served part; the part of its recurrent state is
``test_solar_open2_state_contract.py``'s, its own mechanisms
``test_solar_open2.py``'s."""

from model_contract import Case, contract_of

CASE = Case(
    tiny="tiny-solar-open2",
    # kv_int8 rounds the cached K and V AND the recurrent state
    controls=(("bf16", 3), ("int8", 3), ("fp8", 3), ("kv_int8", 3)),
    # read and written: 6 layers x (4 x 32 x 32 state + 3 x 384 tail), f32
    row_bytes=2 * 6 * (4 * 32 * 32 + 1152) * 4)

globals().update(contract_of(CASE, part="served"))
