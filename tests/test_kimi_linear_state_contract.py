"""The part of ``tiny-kimi-linear``'s contract that is of its recurrent state:
a file of its own, because a file is one worker's under ``--dist loadfile``."""

from model_contract import contract_of
from test_kimi_linear_contract import CASE

globals().update(contract_of(CASE, part="state"))
