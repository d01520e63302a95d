"""The part of ``tiny-solar-open2``'s contract that is of its recurrent state:
a file of its own, because a file is one worker's under ``--dist loadfile``."""

from model_contract import contract_of
from test_solar_open2_contract import CASE

globals().update(contract_of(CASE, part="state"))
