"""The part of ``tiny-laguna``'s contract that is of what it keeps beside a
prefix-cached model's pages: a file of its own, because a file is one
worker's under ``--dist loadfile``."""

from model_contract import contract_of
from test_laguna_contract import CASE

globals().update(contract_of(CASE, part="state"))
