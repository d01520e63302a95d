"""The ``ouro`` reference, with the served path broken underneath it: the
fault a cache of a pass a layer invites.

For one test: ``make_params`` runs inside the server process before the
engine is built, so this module can reach the model code the run will time
and make EVERY pass read and write pass 0's pages (entry ``l`` where entry
``t * L + l`` belongs): a pass then attends the keys the pass before it
left for the tokens of earlier steps. The reference recomputes every
pass's own keys, and ``correct`` has to come out false.
"""

from references import ouro as base
from references.ouro import CONTROLS, chosen_logprobs  # noqa: F401


def make_params(cfg: dict, seed: int):
    from rbg_tpu.models import llama
    llama._pass_addr = lambda addr, t, entry_pages: addr
    return base.make_params(cfg, seed)
