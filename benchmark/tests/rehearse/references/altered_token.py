"""The default reference, with the served path broken underneath it.

For one test: ``make_params`` runs inside the server process before the
engine is built, so this module can reach the engine the run will time
and alter every fifth token where it is produced (the event the engine
hands to the service; its log-probability is left as computed). The
reference then scores tokens the program never chose, and ``correct``
has to come out false.
"""

from harness import reference as base
from harness.reference import CONTROLS, chosen_logprobs  # noqa: F401


def make_params(cfg: dict, seed: int):
    from rbg_tpu.engine.engine import Engine
    inner, vocab, count = Engine.step, cfg["vocab_size"], [0]

    def step(self):
        events = inner(self)
        for ev in events:
            count[0] += 1
            if count[0] % 5 == 0:
                ev.token = 1 + ev.token % (vocab - 1)
        return events

    Engine.step = step
    return base.make_params(cfg, seed)
