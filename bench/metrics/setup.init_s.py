"""setup.init_s: host seconds of the run's first ``setup.init`` phase
(``Trainer.setup``: the weights and optimizer state made on the device
from the seed, the step built), from ``repro.obs.phases()``."""

from bench.harness.scopes import first_phase_s


def read(run):
    return first_phase_s("setup.init")
