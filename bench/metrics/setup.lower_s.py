"""setup.lower_s: host seconds of the run's first ``setup.lower`` phase
(``Trainer.compile`` tracing and lowering the step), from
``repro.obs.phases()``."""

from bench.harness.scopes import first_phase_s


def read(run):
    return first_phase_s("setup.lower")
