"""setup.compile_s: host seconds of the run's first ``setup.compile``
phase (the backend's compile of the step, or its read from the
persistent cache), from ``repro.obs.phases()``."""

from bench.harness.scopes import first_phase_s


def read(run):
    return first_phase_s("setup.compile")
