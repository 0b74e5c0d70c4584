"""device.idle_pct: share of the traced window in which no op runs on a
chip (1 - the union of its op intervals over the window), averaged
over chips."""

from bench.harness import trace as T
from bench.harness.readers import per_device


def read(run):
    return per_device(run, lambda o, lo, hi: 100.0 * (
        1.0 - T.length(T.busy(o, lo, hi)) / (hi - lo)))
