"""moe.device_ms: device time per step of the MoE layer, forward and
backward: the ops whose scope path holds a ``<plan>.<stage>`` scope of
a schedule autosched picked (``core/executor.py`` names every plan
stage so), from the device trace, per chip, averaged over chips."""

from bench.harness import trace as T
from bench.harness.readers import moe_pattern, per_device


def read(run):
    t = run.get("traced")
    if not t or not run["schedules"]:
        return None
    pat = moe_pattern(run["schedules"])
    ms = per_device(run, lambda o, lo, hi: T.scope_time(
        o, t["index"], pat, lo, hi) / 1e6)
    return None if not ms else ms / t["n_steps"]
