"""collective.exposed_ms: device ms per step, averaged over chips, in
which a collective runs and no other op does: all-reduce, all-gather,
all-to-all, reduce-scatter and collective-permute, alone or as the
fusions and async start/done pairs the compiler makes of them
(``trace.collective_ops`` from the compiled step's HLO,
``trace.exposed_collective`` from the device trace).  None on a step
without collectives (one chip)."""

from bench.harness import trace as T
from bench.harness.readers import per_device


def read(run):
    t = run.get("traced")
    names = (t or {}).get("collectives")
    if not names or not any(o[0] in names for ops in t["devices"].values()
                            for o in ops):
        return None
    ns = per_device(run, lambda o, lo, hi: T.exposed_collective(
        o, names, lo, hi))
    return None if ns is None else ns / 1e6 / t["n_steps"]
