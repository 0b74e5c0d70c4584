"""Shared arithmetic of the per-layer metric readers
(``bench/metrics/<metric>.py``).  Each reader gets the run (the numbers
of the untraced window, and under ``traced`` the trace's events) and
returns a number, or None where it finds nothing to read."""

from __future__ import annotations

import re
import statistics

from bench.harness import trace as T
from bench.harness.spec import kernel_counter


def per_device(run, fn):
    """Mean over the run's chips of fn(ops, lo, hi), or None."""
    t = run.get("traced")
    if not t:
        return None
    lo, hi = t["window"]
    vals = [fn(ops, lo, hi) for _, ops in sorted(t["devices"].items())]
    vals = [v for v in vals if v is not None]
    return statistics.fmean(vals) if vals else None


def moe_pattern(schedules) -> str:
    return r"(^|/)(%s)\.[^/]" % "|".join(re.escape(s) for s in schedules)


def kernel_roofline(run, kernel: str):
    """Share (%) of the kernel's device time that its roofline needs:
    calls x max(ops / peak FLOP/s, bytes / HBM bytes/s) over the time of
    those calls.  None where the step holds no such kernel, or its calls
    differ in shape."""
    t = run.get("traced")
    calls = run["kernels"].get(kernel)
    count = kernel_counter(kernel)
    if not t or not calls or count is None:
        return None
    if any(c["operands"] != calls[0]["operands"] for c in calls):
        return None
    ctx = {"loads": t["loads"], "model": run["cell"].config["model"]}
    got = count(calls[0], ctx)
    if got is None:
        return None
    ops, nbytes = got
    pk = run["peaks"]
    least = max(ops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])

    def share(o, lo, hi):
        n, ns = T.kernel_calls(o, kernel, lo, hi)
        return 100.0 * n * least / (ns / 1e9) if n else None
    return per_device(run, share)
