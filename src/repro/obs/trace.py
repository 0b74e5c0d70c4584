"""Plan-stage wall-time tracing.

The executor names every stage with ``jax.named_scope`` (free, trace
metadata only).  This module adds the *timed* mode: for a plan with
stages ``s_1..s_n`` (topo order) it jits one shard_map program per
prefix ``[s_1..s_k]`` via :func:`executor.execute_prefix` — each
returns a replicated probe scalar folding every stage output, so XLA
cannot dead-code any stage — and attributes

    measured(s_k) = median_t(prefix_k) - median_t(prefix_{k-1})

clamped at 0.  The *full* program (``apply_moe``'s) is never modified,
which is why turning timing on cannot perturb outputs: bitwise parity
is structural, not a tolerance (``tests/test_obs.py`` pins it anyway).

Prefix differencing charges a stage with the marginal cost of
extending the program by it — including overlap effects XLA's
scheduler realizes, which is exactly what ``PerfModel.t_plan_stages``
claims to predict.  Noise makes individual small stages jittery
(hence the clamp and the median-of-iters), but the ranked
predicted-vs-measured join in :mod:`repro.obs.audit` is robust to
that: worst offenders are the big stages.

Outputs also export as Chrome-trace JSON (``chrome://tracing`` /
Perfetto): one ``X`` slice per stage laid end to end.
"""

from __future__ import annotations

import json
import math
import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.core import executor
from repro.core import plan as planlib
from repro.core.pipeline import UNCHUNKED_OF
from repro.core.plan import validate


@dataclass
class StageTime:
    name: str
    kind: str
    measured_s: float


@dataclass
class StageTrace:
    """Per-stage wall times for one executed plan."""

    plan: str                    # full plan name (chunked variant)
    schedule: str                # base schedule name requested
    total_s: float               # median wall time of the full program
    overhead_s: float            # prefix-0 program (input probe only)
    stages: List[StageTime] = field(default_factory=list)

    @property
    def n_stages(self) -> int:
        return len(self.stages)

    def by_name(self) -> dict:
        return {s.name: s for s in self.stages}


def _median_time(fn, args, iters: int, warmup: int) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def time_plan_stages(schedule: str, info, mesh, in_specs, args,
                     iters: int = 5, warmup: int = 2,
                     n_chunks: Optional[int] = None) -> StageTrace:
    """Measure per-stage wall times of one plan on one mesh.

    ``info`` is the layer's ``MoEShardInfo``; ``args`` are the
    shard_map operands ``(xt, wg, w1, w3, w2)`` with matching
    ``in_specs`` — i.e. exactly what ``apply_moe`` feeds its body
    (callers: :func:`repro.obs.audit.run_schedule_audit`, the launcher
    ``--trace`` path, and the parity tests).
    """
    base = UNCHUNKED_OF.get(schedule, schedule)
    plan = planlib.build_plan(base, info, n_chunks=n_chunks)
    order = validate(plan)
    out_spec = jax.sharding.PartitionSpec()

    def prefix_fn(k):
        def body(xt, wg, w1, w3_, w2):
            return executor.execute_prefix(plan, xt, wg, w1, w3_, w2,
                                           info, k)
        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_spec,
            check_vma=False))

    medians = []
    for k in range(len(order) + 1):
        label = "input" if k == 0 else order[k - 1].name
        with jax.profiler.TraceAnnotation(f"obs.prefix.{label}"):
            medians.append(_median_time(prefix_fn(k), args, iters, warmup))
    stages = [StageTime(name=st.name, kind=st.kind,
                        measured_s=max(0.0, medians[i + 1] - medians[i]))
              for i, st in enumerate(order)]
    return StageTrace(plan=plan.name, schedule=schedule,
                      total_s=medians[-1], overhead_s=medians[0],
                      stages=stages)


# --- Chrome trace export -----------------------------------------------------

def chrome_trace_events(trace: StageTrace) -> List[dict]:
    """Chrome-trace ``X`` (complete) events, one per stage, laid end to
    end on a single track.  Times in microseconds per the format."""
    events = [{"name": "process_name", "ph": "M", "pid": 0,
               "args": {"name": f"plan {trace.plan}"}},
              {"name": "thread_name", "ph": "M", "pid": 0, "tid": 0,
               "args": {"name": trace.schedule}}]
    ts = 0.0
    for s in trace.stages:
        dur = s.measured_s * 1e6
        events.append({"name": s.name, "cat": s.kind, "ph": "X",
                       "ts": round(ts, 3), "dur": round(dur, 3),
                       "pid": 0, "tid": 0,
                       "args": {"kind": s.kind,
                                "measured_s": s.measured_s}})
        ts += dur
    return events


def save_chrome_trace(trace: StageTrace, path: str) -> str:
    with open(path, "w") as fh:
        json.dump({"traceEvents": chrome_trace_events(trace),
                   "displayTimeUnit": "ms"}, fh, indent=1)
    return path


# --- mesh/operand helpers for standalone harness runs ------------------------

def subset_mesh(shape, names):
    """A mesh over the *first* ``prod(shape)`` local devices (not all of
    them) — the audit runs under dryrun's fake-device farm where the
    full device count is a topology, not a budget."""
    from repro.parallel.mesh import make_mesh
    n = math.prod(int(s) for s in shape)
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(f"need {n} devices for mesh {shape}, "
                         f"have {len(devs)}")
    return make_mesh(shape, names, devices=devs[:n])
