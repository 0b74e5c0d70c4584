"""The ``schedule="auto"`` runtime: per-layer (schedule, chunks, wire)
decisions.

Parm's Algorithm 1 picks S1 or S2 from the alpha-beta model; the
pipelined bodies (``repro.core.pipeline``) add a second axis — how many
micro-chunks to split the AlltoAll/FFN chain into — and the wire-format
subsystem (``repro.core.collectives.CommConfig``) a third: how many
bytes each element of those collectives puts on the fabric.  This
module owns the joint decision:

  * **analytic** mode enumerates the schedule axis from the *plan
    registry* (``repro.core.plan.PLANS``) and scores every (schedule,
    n_chunks) candidate by walking its plan graph with
    :meth:`repro.core.perfmodel.PerfModel.t_plan` (Algorithm 1's S1/S2
    comparison generalized with the compute-overlap term) — no devices
    touched, fully deterministic under a fixed perf model.
  * **measured** mode runs a one-shot calibration on the live mesh: each
    candidate is jitted and timed on synthetic data of the layer's shape
    (:func:`measure_candidates`), and the observed winner is recorded.

Either way the result is a :class:`ScheduleDecision` cached per
``(MoELayerShape, mode, candidates, perf model)`` — so a training run
decides once per distinct MoE layer shape, every later ``apply_moe``
trace hits the cache, and repeated runs under the same perf model make
identical picks (asserted by ``tests/test_autosched.py``).

``apply_moe`` consults :func:`decide` whenever ``MoEConfig.schedule`` is
``"auto"``; ``launch/train.py --autosched measured`` switches modes from
the command line.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

from repro import obs
from repro.core import plan as planlib
from repro.core.perfmodel import (MoELayerShape, PerfModel, WIRE_BYTES,
                                  tpu_v5e_model)
from repro.core.pipeline import PIPELINE_OF  # populates the plan registry

#: The schedule axis of the candidate grid is the *plan registry*
#: (``repro.core.plan.PLANS``): registering a schedule automatically adds
#: it to the analytic and measured grids per its ``PlanEntry`` flags.
#: ``baseline`` is measured-only (it can win on tiny single-axis meshes,
#: but Algorithm 1 proves S1/S2 dominate it analytically — §IV-B);
#: ``s1_seqpar`` is in neither grid (it needs the sequence-parallel
#: activation contract, so it is only ever forced).
DEFAULT_CHUNKS = (1, 2, 4, 8)
#: wire dtypes scored by default (no compression; the legacy pair grid
#: scores with wire_dtype=None, so decisions match the pre-wire runtime)
DEFAULT_WIRE = ("f32",)
#: candidates when ``CommConfig.wire_dtype == "auto"``.  fp8 is excluded
#: on purpose: the analytic model knows only bytes, so it would always
#: pick the narrowest dtype; fp8's accuracy cost must be opted into
#: explicitly (``wire_dtype="fp8_e4m3"``), never chosen silently.
AUTO_WIRE = ("f32", "bf16")


@dataclass(frozen=True)
class ScheduleDecision:
    """The cached outcome of one auto-scheduling decision.

    ``schedule`` is the base schedule name (``baseline``/``s1``/``s2``),
    ``n_chunks`` the micro-chunk count (1 = unchunked), ``wire_dtype``
    the collective payload width, ``source`` how it was reached
    (``analytic`` / ``measured`` / ``forced``), and ``times`` the scored
    candidates as ``(candidate, seconds)`` pairs sorted fastest-first —
    candidates are ``(schedule, n_chunks)`` pairs under the default
    f32-only wire grid (back-compat) and ``(schedule, n_chunks,
    wire_dtype)`` triples under a joint wire decision.
    """

    schedule: str
    n_chunks: int = 1
    source: str = "analytic"
    times: tuple = ()
    wire_dtype: str = "f32"
    #: the process-wide placement epoch this decision was made under
    #: (see :func:`set_placement`); ``cache_summary`` marks decisions
    #: from an older epoch as stale.
    placement_epoch: int = 0

    @property
    def body_name(self) -> str:
        """The ``schedules.BODY`` key implementing this decision."""
        if self.n_chunks > 1:
            return PIPELINE_OF.get(self.schedule, self.schedule)
        return self.schedule


_CACHE: dict = {}

#: process-wide wire ceiling: fp8 decisions are clamped up to this dtype
#: when set (see :func:`set_wire_ceiling`) — the guard rails' overflow
#: fallback.  None = no clamping (the default).
_WIRE_CEILING = None

#: callbacks fired by :func:`invalidate` (observability for plan swaps)
_INVALIDATION_HOOKS: list = []

#: process-wide expert placement (``repro.core.placement.ExpertPlacement``
#: or None = uniform) consulted by ``apply_moe`` when
#: ``MoEConfig.placement == "auto"``, plus a monotone epoch counter so
#: cached decisions record which placement regime they were made under.
_PLACEMENT = None
_PLACEMENT_EPOCH = 0


def clear_cache() -> None:
    """Drop every cached decision and reset the placement registry
    (tests, or after remeshing)."""
    global _PLACEMENT, _PLACEMENT_EPOCH
    _CACHE.clear()
    _PLACEMENT = None
    _PLACEMENT_EPOCH = 0


def invalidate(reason: str = "", shape=None) -> int:
    """Decision-cache invalidation hook: drop cached decisions and
    notify registered hooks.  Returns the number of entries dropped.

    With ``shape=None`` (the default) every decision is dropped — the
    "cheap plan swap" entry point: after changing something decisions
    depend on outside the cache key (e.g. the wire ceiling), call this
    and re-jit; the retrace re-consults :func:`decide`.  Passing a
    ``MoELayerShape`` drops only that shape's decisions (every mode /
    grid / perf-model variant), leaving other layers' lines warm.
    """
    if shape is None:
        n = len(_CACHE)
        _CACHE.clear()
    else:
        drop = [k for k in _CACHE if k[0] == shape]
        for k in drop:
            del _CACHE[k]
        n = len(drop)
    for cb in list(_INVALIDATION_HOOKS):
        cb(reason, n)
    obs.emit("autosched_invalidate", reason=reason, dropped=n)
    return n


def set_placement(placement) -> int:
    """Install ``placement`` (an ``ExpertPlacement`` or None = uniform)
    as the process-wide expert placement and bump the placement epoch.

    The decision cache is deliberately NOT flushed — already-jitted
    steps keep running their traced plans (no re-jit churn); the epoch
    is part of every new :func:`decide` cache key, so the *next* re-jit
    (the caller's choice of moment, e.g. ``Trainer``'s rebalance
    trigger) re-decides under the new placement while
    :func:`cache_summary` marks the old lines stale in the meantime.
    Returns the new epoch.
    """
    global _PLACEMENT, _PLACEMENT_EPOCH
    _PLACEMENT = placement
    _PLACEMENT_EPOCH += 1
    obs.emit("placement_epoch", epoch=_PLACEMENT_EPOCH,
             uniform=placement is None,
             n_phys=getattr(placement, "n_phys", None),
             cap_frac=getattr(placement, "cap_frac", None))
    return _PLACEMENT_EPOCH


def current_placement():
    """The installed ``ExpertPlacement`` (None = uniform) — what
    ``apply_moe`` resolves ``MoEConfig.placement == "auto"`` to at
    trace time."""
    return _PLACEMENT


def placement_epoch() -> int:
    return _PLACEMENT_EPOCH


def add_invalidation_hook(cb) -> None:
    """Register ``cb(reason, n_dropped)`` to observe invalidations."""
    _INVALIDATION_HOOKS.append(cb)


def remove_invalidation_hook(cb) -> None:
    if cb in _INVALIDATION_HOOKS:
        _INVALIDATION_HOOKS.remove(cb)


def set_wire_ceiling(wire) -> None:
    """Clamp every *resolved* wire decision to at least ``wire`` bytes
    per element (None clears).  ``apply_moe`` applies the clamp via
    :func:`clamp_wire` after resolving forced/auto wire dtypes, so a
    single ``set_wire_ceiling("bf16")`` + :func:`invalidate` + re-jit
    swaps every fp8 wire in the model to bf16 — the guard rails' fp8
    overflow fallback — without touching configs or restarting."""
    global _WIRE_CEILING
    if wire is not None and wire not in WIRE_BYTES:
        raise ValueError(f"unknown wire dtype {wire!r} "
                         f"(want one of {tuple(WIRE_BYTES)})")
    _WIRE_CEILING = wire


def wire_ceiling():
    return _WIRE_CEILING


def clamp_wire(wire: str) -> str:
    """Apply the process-wide wire ceiling to a resolved wire dtype:
    dtypes narrower than the ceiling are widened to it, wider ones pass
    through untouched."""
    if _WIRE_CEILING is None or wire not in WIRE_BYTES:
        return wire
    if WIRE_BYTES[wire] < WIRE_BYTES[_WIRE_CEILING]:
        return _WIRE_CEILING
    return wire


def cache_info() -> dict:
    """Snapshot of the decision cache: key -> ScheduleDecision."""
    return dict(_CACHE)


def cache_summary(exclude=()) -> str:
    """One line per cached decision, for run logs.  ``exclude`` filters
    out keys already present before a run (see ``Trainer``), so multi-
    model processes only report their own decisions."""
    lines = []
    for key, d in sorted(_CACHE.items(), key=lambda kv: repr(kv[0][0])):
        if key in exclude:
            continue
        shape, mode = key[0], key[1]
        cls = " decode" if getattr(shape, "infer", False) else ""
        ep = d.placement_epoch
        stale = " STALE" if ep != _PLACEMENT_EPOCH else ""
        lines.append(
            f"autosched[{mode}{cls}] BxL={shape.B}x{shape.L} M={shape.M} "
            f"E={shape.E} ep/esp/mp={shape.n_ep}/{shape.n_esp}/{shape.n_mp}"
            f" -> {d.schedule} x{d.n_chunks} chunks wire={d.wire_dtype}"
            f" ({d.source} placement-epoch={ep}{stale})")
    return "\n".join(lines)


def _norm(cand):
    """Candidate -> (schedule, n_chunks, wire_dtype), defaulting f32."""
    return cand if len(cand) == 3 else (cand[0], cand[1], "f32")


def decide(shape: MoELayerShape, *, perf_model: Optional[PerfModel] = None,
           mode: str = "analytic", chunk_candidates=DEFAULT_CHUNKS,
           wire_candidates=DEFAULT_WIRE, schedules=None,
           measure: Optional[Callable] = None) -> ScheduleDecision:
    """Pick (schedule, n_chunks, wire_dtype) for one MoE layer shape,
    with caching.

    ``wire_candidates`` widens the grid to a joint comm-precision
    decision (``AUTO_WIRE`` when ``CommConfig.wire_dtype == "auto"``);
    with the default f32-only grid, candidates stay the legacy
    ``(schedule, n_chunks)`` pairs.  ``schedules`` restricts the
    schedule axis (a forced schedule that still wants a wire decision).
    Exact ties break toward the *wider* wire dtype, so compression is
    only picked where the model says the comm term actually shrinks the
    layer time.  ``measure`` (measured mode) maps the candidate list to
    ``{candidate: seconds}``; :func:`measure_candidates` builds one from
    a live mesh.  The decision is cached on every argument — pass the
    same arguments, get the identical (cached) decision back.
    """
    if mode not in ("analytic", "measured"):
        raise ValueError(f"unknown autosched mode {mode!r}")
    pm = perf_model or tpu_v5e_model(shape.n_ep, shape.n_esp, shape.n_mp)
    wire_candidates = tuple(wire_candidates)
    joint_wire = wire_candidates != ("f32",)
    # Resolve the schedule grid BEFORE the cache lookup: the registry can
    # grow (register_plan) after a decision was cached, and the stale
    # entry must not shadow the widened grid.
    # The decode shape class (shape.infer) widens the grid to the
    # decode-dedicated plans (s1d) — and, being part of ``shape``, also
    # keys the cache, so a decode decision can never evict a training/
    # prefill decision for the same sizes.
    if schedules is not None:
        scheds = tuple(schedules)
    elif mode == "measured":
        scheds = planlib.measured_schedules(infer=shape.infer)
    else:
        scheds = planlib.analytic_schedules(infer=shape.infer)
    # The placement epoch is part of the key: after a rebalance
    # (set_placement) the stale line stays cached (the running jit still
    # uses it) but any retrace decides afresh under the new placement.
    key = (shape, mode, tuple(chunk_candidates), pm, wire_candidates,
           scheds, _PLACEMENT_EPOCH)
    hit = _CACHE.get(key)
    if hit is not None:
        return hit

    if mode == "measured":
        if measure is None:
            raise ValueError("measured mode needs a `measure` callable "
                             "(see autosched.measure_candidates)")
        cands = [((s, n, w) if joint_wire else (s, n))
                 for s in scheds for n in chunk_candidates
                 for w in wire_candidates]
        times = dict(measure(cands))
    else:
        # Each candidate is scored by walking its actual plan graph
        # (PerfModel.t_plan) — the same stages the executor will run, so
        # a newly registered schedule is scored with no new closed form.
        # Legacy f32-only grids score with wire_dtype=None (factor 1.0,
        # the width the betas were fitted at) so default-config decisions
        # are exactly PR 2's.  A joint grid scores each wire dtype at its
        # true byte width relative to PerfModel.wire_bytes_ref — only the
        # *ratios* between candidates decide the argmin.
        times = {}
        for s in scheds:
            for n in chunk_candidates:
                p = planlib.plan_for_shape(s, shape, n)
                for w in wire_candidates:
                    times[(s, n, w) if joint_wire else (s, n)] = \
                        pm.t_plan(p, shape,
                                  wire_dtype=w if joint_wire else None)
    # rank by time; exact ties prefer the wider wire (no silent
    # compression), then candidate-grid order (stable sort).
    ranked = tuple(sorted(
        times.items(),
        key=lambda kv: (kv[1], -WIRE_BYTES[_norm(kv[0])[2]])))
    sched, n_chunks, wire = _norm(ranked[0][0])
    decision = ScheduleDecision(schedule=sched, n_chunks=n_chunks,
                                source=mode, times=ranked,
                                wire_dtype=wire,
                                placement_epoch=_PLACEMENT_EPOCH)
    _CACHE[key] = decision
    # cache-fill only: the per-trace cache hits stay silent, so the
    # metrics stream records one decision event per distinct layer line
    obs.emit("autosched_decision", schedule=sched, n_chunks=n_chunks,
             wire=wire, mode=mode,
             infer=bool(getattr(shape, "infer", False)),
             tokens=shape.B * shape.L, d_model=shape.M, E=shape.E,
             placement_epoch=_PLACEMENT_EPOCH)
    return decision


def decide_placement(shape, loads, *, schedule, n_chunks: int = 1,
                     candidate=None, perf_model: Optional[PerfModel] = None,
                     capacity_factor: float = 1.0, top_k: int = 1,
                     margin: float = 1.05, max_replicas=None):
    """Score a load-derived expert placement against uniform for one
    layer shape.

    Builds ``candidate`` (default: ``placement_from_loads`` over the
    observed per-expert ``loads``), prices the layer's plan both ways
    with the skew-aware cost model (``PerfModel.t_plan(..., loads=...)``
    — uniform pays the max-rank load inflation, the placed plan pays
    its shrunk pool at its own residual imbalance), and returns
    ``(placement_or_None, t_placed, t_uniform)`` where the placement is
    ``None`` unless it beats uniform by at least ``margin``.
    """
    from repro.core.placement import placement_from_loads

    pm = perf_model or tpu_v5e_model(shape.n_ep, shape.n_esp, shape.n_mp)
    if candidate is None:
        candidate = placement_from_loads(
            loads, shape.n_ep, n_experts=shape.E,
            capacity_factor=capacity_factor, top_k=top_k,
            max_replicas=max_replicas, epoch=_PLACEMENT_EPOCH + 1)
    t_uni = pm.t_plan(planlib.plan_for_shape(schedule, shape, n_chunks),
                      shape, loads=loads)
    if candidate is None or candidate.is_identity:
        return None, t_uni, t_uni
    t_cand = pm.t_plan(
        planlib.plan_for_shape(schedule, shape, n_chunks,
                               placement=candidate), shape, loads=loads)
    win = t_cand * margin < t_uni
    return (candidate if win else None), t_cand, t_uni


def maybe_rebalance(loads, *, margin: float = 1.05,
                    capacity_factor: float = 1.0, top_k: int = 1,
                    perf_model: Optional[PerfModel] = None,
                    max_replicas=None, infer: bool = False):
    """The rebalance trigger: derive a placement from the live load EMA,
    score it against uniform over every compatible cached decision, and
    install it on a win.

    ``loads`` is the smoothed per-expert load vector (``LoadEMA.value``).
    Candidate shapes come from :func:`cache_info` — the layers this
    process has actually decided for (``infer`` selects the decode
    class).  The candidate must beat uniform by ``margin`` on *every*
    compatible shape (the placement is process-wide, so a loss anywhere
    vetoes).  On a win, :func:`set_placement` installs it and the new
    epoch is returned; if the loads have evened out (identity candidate)
    while a placement is installed, the placement is cleared (also a new
    epoch).  Returns None when nothing changes — the caller skips the
    re-jit entirely.
    """
    from repro.core.placement import placement_from_loads

    import numpy as _np

    loads = _np.asarray(loads, dtype=_np.float64)
    seen, todo = set(), []
    for key, d in _CACHE.items():
        shape = key[0]
        if bool(getattr(shape, "infer", False)) != infer:
            continue
        if shape.n_ep <= 1 or shape.E != loads.size:
            continue
        sk = (shape, d.schedule, d.n_chunks)
        if sk in seen:
            continue
        seen.add(sk)
        todo.append(sk)
    if not todo:
        return None
    n_ep = todo[0][0].n_ep
    cand = placement_from_loads(
        loads, n_ep, n_experts=int(loads.size),
        capacity_factor=capacity_factor, top_k=top_k,
        max_replicas=max_replicas, epoch=_PLACEMENT_EPOCH + 1)
    if infer and cand.cap_frac < 1.0:
        # decode layers run drop-free (apply_moe forces cap_frac=1.0),
        # so score the candidate the way decode will actually run it; a
        # capacity-shrink-only candidate (no replication) degenerates to
        # a bare permutation at full capacity — treat as uniform
        from dataclasses import replace as _dc_replace
        from repro.core.placement import identity_placement
        cand = identity_placement(cand.n_experts, n_ep) \
            if cand.n_phys == cand.n_experts \
            else _dc_replace(cand, cap_frac=1.0)
    if cand.is_identity:
        if _PLACEMENT is not None:
            return set_placement(None)  # loads evened out: back to uniform
        return None
    cur = _PLACEMENT
    if cur is not None and cur.assignments == cand.assignments \
            and abs(cur.cap_frac - cand.cap_frac) < 0.05:
        return None  # already running (close enough to) this placement
    for shape, sched, nc in todo:
        if shape.n_ep != n_ep:
            continue  # placement is per-EP-degree; skip foreign meshes
        got, _, _ = decide_placement(
            shape, loads, schedule=sched, n_chunks=nc, candidate=cand,
            perf_model=perf_model, margin=margin)
        if got is None:
            return None
    return set_placement(cand)


def measure_candidates(mesh, dims, cfg, *, tokens: int, d_model: int,
                       iters: int = 3, warmup: int = 1,
                       seed: int = 0) -> Callable:
    """Build a ``measure`` callable timing candidates on the live mesh.

    Returns ``f(candidates) -> {candidate: seconds}`` — candidates are
    ``(schedule, n_chunks)`` pairs or ``(schedule, n_chunks, wire_dtype)``
    triples — that jits ``apply_moe`` once per candidate over synthetic
    data and records median wall time.  ``tokens`` is the *global* pool
    (B*L of the real layer): the nested ``apply_moe`` re-shards it over
    the same batch axes, so each candidate runs at the true per-device
    token count.  Raises if every candidate fails; off the TPU,
    individual failures score ``inf`` (on the TPU any failure raises).
    The imports are lazy to keep ``moe -> autosched`` one-directional at
    module load.
    """

    def _measure(candidates):
        import sys as _sys
        import time as _time

        import jax
        import jax.numpy as jnp
        from dataclasses import replace

        from repro.core.collectives import CommConfig
        from repro.core.moe import apply_moe, init_moe_params

        key = jax.random.PRNGKey(seed)
        params = init_moe_params(key, cfg)
        x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                              (1, tokens, d_model), jnp.float32)
        out, errors = {}, {}
        for cand in candidates:
            sched, n_chunks, wire = _norm(cand)
            c = replace(cfg, schedule=sched, pipeline_chunks=n_chunks,
                        comm=CommConfig(wire_dtype=wire,
                                        scaling=cfg.comm.scaling))
            fn = jax.jit(lambda x, p, c=c, s=sched: apply_moe(
                x, p, mesh=mesh, dims=dims, cfg=c, schedule=s)[0])
            try:
                for _ in range(max(warmup, 1)):
                    fn(x, params).block_until_ready()
                ts = []
                for _ in range(max(iters, 1)):
                    t0 = _time.perf_counter()
                    fn(x, params).block_until_ready()
                    ts.append(_time.perf_counter() - t0)
                ts.sort()
                out[cand] = ts[len(ts) // 2]
            except Exception as e:  # noqa: BLE001 — unlowerable candidate
                if jax.default_backend() == "tpu":
                    raise   # on the chip a failed candidate fails the run
                out[cand] = float("inf")
                errors[cand] = repr(e)
        if errors and all(t == float("inf") for t in out.values()):
            raise RuntimeError(
                "autosched measured calibration failed for every candidate: "
                + "; ".join(f"{c}: {m}" for c, m in errors.items()))
        for c, m in errors.items():
            # partial failures score inf (never win) but must be visible,
            # or "measured mode never picks X" is undebuggable from logs
            print(f"autosched: candidate {c} failed calibration: {m}",
                  file=_sys.stderr, flush=True)
        return out

    def run(candidates):
        # decide() is usually reached while TRACING train_step; calling
        # the candidate jits on that thread would stage them into the
        # ambient trace (returning tracers) instead of executing.  JAX's
        # trace state is thread-local, so a worker thread gives a clean
        # eager context on every jax version — the calibration runs for
        # real on the live devices while the outer trace is suspended.
        import threading

        box = {}

        def work():
            try:
                box["out"] = _measure(candidates)
            except BaseException as e:  # noqa: BLE001 — reraise on caller
                box["err"] = e

        t = threading.Thread(target=work, name="autosched-calibration")
        t.start()
        t.join()
        if "err" in box:
            raise box["err"]
        return box["out"]

    return run
