"""Lower a schedule :class:`~repro.core.plan.Plan` to jax, stage by stage.

``execute`` walks the validated plan graph inside a shard_map body and
emits, for each stage kind, the exact collective / registry-kernel call
sequence the hand-written schedule bodies used — so a plan-built
schedule is numerically the legacy body it replaced (asserted per
(schedule x n_chunks x wire_dtype) against the golden copies in
``tests/helpers/legacy_bodies.py``).  All schedule-specific knowledge
lives in the plans; this module knows only how to emit one stage of
each kind.

The stage vocabulary and its lowering:

  gate          topk_gate over the stage input's token pool
  dispatch      registry ``moe_dispatch`` scatter into (E, cap, M)
  mp_split      take this rank's slice (free fwd, AllGather bwd)
  dispatch_a2a  EP AlltoAll (baseline layout) or fused EP&ESP AlltoAll
                (expert-major dump, §Perf A2); ``hier=...`` decomposes
                it into intra- + inter-group hops (s2h)
  expert_ffn    registry ``expert_ffn`` on the local expert batch
  allreduce     in-network psum over ESP (baseline partial sums)
  combine_a2a   the return AlltoAll; fused variant reduces ESP partials
                locally; ``saa=True`` runs the chunked SAA combine +
                MP-AllGather overlap; ``stack_ag=True`` appends the
                per-chunk stacked AllGather (s2/s2h capacity restore)
  ag_mp         AllGather over ESP (baseline entry, wire-exempt) or MP
                (S1 exit, wire)
  combine       registry ``moe_combine`` gather + gate-weight mix
  rs_mp         exit split (the baseline's ESP-Split)
  slice/merge   micro-chunk bookkeeping inserted by ``split_capacity``

Wire precision: stages with ``wire=True`` get the plan's stamped
``CommConfig`` and call the ``wire_*`` collective twins; everything else
calls the raw collectives (f32), reproducing the legacy bodies' exempt
set (the pre-gate AllGather feeds the router — rounding it would change
routing — and the ESP-AllReduce sums in-network with no decode point).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import collectives as coll
from repro.core.gating import combine, dispatch, topk_gate
from repro.core.plan import INPUT, Plan, validate
from repro.kernels.registry import get_op


def expert_ffn(xb, w1, w3, w2, info):
    """Per-expert FFN on this device's (El, t, M) batch.

    Weights are the local ESP shard (hidden dim sliced N_ESP ways), so the
    output is a *partial sum* that the caller reduces across the ESP group
    (psum in the baseline, the combine-AlltoAll's local reduction in S1/S2).
    Compute is the registry's ``expert_ffn`` op under ``info.kernel``.
    """
    op = get_op("expert_ffn", cfg=info.kernel, act=info.act)
    return op(xb, w1, w3 if info.glu else None, w2)


def _aux_mean(aux, info):
    axes = tuple(dict.fromkeys(info.ep_axes + info.esp_axes + info.mp_axes))
    return {k: (lax.pmean(v, axes) if v.ndim == 0 else v)
            for k, v in aux.items()}


def _group(info, key):
    """Resolve a logical axis key to (mesh axis names, group size)."""
    return {"ep": (info.ep_axes, info.n_ep),
            "esp": (info.esp_axes, info.n_esp),
            "mp": (info.mp_axes, info.n_mp)}[key]


def _gate_cap(info, spec: str) -> int:
    """Per-expert capacity for the token pool a gate stage sees."""
    if spec == "pool":           # the unsplit s_local pool (s2, seqpar)
        return info.cap
    if spec == "esp_pool":       # post-ESP-AllGather pool (baseline)
        return info.cap * info.n_esp
    if spec == "mp_shard":       # this MP rank's 1/N_MP slice (s1)
        return info.cap // info.n_mp
    raise ValueError(f"unknown gate cap spec {spec!r}")


class _PlacedTables:
    """Trace-time constant lookup tables for an ``ExpertPlacement``
    (tiny int32 arrays; the placement itself never enters jit)."""

    __slots__ = ("n_phys", "assign", "rep_count", "rep_index", "rep_table")

    def __init__(self, pl):
        self.n_phys = pl.n_phys
        self.assign = jnp.asarray(pl.assignments, jnp.int32)     # (R,)
        self.rep_count = jnp.asarray(pl.rep_count)               # (E,)
        self.rep_index = jnp.asarray(pl.replica_index)           # (R,)
        self.rep_table = jnp.asarray(pl.rep_table)               # (E, r*)


def _placed_flat(ctx, g, cap: int):
    """Flat physical-buffer index per (token, choice) under a placement:
    logical slot ``s`` of expert ``e`` maps round-robin to replica
    ``s % r_e`` at physical slot ``s // r_e`` — the replica-fractional
    dispatch split.  ``n_phys * cap`` is the drop sentinel.  Memoized on
    the GateResult like :meth:`GateResult.flat`."""
    key = ("placed", cap)
    if key not in g._flat:
        t = ctx.placed
        r = t.rep_count[g.expert_idx]                            # (S, k)
        phys = t.rep_table[g.expert_idx, g.slot_idx % r]
        pslot = g.slot_idx // r
        g._flat[key] = jnp.where(pslot < cap, phys * cap + pslot,
                                 t.n_phys * cap).astype(jnp.int32)
    return g._flat[key]


class _Ctx:
    __slots__ = ("info", "wg", "w1", "w3", "w2", "comm", "gate", "dtype",
                 "placement", "placed")

    def __init__(self, info, wg, w1, w3, w2, comm, dtype, placement=None):
        self.info, self.comm = info, comm
        self.wg, self.w1, self.w3, self.w2 = wg, w1, w3, w2
        self.gate = None     # (GateResult, cap) once the gate stage ran
        self.dtype = dtype   # layer-input dtype (raw-wire decode target)
        self.placement = placement
        self.placed = _PlacedTables(placement) \
            if placement is not None else None


def _emit(st, vals, ctx):
    """Lower one stage; ``vals`` are its deps' values in order."""
    info = ctx.info
    E, Eh = info.gate.n_experts, info.held
    Ne, Ns, Nm = info.n_ep, info.n_esp, info.n_mp
    comm = ctx.comm if st.wire else None
    kind = st.kind

    if kind == "gate":
        cap = _gate_cap(info, st.p("cap", "pool"))
        if ctx.placed is not None:
            # placed: cap becomes the per-*physical*-slot capacity; the
            # gate keeps r_e * cap slots per logical expert (effective
            # capacity vector) so a replicated hot expert drops less
            cap = st.p("placed_cap") or ctx.placement.scaled_cap(cap)
            eff = ctx.placed.rep_count * cap                     # (E,)
            g = topk_gate(vals[0], ctx.wg, info.gate, eff)
        else:
            g = topk_gate(vals[0], ctx.wg, info.gate, cap)
        ctx.gate = (g, cap)
        return ctx.gate

    if kind == "dispatch":
        tokens, (g, cap) = vals
        if ctx.placed is not None:
            return dispatch(tokens, g.expert_idx, g.slot_idx, cap,
                            ctx.placed.n_phys, info.kernel,
                            flat=_placed_flat(ctx, g, cap))
        return dispatch(tokens, g.expert_idx, g.slot_idx, cap, Eh,
                        info.kernel, flat=g.flat(cap, E, info.n_held))

    if kind in ("mp_split", "rs_mp"):
        axes, n = _group(info, st.axes[0])
        return coll.mp_split(vals[0], axes, n, axis=st.p("axis", 0))

    if kind == "ag_mp":
        axes, n = _group(info, st.axes[0])
        axis = st.p("axis", 0)
        if st.wire:
            return coll.wire_mp_all_gather(vals[0], axes, n, comm,
                                           axis=axis)
        return coll.mp_all_gather(vals[0], axes, n, axis=axis)

    if kind == "dispatch_a2a":
        d = vals[0]
        if not st.p("fused"):
            # baseline layout: (E, c, M) -> (Ne, El, c, M) EP blocks
            # (first dim may be R physical slots under a placement)
            sb = d.reshape(Ne, d.shape[0] // Ne, d.shape[1], -1)
            rb = coll.wire_ep_all_to_all(sb, info.ep_axes, comm)
            return coll.to_expert_batch(rb)
        sb = coll.dump_em(d, Ne, Ns)                    # (El, G, c, M)
        hier = st.p("hier")
        if hier:
            rb = coll.wire_hier_ep_esp_all_to_all(
                sb, info.ep_axes, info.esp_axes, Ne, Ns, comm,
                axis=1, order=hier)
        elif st.p("raw") and coll.wire_raw_ok(comm):
            # grouped-megakernel consumer: leave the payload *encoded*
            # (f32/bf16 are plain casts) — the ragged kernel's f32 upcast
            # is the decode, so the full-buffer codec pass is elided
            rb = coll.ep_esp_all_to_all(
                coll.wire_encode(sb, comm), info.ep_axes, info.esp_axes,
                split_axis=1, concat_axis=1)
        else:
            rb = coll.wire_ep_esp_all_to_all(
                sb, info.ep_axes, info.esp_axes, comm,
                split_axis=1, concat_axis=1)
        return coll.to_expert_batch_em(rb)              # (El, G*c, M)

    if kind == "expert_ffn":
        return expert_ffn(vals[0], ctx.w1, ctx.w3, ctx.w2, info)

    if kind == "expert_ffn_grouped":
        return _emit_grouped(st, vals, ctx)

    if kind == "allreduce":
        axes, _ = _group(info, st.axes[0])
        return lax.psum(vals[0], axes)

    if kind == "combine_a2a":
        h = vals[0]
        if not st.p("fused"):
            back = coll.wire_ep_all_to_all(
                coll.from_expert_batch(h, Ne), info.ep_axes, comm)
            return back.reshape(back.shape[0] * back.shape[1],
                                back.shape[2], -1)      # (E|R, c, M)
        y4 = coll.from_expert_batch_em(h, info.combined_group)
        if st.p("saa"):
            return coll.saa_combine_allgather(
                y4, info.ep_axes, info.esp_axes, info.mp_axes,
                n_ep=Ne, n_esp=Ns, n_mp=Nm,
                n_chunks=st.p("saa_chunks", info.saa_chunks),
                comm=comm)                              # (E, c*Nm, M)
        hier = st.p("hier")
        if hier:
            back = coll.wire_hier_ep_esp_all_to_all(
                y4, info.ep_axes, info.esp_axes, Ne, Ns, comm,
                axis=1, order=hier)
        elif st.p("raw") and coll.wire_raw_ok(comm):
            # grouped-megakernel producer: the ragged kernel already cast
            # its output to the wire dtype (the encode half of the fused
            # codec); move it raw, decode once, then reduce in f32
            back = coll.wire_decode(
                coll.ep_esp_all_to_all(y4, info.ep_axes, info.esp_axes,
                                       split_axis=1, concat_axis=1),
                comm, ctx.dtype)
        else:
            back = coll.wire_ep_esp_all_to_all(
                y4, info.ep_axes, info.esp_axes, comm,
                split_axis=1, concat_axis=1)
        mine = coll.undump_reduce_em(back, Ne, Ns)      # (E|R, c, M)
        if not st.p("stack_ag"):
            return mine
        if Nm == 1:
            part = mine[:, None]                        # (E, 1, c, M)
        else:
            part = coll.wire_all_gather_stacked(
                mine, tuple(info.mp_axes), Nm, comm, axis=1)
        return part.reshape(mine.shape[0], -1, part.shape[-1])

    if kind == "combine":
        buf, (g, cap) = vals
        flat = _placed_flat(ctx, g, cap) if ctx.placed is not None \
            else g.flat(cap, E, info.n_held)
        return combine(buf, g.expert_idx, g.slot_idx, g.weights, cap,
                       info.kernel, flat=flat)

    if kind == "slice":
        i, n = st.p("index"), st.p("n")
        axis = st.p("axis", 1)
        cs = vals[0].shape[axis] // n
        return lax.slice_in_dim(vals[0], i * cs, (i + 1) * cs, axis=axis)

    if kind == "merge":
        axis = st.p("axis", 1)
        if st.p("mode", "concat") == "concat":
            return (vals[0] if len(vals) == 1
                    else jnp.concatenate(vals, axis=axis))
        # stack_mp: parts are (E|R, Nm*cs, M); restore the legacy
        # (mp_rank, chunk, slot) capacity order of the pre-split buffer.
        parts = [p.reshape(p.shape[0], Nm, -1, p.shape[-1]) for p in vals]
        stacked = jnp.stack(parts, axis=2)       # (E, Nm, n, cs, M)
        return stacked.reshape(stacked.shape[0], -1, stacked.shape[-1])

    raise ValueError(f"executor: unknown stage kind {kind!r}")


def _emit_grouped(st, vals, ctx):
    """Lower an ``expert_ffn_grouped`` stage (``plan.fuse_grouped``).

    Pool form (deps: the dispatch-A2A receive buffer): exchange the
    per-(expert, sender) routed-row counts over the same combined group
    — a tiny (El, G) int32 AlltoAll — and run the ragged grouped-GEMM
    kernel: token tiles beyond a group's routed count never reach the
    MXU, so compute scales with routed tokens, not capacity.  When the
    surrounding AlltoAlls run ``raw`` the buffer arrives in the wire
    dtype; the kernel's f32 upcast and output cast are the fused codec.

    Local form (``local=True``; deps: token slice + gate): one fused
    megakernel doing dispatch gather -> ragged FFN -> combine scatter +
    gate-weight mix, with the wire round-trip applied at the two pool
    boundaries.  fp8's scale-tail codec cannot fuse, so it composes the
    unfused ops around explicit :func:`collectives.wire_roundtrip`.
    """
    info = ctx.info
    E, Eh = info.gate.n_experts, info.held
    Ne, Ns = info.n_ep, info.n_esp
    comm = ctx.comm if st.wire else None

    if st.p("local"):
        tokens, (g, cap) = vals
        flat = g.flat(cap, E, info.n_held)
        wd = getattr(comm, "wire_dtype", "f32") if comm is not None \
            else "f32"
        if coll.wire_raw_ok(comm):
            op = get_op("expert_ffn_grouped", cfg=info.kernel,
                        act=info.act, cap=cap, wire=wd)
            return op(tokens, flat, g.weights, ctx.w1,
                      ctx.w3 if info.glu else None, ctx.w2)
        # fp8 wire: compose the unfused ops around the codec round-trip
        # (bit-identical to the pool path's encode/decode at both
        # boundaries; the FFN itself stays ragged/dropless)
        d = dispatch(tokens, g.expert_idx, g.slot_idx, cap, Eh,
                     info.kernel, flat=flat)             # (Eh, cap, M)
        d = coll.wire_roundtrip(d, comm)
        load = g.aux["load"] if Eh == E else g.aux["load"][:Eh]
        cnt = jnp.minimum(load, cap).astype(jnp.int32)[:, None]
        op = get_op("expert_ffn_ragged", cfg=info.kernel, act=info.act)
        h = op(d.reshape(Eh, 1, cap, -1), cnt, ctx.w1,
               ctx.w3 if info.glu else None, ctx.w2)
        h = coll.wire_roundtrip(h.reshape(Eh, cap, -1), comm)
        return combine(h, g.expert_idx, g.slot_idx, g.weights, cap,
                       info.kernel, flat=flat)

    h = vals[0]                                  # (El, G*c, M), maybe raw
    g, cap = ctx.gate
    G = info.combined_group
    El, Gc, M = h.shape
    c = Gc // G
    # This chunk covers capacity slots [ci*c, (ci+1)*c) of every expert;
    # GShard slots are contiguous from 0, so the chunk's routed rows per
    # expert are clip(routed - ci*c, 0, c).
    ci = st.p("chunk_index", 0)
    if ctx.placed is not None:
        # placed: rows of logical expert e land round-robin on its
        # replicas, so physical slot p (replica j of expert a_p) holds
        # ceil((routed_a - j) / r_a) rows, contiguous from 0
        t = ctx.placed
        eff = (t.rep_count * cap).astype(jnp.float32)
        routed = jnp.minimum(g.aux["load"], eff).astype(jnp.int32)
        r = t.rep_count[t.assign]
        cnt_p = jnp.clip((routed[t.assign] - t.rep_index + r - 1) // r,
                         0, cap)                                 # (R,)
        cnt = jnp.clip(cnt_p - ci * c, 0, c)
        nl = t.n_phys // Ne                      # local phys slots/rank
    else:
        load = g.aux["load"] if Eh == E else g.aux["load"][:Eh]
        routed = jnp.minimum(load, float(cap)).astype(jnp.int32)
        cnt = jnp.clip(routed - ci * c, 0, c)                    # (Eh,)
        nl = Eh // Ne
    # Receive-side ragged metadata: sender g' = (i', j') delivered its
    # rows for OUR local expert el, so the valid-row count of block
    # rb[el, g'] is g''s routed count for global expert i*El + el —
    # exchanged with the dump_em-layout (El, G) counts AlltoAll.
    snd = jnp.broadcast_to(cnt.reshape(Ne, nl).T[:, :, None],
                           (nl, Ne, Ns)).reshape(nl, G)
    rcv = coll.ep_esp_all_to_all(snd, info.ep_axes, info.esp_axes,
                                 split_axis=1, concat_axis=1)   # (El, G)
    op = get_op("expert_ffn_ragged", cfg=info.kernel, act=info.act)
    out = op(h.reshape(El, G, c, M), rcv, ctx.w1,
             ctx.w3 if info.glu else None, ctx.w2)
    return out.reshape(El, Gc, M)


def execute(plan: Plan, x, wg, w1, w3, w2, info):
    """Run one MoE layer under ``plan`` (shard_map side).

    Same contract as the legacy schedule bodies: ``x`` is this device's
    (S, M) token slice, returns ``(y, aux)`` with aux scalars pmean-ed
    over the full device group.  Under a placed plan
    (``plan.placement``) the expert weights must already be the placed
    physical gather ``w[placement.assignments]`` — ``apply_moe`` does
    this outside the shard_map, and its take-VJP sums replica weight
    gradients (the placement's "summed combine").
    """
    order = validate(plan)
    ctx = _Ctx(info, wg, w1, w3, w2, getattr(plan, "comm", None), x.dtype,
               placement=getattr(plan, "placement", None))
    env = {INPUT: x}
    for st in order:
        # named_scope is trace-time metadata only (names the HLO ops for
        # profilers / dumped modules); the lowered program is unchanged.
        with jax.named_scope(f"{plan.name}.{st.name}"):
            env[st.name] = _emit(st, [env[d] for d in st.deps], ctx)
    if ctx.gate is None:
        raise ValueError(f"plan {plan.name!r} has no gate stage")
    g, _ = ctx.gate
    return env[plan.output], _aux_mean(g.aux, info)


def _probe(v):
    """DCE-proof scalar fingerprint of one stage value."""
    if isinstance(v, tuple):             # gate stage: (GateResult, cap)
        return jnp.sum(v[0].weights.astype(jnp.float32))
    return jnp.sum(v.astype(jnp.float32))


def execute_prefix(plan: Plan, x, wg, w1, w3, w2, info, n_stages: int):
    """Run only the first ``n_stages`` stages of ``plan`` (topo order)
    and return a replicated scalar folding a probe of every stage
    output (so no stage is dead code).

    The obs stage-timing harness (``repro.obs.trace``) times the jitted
    prefix programs for k = 0..n and attributes ``t[k] - t[k-1]`` to
    stage k.  Validated topo order lists every stage after its deps, so
    any prefix is a closed subgraph; stateful context (the gate result)
    is always populated before a consumer runs.
    """
    order = validate(plan)
    ctx = _Ctx(info, wg, w1, w3, w2, getattr(plan, "comm", None), x.dtype,
               placement=getattr(plan, "placement", None))
    env = {INPUT: x}
    acc = jnp.sum(x.astype(jnp.float32))
    for st in order[:n_stages]:
        with jax.named_scope(f"{plan.name}.{st.name}"):
            env[st.name] = _emit(st, [env[d] for d in st.deps], ctx)
        acc = acc + _probe(env[st.name])
    axes = tuple(dict.fromkeys(info.ep_axes + info.esp_axes
                               + info.mp_axes))
    return lax.psum(acc, axes)
