"""The MoE layer: Parm's schedules as a first-class, composable module.

``apply_moe`` is the public entry point used by every model definition.
It wires the schedule bodies (repro.core.schedules + the chunk-pipelined
variants in repro.core.pipeline) into a shard_map over the caller's mesh,
handles the decode-time fallback when the token count cannot be sharded
over the EP axes, computes capacities, and — when ``schedule="auto"``
and/or ``CommConfig.wire_dtype="auto"`` — consults the autoscheduler
(repro.core.autosched) for the per-layer (schedule, n_chunks,
wire_dtype) decision, analytically or from a one-shot measured
calibration.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace as _dc_replace
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

import itertools

from repro import obs
from repro.core import autosched, executor
from repro.core import plan as planlib
from repro.core.collectives import CommConfig
from repro.core.gating import GateConfig, capacity
from repro.core.perfmodel import MoELayerShape, PerfModel, tpu_v5e_model
from repro.core.pipeline import PIPELINE_OF, UNCHUNKED_OF, clamp_chunks
from repro.core.schedules import BODY, MoEShardInfo, expert_ffn
from repro.kernels.registry import KernelConfig
from repro.parallel.mesh import ParallelDims, axis_size


@dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                     # per-expert hidden size
    n_experts: int                # the router's width (E of the capacity)
    top_k: int = 1
    capacity_factor: float = 1.25
    n_shared_experts: int = 0     # llama4-style shared expert(s)
    glu: bool = True              # SwiGLU experts
    normalize_topk: bool = False
    aux_loss_weight: float = 1e-2
    z_loss_weight: float = 1e-3
    schedule: str = "auto"        # baseline | s1 | s2 | s1_seqpar | s2h |
    #   *_pipe | auto — or any schedule registered via plan.register_plan
    saa_chunks: int = 4
    pipeline_chunks: int = 1      # micro-chunks for the *_pipe bodies (1 = off)
    autosched: str = "analytic"   # "auto" decision mode: analytic | measured
    act: str = "silu"             # expert activation ("silu" | "gelu")
    kernel: KernelConfig = KernelConfig()  # hot-path op backend + tiles
    comm: CommConfig = CommConfig()  # collective wire format (f32 default;
    #   wire_dtype="auto" lets the autoscheduler pick f32-vs-bf16 jointly
    #   with (schedule, n_chunks); fp8_e4m3 must be requested explicitly)
    placement: object = None      # expert placement: None (uniform) |
    #   "auto" (read the live placement from the autosched registry at
    #   trace time — the rebalance loop's swap point) | a concrete
    #   ExpertPlacement (forced, e.g. the parity tests)
    n_held: Optional[int] = None  # experts held here, [0, n_held): one
    #   chip's share of a layer whose other experts live on chips that are
    #   not there (routes to them take no slot and add nothing); None = all

    @property
    def held(self) -> int:
        return self.n_experts if self.n_held is None else self.n_held

    def gate_config(self) -> GateConfig:
        return GateConfig(
            n_experts=self.n_experts, top_k=self.top_k,
            capacity_factor=self.capacity_factor,
            normalize_topk=self.normalize_topk,
            aux_loss_weight=self.aux_loss_weight,
            z_loss_weight=self.z_loss_weight)


def init_moe_params(key, cfg: MoEConfig, dtype=jnp.float32) -> dict:
    """The router over all ``n_experts``; the weights of the ``held``
    experts only."""
    M, F, E = cfg.d_model, cfg.d_ff, cfg.held
    ks = jax.random.split(key, 6)
    scale_in = 1.0 / math.sqrt(M)
    scale_out = 1.0 / math.sqrt(F)
    p = {
        "wg": jax.random.normal(ks[0], (M, cfg.n_experts),
                                jnp.float32) * scale_in,
        "w1": jax.random.normal(ks[1], (E, M, F), dtype) * scale_in,
        "w2": jax.random.normal(ks[2], (E, F, M), dtype) * scale_out,
    }
    if cfg.glu:
        p["w3"] = jax.random.normal(ks[3], (E, M, F), dtype) * scale_in
    if cfg.n_shared_experts:
        Fs = F * cfg.n_shared_experts
        p["shared_w1"] = jax.random.normal(ks[4], (M, Fs), dtype) * scale_in
        p["shared_w3"] = jax.random.normal(ks[5], (M, Fs), dtype) * scale_in
        p["shared_w2"] = (jax.random.normal(key, (Fs, M), dtype)
                          * (1.0 / math.sqrt(Fs)))
    return p


def moe_param_specs(cfg: MoEConfig, mesh, dims: ParallelDims) -> dict:
    """PartitionSpecs: experts over EP, hidden over ESP, gate replicated."""
    def ep_ok(n):
        return dims.ep and n % axis_size(mesh, dims.ep) == 0

    def esp_ok(n):
        return dims.esp and n % axis_size(mesh, dims.esp) == 0

    E, F, M = cfg.held, cfg.d_ff, cfg.d_model
    e_ax = tuple(dims.ep) if ep_ok(E) else None
    f_ax = tuple(dims.esp) if esp_ok(F) else None
    specs = {
        "wg": P(None, None),
        "w1": P(e_ax, None, f_ax),
        "w2": P(e_ax, f_ax, None),
    }
    if cfg.glu:
        specs["w3"] = P(e_ax, None, f_ax)
    if cfg.n_shared_experts:
        mp_ax = tuple(dims.mp) if dims.mp and (
            F * cfg.n_shared_experts) % axis_size(mesh, dims.mp) == 0 else None
        specs["shared_w1"] = P(None, mp_ax)
        specs["shared_w3"] = P(None, mp_ax)
        specs["shared_w2"] = P(mp_ax, None)
    return specs


def shard_pool_capacity(tokens_global: int, n_token_shard: int, n_mp: int,
                        gate_cfg: GateConfig, infer: bool = False):
    """(s_local, cap) for one device's token pool — THE capacity formula.

    ``s_local`` is the per-shard pool (``tokens_global`` split over the
    token-shard group: batch axes, plus MP under the seqpar contract);
    ``cap`` is the per-expert capacity aligned to ``max(8, n_mp)`` so the
    S1/S2 capacity splits stay divisible.  ``apply_moe`` computes its
    capacities through this helper and ``launch/dryrun.py`` mirrors it,
    so the recorded decisions/plans match what actually compiles.

    ``infer=True`` (decode-time pools) raises ``cap`` to cover the whole
    pool: a decode batch mixes live requests with idle padding rows, and
    Parm-style capacity drops would let one request's token be displaced
    by batch *composition* — with ``cap >= pool`` every token always has
    a slot, so a row's decode output is independent of its batch mates
    (the invariant the serving engine's parity tests pin down).  The
    memory cost is E * pool * M, negligible at decode sizes.
    """
    s_local = tokens_global // max(n_token_shard, 1)
    align = max(8, n_mp)
    cap = max(align, -(-capacity(max(s_local, 1), gate_cfg)
                       // align) * align)
    if infer:
        cap = max(cap, -(-max(s_local, 1) // align) * align)
    return s_local, cap


_TRACE_ORDINAL = itertools.count()  # apply_moe call ordinal (trace tag)


# --- decode fallback ---------------------------------------------------------

def _replicated_body(x, wg, w1, w3, w2, info: MoEShardInfo):
    """All-reduce-based MoE for tiny token counts (decode with B < EP size):
    tokens stay replicated, each device computes its local experts masked by
    the routing, and a psum over (EP, ESP) assembles the output."""
    El = w1.shape[0]
    gate = info.gate
    logits = jnp.asarray(x, jnp.float32) @ jnp.asarray(wg, jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, eidx = lax.top_k(probs, gate.top_k)                 # (S, k)
    if gate.normalize_topk:
        gate_w = gate_w / jnp.sum(gate_w, axis=-1, keepdims=True)
    ep_idx = lax.axis_index(info.ep_axes) if info.ep_axes else 0
    gids = ep_idx * El + jnp.arange(El)                         # (El,)
    sel = (eidx[:, :, None] == gids[None, None, :]).astype(x.dtype)
    wsel = jnp.einsum("sk,ske->se", gate_w.astype(x.dtype), sel)  # (S, El)
    xb = jnp.broadcast_to(x[None], (El, *x.shape))              # (El, S, M)
    h = expert_ffn(xb, w1, w3, w2, info)                        # partial
    y = jnp.einsum("esm,se->sm", h, wsel)
    red = tuple(dict.fromkeys(info.ep_axes + info.esp_axes))
    if red:
        y = lax.psum(y, red)
    aux = {"aux_loss": jnp.float32(0.0), "z_loss": jnp.float32(0.0),
           "drop_frac": jnp.float32(0.0)}
    return y, aux


# --- public entry ------------------------------------------------------------

def select_schedule(cfg: MoEConfig, shape: MoELayerShape,
                    perf_model: Optional[PerfModel] = None) -> str:
    """Schedule name for one layer shape (no chunk count; see
    ``autosched.decide`` for the full (schedule, n_chunks) decision)."""
    if cfg.schedule != "auto":
        return cfg.schedule
    pm = perf_model or tpu_v5e_model(shape.n_ep, shape.n_esp, shape.n_mp)
    return autosched.decide(shape, perf_model=pm).schedule


def apply_moe(x, params: dict, *, mesh, dims: ParallelDims, cfg: MoEConfig,
              schedule: Optional[str] = None,
              perf_model: Optional[PerfModel] = None,
              infer: bool = False):
    """Run one MoE layer under the configured Parm schedule.

    x: (B, L, M) activations; replicated over MP axes (or MP-split over
    them under the ``s1_seqpar`` contract).  Returns (y, aux).

    ``infer=True`` marks a decode-time call (``decode_block``): the
    layer shape joins the *decode* shape class — its own autosched cache
    entries, the decode-widened schedule grid (``s1d``), no capacity
    chunking, and drop-free capacity (``shard_pool_capacity``).
    """
    B, L, M = x.shape
    sizes = dims.sizes(mesh)
    n_ep, n_esp, n_mp = sizes["ep"], sizes["esp"], sizes["mp"]
    gate_cfg = cfg.gate_config()

    if n_ep > 1 and cfg.n_experts % n_ep:
        raise ValueError(f"E={cfg.n_experts} not divisible by EP={n_ep}")
    if n_esp > 1 and cfg.d_ff % n_esp:
        raise ValueError(f"d_ff={cfg.d_ff} not divisible by ESP={n_esp}")
    if not 0 < cfg.held <= cfg.n_experts:
        raise ValueError(f"n_held={cfg.n_held} not in [1, {cfg.n_experts}]")
    if cfg.held < cfg.n_experts and (n_ep > 1 or n_esp > 1):
        raise ValueError(f"a share of {cfg.held} of {cfg.n_experts} experts "
                         f"runs without the exchange: EP={n_ep}, "
                         f"ESP={n_esp}")

    tokens_global = B * L
    batch_ax = dims.batch_axes
    n_batch = axis_size(mesh, batch_ax)

    sched = schedule or cfg.schedule
    n_chunks = max(cfg.pipeline_chunks, 1)
    seqpar = sched in ("s1_seqpar", "s1_seqpar_pipe")
    token_shard = batch_ax + (dims.mp if seqpar else ())
    n_token_shard = axis_size(mesh, token_shard)

    s_local, cap = shard_pool_capacity(tokens_global, n_token_shard,
                                       n_mp, gate_cfg, infer=infer)
    obs.emit("moe.share", n_experts=cfg.n_experts, n_held=cfg.held,
             cap=cap)
    divisible = (tokens_global % max(n_token_shard, 1) == 0
                 and (seqpar or s_local % max(n_mp, 1) == 0)
                 and s_local > 0)
    use_fallback = (not divisible) or s_local < n_mp

    comm = cfg.comm or CommConfig()
    wire = comm.wire_dtype
    if use_fallback:
        sched = "dense_decode"
        wire = "f32" if wire == "auto" else wire  # psum-only body: no wire
    elif sched == "auto" or wire == "auto":
        shape = MoELayerShape(
            B=max(s_local // max(L, 1), 1), L=min(L, s_local), M=M,
            H=cfg.d_ff, E=cfg.n_experts, k=cfg.top_k,
            f=cfg.capacity_factor, n_mp=n_mp, n_esp=n_esp, n_ep=n_ep,
            infer=infer)
        # Only score chunk counts the bodies can actually run: every
        # schedule's chunked dim is a multiple of cap/N_MP, so clamping
        # against it keeps scored == executed (and dedups candidates).
        # Decode pools never chunk: the per-chunk alphas dominate at a
        # handful of tokens, so the decode grid is pinned to n_chunks=1.
        cands = ((1,) if infer else
                 tuple(sorted({clamp_chunks(cap // max(n_mp, 1), n)
                               for n in autosched.DEFAULT_CHUNKS})))
        # A forced schedule with wire="auto" restricts the decision to
        # that schedule (and the forced chunk count): only the wire axis
        # is still free.
        forced = None
        if sched != "auto":
            forced = (UNCHUNKED_OF.get(sched, sched),)
            cands = (clamp_chunks(cap // max(n_mp, 1), n_chunks),)
        wire_cands = (autosched.AUTO_WIRE if wire == "auto" else (wire,))
        # tokens_global: the nested apply_moe re-shards over the same
        # batch axes, so candidates are timed at the true per-device pool.
        measure = (autosched.measure_candidates(
            mesh, dims, cfg, tokens=tokens_global, d_model=M)
            if cfg.autosched == "measured" else None)
        decision = autosched.decide(shape, perf_model=perf_model,
                                    mode=cfg.autosched,
                                    chunk_candidates=cands,
                                    wire_candidates=wire_cands,
                                    schedules=forced, measure=measure)
        if sched == "auto":
            sched, n_chunks = decision.schedule, decision.n_chunks
        wire = decision.wire_dtype if wire == "auto" else wire
    # guard-rail wire ceiling (fp8 overflow fallback): clamp the resolved
    # wire up to the process-wide floor width, if one is set.  Applied
    # after auto/forced resolution so it covers both paths; a no-op
    # (identity) when no ceiling is active.
    wire = autosched.clamp_wire(wire)
    if not use_fallback and n_chunks > 1 and sched in PIPELINE_OF:
        # route chunked requests to the pipelined body of the same schedule
        sched = PIPELINE_OF[sched]

    # Expert placement: "auto" reads the live rebalanced placement from
    # the autosched registry at trace time (the Trainer/Engine re-jit
    # after autosched.set_placement, so the swap needs no config churn).
    # A placement only applies when there is an EP group to remap over
    # and its geometry matches this layer; the decode fallback body
    # computes densely and ignores it.
    pl = cfg.placement
    if pl == "auto":
        pl = autosched.current_placement()
    if pl is not None and (use_fallback or n_ep <= 1
                           or pl.n_experts != cfg.n_experts
                           or pl.n_ep != n_ep):
        pl = None
    if pl is not None and infer and pl.cap_frac < 1.0:
        # decode pools are drop-free by contract (shard_pool_capacity
        # raises cap to cover the pool); keep the replication but not
        # the capacity shrink, so r_e * cap >= pool always holds
        pl = _dc_replace(pl, cap_frac=1.0)

    info = MoEShardInfo(
        ep_axes=tuple(dims.ep), esp_axes=tuple(dims.esp),
        mp_axes=tuple(dims.mp), n_ep=n_ep, n_esp=n_esp, n_mp=n_mp,
        tokens=s_local, cap=cap, gate=gate_cfg, act=cfg.act, glu=cfg.glu,
        saa_chunks=cfg.saa_chunks, pipeline_chunks=n_chunks,
        kernel=cfg.kernel,
        comm=CommConfig(wire_dtype=wire, scaling=comm.scaling),
        placement=pl, n_held=cfg.n_held)

    if sched == "dense_decode":
        body = _replicated_body
    else:
        body = BODY.get(sched)
    if body is None:
        # A schedule registered via plan.register_plan but without a BODY
        # alias (the docs' "add a schedule" path): execute its plan
        # directly, chunked per info.pipeline_chunks.  Registration alone
        # is enough to be selectable — by name or by the auto grids.
        base = UNCHUNKED_OF.get(sched, sched)
        if base not in planlib.PLANS:
            raise KeyError(f"unknown schedule {sched!r}: not in "
                           f"schedules.BODY nor the plan registry "
                           f"(have {sorted(set(BODY) | set(planlib.PLANS))})")

        def body(xt, wg, w1, w3_, w2, info, _base=base):
            return executor.execute(planlib.build_plan(_base, info),
                                    xt, wg, w1, w3_, w2, info)
    if pl is not None:
        # Placed-weight gather: physical slot p computes logical expert
        # assignments[p].  Done outside the shard_map so the take-VJP
        # scatter-adds replica weight gradients back into the logical
        # parameters — the placement's "summed combine" for weights.
        # (R, M, F) shards over the same P(ep, ...) specs: R % n_ep == 0.
        idx = jnp.asarray(pl.assignments, jnp.int32)
        gathered = {k: jnp.take(params[k], idx, axis=0)
                    for k in ("w1", "w2", "w3") if params.get(k) is not None}
        params = dict(params, **gathered)
    pspecs = moe_param_specs(cfg, mesh, dims)
    w3 = params.get("w3")
    if w3 is None:
        # non-GLU experts have no w3: ship a zero-size replicated stand-in
        # instead of aliasing w1 into a dead (sharded, transferred) operand.
        w3 = jnp.zeros((0,), x.dtype)
        w3_spec = P(None)
    else:
        w3_spec = pspecs["w3"]

    x_spec = (P(tuple(token_shard) or None, None) if not use_fallback
              else P(None, None))
    in_specs = (x_spec, pspecs["wg"], pspecs["w1"], w3_spec, pspecs["w2"])
    out_specs = (x_spec, {k: P() for k in
                          ("aux_loss", "z_loss", "drop_frac",
                           "expert_load")})

    def shard_body(xt, wg, w1, w3_, w2):
        y, aux = body(xt, wg, w1, w3_ if cfg.glu else None, w2, info)
        # per-expert routed-row counts, averaged over the per-device gate
        # pools (replicated so the P() out_spec holds); the decode
        # fallback body has no capacity buffer, hence no routed counts
        routed = aux.get("routed",
                         jnp.zeros((cfg.n_experts,), jnp.float32))
        aux = {k: aux[k] for k in ("aux_loss", "z_loss", "drop_frac")}
        aux["expert_load"] = lax.pmean(routed, tuple(mesh.axis_names))
        return y.astype(x.dtype), aux

    xt = x.reshape(tokens_global, M)
    # trace-time telemetry tags: runtime events whose callbacks are
    # built while tracing this layer (the fp8 saturation monitor) carry
    # which apply_moe call / schedule / wire they belong to.
    with obs.trace_tag(moe_call=next(_TRACE_ORDINAL), schedule=sched,
                       wire=wire):
        y, aux = jax.shard_map(
            shard_body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False)(xt, params["wg"], params["w1"], w3,
                             params["w2"])
    y = y.reshape(B, L, M)

    if cfg.n_shared_experts:
        h = jnp.einsum("blm,mf->blf", x, params["shared_w1"])
        h = jax.nn.silu(h) * jnp.einsum("blm,mf->blf", x, params["shared_w3"])
        y = y + jnp.einsum("blf,fm->blm", h, params["shared_w2"])
    return y, aux
