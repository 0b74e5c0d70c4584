"""Train / prefill / serve step factories + the Trainer driver.

These are the functions the multi-pod dry-run lowers and the launchers
execute: ``train_step`` (fwd+bwd+AdamW), ``prefill_fn`` (full-sequence
forward) and ``serve_step`` (one token against a KV cache, with greedy
sampling) — plus the serving engine's two steps
(``make_engine_prefill_step`` / ``make_engine_decode_step``: paged-arena
scatter/gather through page tables, per-row positions, per-row
sampling).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.models.model import Model
from repro.optim.adamw import (AdamWConfig, adamw_init, adamw_update,
                               opt_state_specs)
from repro.parallel.mesh import ParallelDims, axis_size


def named_tree(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def batch_specs(model: Model, mesh, dims: ParallelDims, kind: str) -> dict:
    """PartitionSpecs for a batch dict (dim 0 over batch axes if divisible)."""
    axes = dims.dp + dims.ep if (dims.merged or not dims.esp) \
        else dims.dp + dims.ep + dims.esp

    def bspec(ndim, batch_size=None):
        ax = tuple(axes) if axes and (
            batch_size is None or batch_size % axis_size(mesh, axes) == 0) \
            else None
        return P(*((ax,) + (None,) * (ndim - 1)))
    return bspec


def cache_specs(model: Model, mesh, dims: ParallelDims, batch: int,
                max_len: int, *, seq_shard: bool = False):
    """Specs for the decode cache: batch dim (axis 1, after the layer-stack
    axis) sharded over the batch axes when divisible.

    ``seq_shard=True`` additionally shards attention K/V caches along the
    cache-length dim over the MP axes (context-parallel decode — the
    beyond-paper §Perf lever for collective/memory-bound decode shapes)."""
    axes = tuple(dims.batch_axes)
    n = axis_size(mesh, axes) if axes else 1
    mp = tuple(dims.mp)
    n_mp = axis_size(mesh, mp) if mp else 1
    shapes = jax.eval_shape(lambda: model.init_cache(batch, max_len))

    def rule(leaf):
        spec = [None] * leaf.ndim
        batch_shardable = batch % n == 0 and batch >= n and axes
        if leaf.ndim >= 2 and leaf.shape[1] == batch and batch_shardable:
            spec[1] = axes
        if seq_shard and mp and leaf.ndim == 5:
            # (layers, B, W, K, hd) attention cache: shard W over MP, and
            # when the batch axes are idle (B < their size, e.g. B=1
            # long-context serving) over those too — full context
            # parallelism across the pod (§Perf C6).
            waxes = mp if batch_shardable else tuple(axes) + tuple(mp)
            nw = axis_size(mesh, waxes)
            if leaf.shape[2] % nw == 0 and leaf.shape[2] >= 16 * nw:
                spec[2] = waxes
        return P(*spec)

    return jax.tree.map(rule, shapes)


# --- step factories -----------------------------------------------------------

def make_train_step(model: Model, mesh, dims: ParallelDims,
                    opt_cfg: AdamWConfig, schedule: Optional[str] = None):
    def train_step(params, opt_state, batch):
        def loss_fn(p):
            return model.loss(p, batch, mesh=mesh, dims=dims,
                              schedule=schedule)
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        params2, opt_state2, om = adamw_update(params, grads, opt_state,
                                               opt_cfg)
        return params2, opt_state2, {**metrics, **om, "loss": loss}
    return train_step


def make_guarded_train_step(model: Model, mesh, dims: ParallelDims,
                            opt_cfg: AdamWConfig,
                            schedule: Optional[str] = None):
    """``make_train_step`` wrapped in guard rails, one compilation.

    Signature grows two traced scalars: ``lr_scale`` (the guard rails'
    dynamic LR backoff — multiplies the scheduled LR inside
    ``adamw_update``) and ``grad_fault`` (fault injection: the loss is
    seeded as ``loss * (1 + grad_fault)`` so every gradient comes out
    scaled by ``1 + grad_fault`` through the chain rule — one scalar
    multiply instead of a per-leaf pass; 0.0 is the exact identity and
    NaN/inf poisons every gradient).  The update is computed
    unconditionally and *discarded leaf-wise* when the loss or the
    global grad norm (already computed by AdamW for clipping — no second
    O(N) pass) goes non-finite: the ``where(finite, new, old)`` select
    runs *inside* ``adamw_update``'s per-leaf expression (where XLA
    fuses it with the update writes — a post-hoc tree-select measurably
    does not fuse and costs an extra memory pass), covering params, both
    moments, and the step counter, so a skipped step leaves the
    optimizer bit-identical to never having run.  Metrics gain a
    ``nonfinite`` flag the host-side policy (``runtime.guards``) folds
    into its skip/rollback decision.

    On the clean path (``lr_scale=1.0, grad_fault=0.0, finite=True``)
    every extra op is an IEEE identity, so outputs are bitwise equal to
    the unguarded step (tests/test_runtime.py locks this down).
    """
    def train_step(params, opt_state, batch, lr_scale, grad_fault):
        def loss_fn(p):
            loss, metrics = model.loss(p, batch, mesh=mesh, dims=dims,
                                       schedule=schedule)
            return loss * (1.0 + grad_fault), metrics
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        params2, opt_state2, om = adamw_update(params, grads, opt_state,
                                               opt_cfg, lr_scale=lr_scale,
                                               finite=jnp.isfinite(loss))
        finite = om.pop("finite")
        return params2, opt_state2, {**metrics, **om, "loss": loss,
                                     "nonfinite": ~finite}
    return train_step


def make_prefill_fn(model: Model, mesh, dims: ParallelDims,
                    schedule: Optional[str] = None):
    def prefill(params, batch):
        logits, aux = model.forward(params, batch, mesh=mesh, dims=dims,
                                    schedule=schedule)
        return logits

    return prefill


def make_serve_step(model: Model, mesh, dims: ParallelDims,
                    schedule: Optional[str] = None, greedy: bool = True):
    """Cross-attention archs (VLM/audio) take the per-request precomputed
    context K/V as a fourth argument (built once via model.ctx_kv)."""
    if model.has_cross:
        def serve_step(params, cache, batch, ctx_kv):
            logits, cache2 = model.decode_step(
                params, cache, batch, mesh=mesh, dims=dims,
                schedule=schedule, ctx_kv=ctx_kv)
            next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return next_tok[:, None], cache2
        return serve_step

    def serve_step(params, cache, batch):
        logits, cache2 = model.decode_step(params, cache, batch,
                                           mesh=mesh, dims=dims,
                                           schedule=schedule)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok[:, None], cache2

    return serve_step


def make_engine_prefill_step(model: Model, mesh, dims: ParallelDims,
                             schedule: Optional[str] = None):
    """The serving engine's prefill step over the PAGED block arena: one
    jitted call per admitted group (or per prefill chunk) — batched
    forward over each row's token span at ``starts``, written into the
    arena through ``tables``, then sampling at each row's own final
    valid position.  (Never a per-token loop: the regression test in
    tests/test_serve.py counts calls per group/chunk.)
    """
    def prefill_step(params, arena, tokens, starts, lens, tables, keys,
                     temps, topks):
        from repro.serve.sampler import sample   # lazy: no train<->serve cycle
        logits, arena2 = model.paged_step(
            params, arena,
            {"tokens": tokens, "starts": starts, "lens": lens,
             "tables": tables},
            mesh=mesh, dims=dims, schedule=schedule, infer=False)
        return sample(logits, keys, temps, topks), arena2

    return prefill_step


def make_engine_decode_step(model: Model, mesh, dims: ParallelDims,
                            schedule: Optional[str] = None,
                            with_aux: bool = False):
    """The serving engine's decode step over the PAGED block arena: one
    token per row at per-row positions (``steps`` is a (B,) vector, so
    requests at different depths batch together), reading/writing
    through fixed-shape ``(B, max_blocks)`` page tables — one
    compilation no matter how requests come and go.  Idle rows carry an
    all-null table: their writes land in the masked null page and their
    outputs are ignored.

    ``with_aux=True`` returns a third output — the (E,) per-expert
    routed-row count for this round ((0,) for dense stacks), feeding the
    engine's load EMA; the default keeps the two-output signature every
    existing caller jits.
    """
    def decode_step(params, arena, tokens, steps, tables, keys, temps,
                    topks):
        from repro.serve.sampler import sample
        out = model.paged_step(
            params, arena,
            {"tokens": tokens, "starts": steps,
             "lens": jnp.ones_like(steps), "tables": tables},
            mesh=mesh, dims=dims, schedule=schedule, infer=True,
            with_aux=with_aux)
        if with_aux:
            logits, arena2, aux = out
            return (sample(logits, keys, temps, topks), arena2,
                    aux["expert_load"])
        logits, arena2 = out
        return sample(logits, keys, temps, topks), arena2

    return decode_step


# --- driver ---------------------------------------------------------------------

@dataclass
class Trainer:
    """End-to-end training driver (used by examples/ and launch/train.py).

    ``guards`` (a :class:`repro.runtime.guards.GuardConfig`) opts into
    the fault-tolerant loop: the guarded step (skip-step + LR backoff),
    retained-checkpoint rollback through ``ckpt_path`` (kept to
    ``ckpt_retain`` files), the fp8 wire-overflow fallback, and the
    ``faults`` injection hooks.  With ``guards=None`` (default) setup
    and run are byte-for-byte the pre-existing paths.

    ``placement="auto"`` + ``rebalance_every=N`` opts into load-adaptive
    expert placement: the per-expert ``expert_load`` metric feeds a
    rolling EMA every step, and every N steps the skew-aware cost model
    scores a replication placement derived from the EMA against uniform
    (``autosched.maybe_rebalance``); on a win the placement is installed
    process-wide and the step re-jitted — the same cheap plan-swap
    mechanism as the fp8 wire fallback (the MoE config must route
    ``placement="auto"`` for the retrace to pick it up, which
    launch/train.py --placement auto arranges).
    """
    model: Model
    mesh: object
    dims: ParallelDims
    opt_cfg: AdamWConfig
    schedule: Optional[str] = None
    ckpt_path: Optional[str] = None
    guards: Optional[object] = None       # runtime.guards.GuardConfig
    faults: Optional[object] = None       # runtime.faults.FaultPlan
    ckpt_retain: int = 3
    placement: Optional[str] = None       # None (uniform) | "auto"
    rebalance_every: int = 0              # steps between rebalance checks
    rebalance_margin: float = 1.05        # modeled win required to swap

    def setup(self, key):
        """Make the parameters and the optimizer state on the mesh from
        ``key`` and build the step; the phase ``setup.init``."""
        with obs.phase("setup.init"):
            m, mesh, dims = self.model, self.mesh, self.dims
            pspecs = m.specs(mesh, dims)
            p_sh = named_tree(mesh, pspecs)
            o_sh = named_tree(mesh, opt_state_specs(pspecs))
            params = jax.jit(m.init, out_shardings=p_sh)(key)
            opt_state = jax.jit(adamw_init, out_shardings=o_sh)(params)
            self._p_sh, self._o_sh = p_sh, o_sh
            from repro.core.placement import LoadEMA
            self.load_ema = LoadEMA()
            if self.guards is None:
                self._step_fn = make_train_step(m, mesh, dims, self.opt_cfg,
                                                self.schedule)
                self._step = jax.jit(self._step_fn, donate_argnums=(0, 1))
            else:
                from repro.runtime import guards as guardlib
                self.guard_state = guardlib.GuardState(cfg=self.guards)
                guardlib.reset_fp8_counter()
                # monitor installed BEFORE the jit below traces, so fp8
                # encodes in this step's program carry the saturation counter
                guardlib.enable_fp8_monitor()
                if self.faults:
                    factor = self.faults.fp8_sat_factor()
                    if factor:
                        from repro.core import collectives
                        collectives.set_fp8_sat_injection(factor)
                self._step_fn = make_guarded_train_step(
                    m, mesh, dims, self.opt_cfg, self.schedule)
                self._step = jax.jit(self._step_fn, donate_argnums=(0, 1))
            from repro.core import autosched
            self._sched_keys = set(autosched.cache_info())
            return params, opt_state

    def compile(self, params, opt_state, batch):
        """Compile the (unguarded) step ahead of its first call for these
        arguments and return the compiled program; ``run`` then executes
        it.  A later re-jit (placement rebalance) replaces it as usual.
        The phases ``setup.lower`` and ``setup.compile`` (the backend's
        compile, or the read of the persistent cache) time the two."""
        with obs.phase("setup.lower"):
            lowered = self._step.lower(params, opt_state, batch)
        with obs.phase("setup.compile"):
            self._step = lowered.compile()
        return self._step

    def _log_step0(self, metrics):
        # the first step traced the model: any schedule="auto" MoE
        # layers have made their (schedule, n_chunks) decisions now
        from repro.core import autosched
        summary = autosched.cache_summary(
            exclude=getattr(self, "_sched_keys", ()))
        if summary:
            print(summary, flush=True)
        el = metrics.get("expert_load")
        if el is not None and getattr(el, "ndim", 0) == 1 \
                and el.shape[-1]:
            with jax.profiler.TraceAnnotation("train.expert_load_read"):
                el = jax.device_get(el)
            vals = " ".join(f"{float(c):.0f}" for c in el)
            print(f"expert load (routed rows/expert, all layers): "
                  f"[{vals}]", flush=True)

    def _track_load(self, metrics):
        """Fold this step's per-expert routed-row counts into the
        rolling load EMA (host-side numpy; a no-op for dense models).
        The read waits for the step: the span ``train.expert_load_read``."""
        el = metrics.get("expert_load")
        if el is not None and getattr(el, "ndim", 0) == 1 and el.shape[-1]:
            with jax.profiler.TraceAnnotation("train.expert_load_read"):
                el = jax.device_get(el)
            if float(el.sum()) > 0:      # all-zero = no routing signal
                self.load_ema.update(el)

    def _emit_train_step(self, m):
        """One ``train_step`` event per history row (loss, grad norm,
        LR scale, imbalance, ...), plus the per-expert load vector when
        the EMA is live — the streaming twin of ``history``."""
        if not obs.enabled():
            return
        obs.emit("train_step", **m)
        if self.load_ema.ready:
            obs.emit("expert_load", step=m.get("step"),
                     load=[round(float(v), 3)
                           for v in self.load_ema.value()])

    def _log(self, step, metrics, t0, **extra) -> dict:
        """One history row: the step's scalar metrics, read in the span
        ``train.log`` (the reads wait for the step), the wall time since
        ``t0``, ``extra`` and the load imbalance; emitted and printed."""
        with jax.profiler.TraceAnnotation("train.log"):
            # vector metrics (e.g. expert_load) are step-0 diagnostics,
            # not per-step scalars — keep the history float-only
            m = {k: float(v) for k, v in metrics.items()
                 if getattr(v, "ndim", 0) == 0}
        m["step"] = step
        m["wall_s"] = time.perf_counter() - t0
        m.update(extra)
        if self.load_ema.ready:
            m["load_imbalance"] = self.load_ema.imbalance()
        self._emit_train_step(m)
        print(f"step {step:5d}  loss {m['loss']:.4f}  "
              f"ce {m['ce']:.4f}  gnorm {m['grad_norm']:.3f}  "
              f"lr {m['lr']:.2e}", flush=True)
        return m

    def _maybe_rebalance(self, step):
        """Every ``rebalance_every`` steps, ask autosched whether a
        placement derived from the load EMA beats uniform under the
        skew-aware cost model; on a win, re-jit the step — the retrace
        resolves ``MoEConfig.placement == "auto"`` to the new placement
        (same cheap plan-swap mechanism as the fp8 wire fallback;
        params/opt state untouched)."""
        if self.placement != "auto" or not self.rebalance_every:
            return
        if step == 0 or step % self.rebalance_every or \
                not self.load_ema.ready:
            return
        from repro.core import autosched
        mcfg = getattr(self.model.cfg, "moe", None)
        if mcfg is None:
            return
        epoch = autosched.maybe_rebalance(
            self.load_ema.value(), margin=self.rebalance_margin,
            capacity_factor=mcfg.capacity_factor, top_k=mcfg.top_k)
        if epoch is None:
            return
        pl = autosched.current_placement()
        desc = pl.summary() if pl is not None else "uniform"
        self._step = jax.jit(self._step_fn, donate_argnums=(0, 1))
        obs.emit("train_rebalance", step=step, epoch=epoch,
                 placement=desc)
        print(f"step {step:5d}  REBALANCE -> placement epoch {epoch}: "
              f"{desc}", flush=True)

    def run(self, params, opt_state, data, n_steps: int, log_every: int = 10,
            ckpt_every: int = 0):
        if self.guards is not None:
            return self._run_guarded(params, opt_state, data, n_steps,
                                     log_every, ckpt_every)
        history = []
        bx = tuple(self.dims.batch_axes)
        t0 = time.perf_counter()
        for step in range(n_steps):
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                if obs.enabled():
                    obs.set_context(step=step)
                with jax.profiler.TraceAnnotation("train.input"):
                    batch = data.sharded_batch(step, self.mesh, bx)
                with jax.profiler.TraceAnnotation("train.dispatch"):
                    params, opt_state, metrics = self._step(params, opt_state,
                                                            batch)
                if step == 0:
                    self._log_step0(metrics)
                self._track_load(metrics)
                self._maybe_rebalance(step)
                if step % log_every == 0 or step == n_steps - 1:
                    history.append(self._log(step, metrics, t0))
                if ckpt_every and self.ckpt_path and step and \
                        step % ckpt_every == 0:
                    from repro.checkpoint import save_checkpoint
                    with obs.phase("train.checkpoint", step=step):
                        save_checkpoint(self.ckpt_path,
                                        {"params": params, "opt": opt_state},
                                        step)
        return params, opt_state, history

    def _run_guarded(self, params, opt_state, data, n_steps: int,
                     log_every: int = 10, ckpt_every: int = 0):
        """The fault-tolerant loop: guarded step -> observe -> (apply |
        skip | rollback), snapshots on clean steps, fp8 fallback swap."""
        from repro.core import autosched
        from repro.runtime import guards as guardlib
        from repro.runtime.rollback import RollbackManager
        from repro.checkpoint.ckpt import CheckpointStore

        state = self.guard_state
        mgr = None
        if self.ckpt_path:
            store = CheckpointStore(self.ckpt_path, retain=self.ckpt_retain,
                                    faults=self.faults)
            mgr = RollbackManager(store, shardings={
                "params": self._p_sh, "opt_state": self._o_sh})
            # anchor before step 0: a streak in the first interval must
            # have somewhere to roll back to
            with obs.phase("train.checkpoint", step=0):
                mgr.snapshot(params, opt_state, 0)

        history = []
        bx = tuple(self.dims.batch_axes)
        t0 = time.perf_counter()
        for step in range(n_steps):
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                if obs.enabled():
                    obs.set_context(step=step)
                with jax.profiler.TraceAnnotation("train.input"):
                    batch = data.sharded_batch(step, self.mesh, bx)
                gf = self.faults.grad_fault(step) if self.faults else 0.0
                # donated-in params/opt_state come back as the OLD values on a
                # skipped step (the jitted where-select), so unconditional
                # reassignment is correct either way
                with jax.profiler.TraceAnnotation("train.dispatch"):
                    params, opt_state, metrics = self._step(
                        params, opt_state, batch, state.lr_scale, gf)
                with jax.profiler.TraceAnnotation("train.guard_read"):
                    loss = float(metrics["loss"])
                    nonfinite = bool(metrics["nonfinite"])
                action = state.observe(step, loss, nonfinite)
                if step == 0:
                    self._log_step0(metrics)
                self._track_load(metrics)
                self._maybe_rebalance(step)
                if action == guardlib.ROLLBACK:
                    res = mgr.rollback(step) if mgr is not None else None
                    if res is None:
                        # nothing restorable: limp on with the backed-off LR
                        state.record_rollback(step, None)
                        obs.emit("guard_rollback", restored_step=None,
                                 loss=loss)
                    else:
                        params, opt_state, rstep = res
                        state.record_rollback(step, rstep)
                        obs.emit("guard_rollback", restored_step=rstep,
                                 loss=loss)
                        print(f"step {step:5d}  ROLLBACK -> re-anchored to "
                              f"checkpoint step {rstep}", flush=True)
                elif action == guardlib.SKIP:
                    obs.emit("guard_skip", streak=state.streak,
                             lr_scale=state.lr_scale)
                    print(f"step {step:5d}  SKIPPED (non-finite, streak "
                          f"{state.streak}, lr_scale {state.lr_scale:.3g})",
                          flush=True)
                if state.check_fp8():
                    # fp8 wire overflow: clamp every wire decision up to the
                    # fallback dtype and re-jit — the retrace re-consults
                    # autosched.decide under the new ceiling (cheap plan
                    # swap; params/opt state untouched)
                    autosched.set_wire_ceiling(state.cfg.fp8_fallback)
                    n = autosched.invalidate("fp8 wire overflow fallback")
                    self._step = jax.jit(self._step_fn, donate_argnums=(0, 1))
                    obs.emit("fp8_fallback",
                             sat_rate=guardlib.fp8_sat_rate(),
                             wire=state.cfg.fp8_fallback, invalidated=n)
                    print(f"fp8 wire overflow (sat rate "
                          f"{guardlib.fp8_sat_rate():.2e}): falling back to "
                          f"{state.cfg.fp8_fallback} wire "
                          f"({n} cached decisions invalidated)", flush=True)
                if step % log_every == 0 or step == n_steps - 1:
                    history.append(self._log(step, metrics, t0,
                                             lr_scale=state.lr_scale))
                if mgr is not None and ckpt_every and step and \
                        step % ckpt_every == 0 and action == guardlib.OK:
                    with obs.phase("train.checkpoint", step=step):
                        mgr.snapshot(params, opt_state, step)
        print(state.summary(), flush=True)
        return params, opt_state, history
