"""Model assembly: embedding -> run-partitioned scanned blocks -> head.

Layers are grouped into maximal consecutive same-kind runs; each run's
parameters are stacked with a leading layer axis and executed with
``lax.scan`` so the lowered HLO stays compact for 40+-layer models (the
multi-pod dry-run compiles every architecture at full size).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import blocks as blk
from repro.models.attention import AttnConfig
from repro.models.layers import (apply_norm, embed, embedding_specs,
                                 init_embedding, init_norm, norm_specs,
                                 sinusoidal_positions, unembed)
from repro.parallel.mesh import ParallelDims, axis_size as _axis_size


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.runs = cfg.runs()
        self.has_cross = any(
            blk.base_kind(k) in ("cross", "xdec") for k, _ in self.runs)

    # --- params -----------------------------------------------------------
    def init(self, key) -> dict:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        keys = jax.random.split(key, len(self.runs) + 4)
        params = {"embed": init_embedding(keys[0], cfg.vocab_size,
                                          cfg.d_model, dtype),
                  "final_norm": init_norm(cfg.d_model, cfg.norm_type)}
        if not cfg.tie_embeddings:
            params["lm_head"] = {
                "w": jax.random.normal(keys[1], (cfg.d_model, cfg.vocab_size),
                                       dtype) / math.sqrt(cfg.d_model)}
        for r, (kind, n) in enumerate(self.runs):
            ks = jax.random.split(keys[2 + r], n)
            stacked = jax.vmap(
                lambda k: blk.init_block(k, cfg, kind, dtype))(ks)
            params[f"run{r}"] = stacked
        if cfg.arch_type == "audio" and cfg.encoder_layers:
            ks = jax.random.split(keys[-1], cfg.encoder_layers)
            params["encoder"] = jax.vmap(
                lambda k: blk.init_block(k, cfg, "encoder", dtype))(ks)
            params["enc_norm"] = init_norm(cfg.d_model, cfg.norm_type)
        return params

    def specs(self, mesh, dims: ParallelDims) -> dict:
        cfg = self.cfg
        specs = {"embed": embedding_specs(mesh, dims.mp, cfg.vocab_size),
                 "final_norm": norm_specs(cfg.norm_type)}
        if not cfg.tie_embeddings:
            v_ax = embedding_specs(mesh, dims.mp, cfg.vocab_size)["table"][0]
            specs["lm_head"] = {"w": P(None, v_ax)}

        def add_layer_dim(spec):
            return P(*((None,) + tuple(spec)))

        for r, (kind, n) in enumerate(self.runs):
            s = blk.block_specs(cfg, kind, mesh, dims)
            specs[f"run{r}"] = jax.tree.map(
                add_layer_dim, s, is_leaf=lambda x: isinstance(x, P))
        if cfg.arch_type == "audio" and cfg.encoder_layers:
            s = blk.block_specs(cfg, "encoder", mesh, dims)
            specs["encoder"] = jax.tree.map(
                add_layer_dim, s, is_leaf=lambda x: isinstance(x, P))
            specs["enc_norm"] = norm_specs(cfg.norm_type)
        return specs

    # --- forward ------------------------------------------------------------
    def _encode_ctx(self, params, batch):
        """Context tokens for cross-attention: VLM image embeds (stub
        frontend) or the whisper encoder run over stub audio frames."""
        cfg = self.cfg
        ctx = batch.get("ctx_embeds")
        if ctx is None:
            return None
        if cfg.arch_type == "audio":
            x = ctx + sinusoidal_positions(ctx.shape[1],
                                           cfg.d_model).astype(ctx.dtype)

            def enc_step(h, layer_params):
                h, _ = blk.apply_block(layer_params, cfg, "encoder", h,
                                       mesh=self._mesh, dims=self._dims)
                return h, None

            x, _ = lax.scan(enc_step, x, params["encoder"])
            return apply_norm(params["enc_norm"], x, cfg.norm_eps,
                              cfg.kernel_cfg)
        return ctx

    def forward(self, params, batch, *, mesh, dims: ParallelDims,
                schedule: Optional[str] = None):
        """Full-sequence forward (train / prefill). Returns (logits, aux)."""
        x, aux = self._backbone(params, batch, mesh=mesh, dims=dims,
                                schedule=schedule)
        return self._head(params, x), aux

    def _backbone(self, params, batch, *, mesh, dims: ParallelDims,
                  schedule: Optional[str] = None):
        """Embedding -> blocks -> final norm (no LM head)."""
        cfg = self.cfg
        self._mesh, self._dims = mesh, dims
        tokens = batch["tokens"]
        B, L = tokens.shape
        x = embed(params["embed"], tokens)
        if not cfg.use_rope and cfg.arch_type not in ("ssm",):
            x = x + sinusoidal_positions(L, cfg.d_model).astype(x.dtype)
        ctx = self._encode_ctx(params, batch)
        # None = the default contiguous-from-zero layout; apply_attn fills in
        # the arange itself and stays eligible for the Pallas kernel path
        # (which derives positions from block indices).
        positions = None
        aux_total = jnp.float32(0.0)
        expert_load = jnp.zeros((0,), jnp.float32)

        seq_spec = None
        if cfg.seq_parallel and dims.mp and L % max(
                1, _axis_size(mesh, dims.mp)) == 0:
            # Megatron-SP (§Perf B2): keep the residual stream sequence-
            # sharded over MP between blocks; GSPMD turns the per-layer
            # AllReduces into ReduceScatter+AllGather and runs the norms /
            # residual adds on L/N_MP tokens.
            from jax.sharding import NamedSharding, PartitionSpec as P
            baxes = tuple(dims.batch_axes) or None
            seq_spec = NamedSharding(mesh, P(baxes, tuple(dims.mp), None))

        for r, (kind, n) in enumerate(self.runs):
            def step(h, layer_params, kind=kind):
                h2, aux = blk.apply_block(
                    layer_params, cfg, kind, h, mesh=mesh, dims=dims,
                    ctx=ctx, positions=positions, schedule=schedule)
                if seq_spec is not None:
                    h2 = jax.lax.with_sharding_constraint(h2, seq_spec)
                return h2, aux

            if cfg.remat:
                step = jax.checkpoint(step)
            x, auxs = lax.scan(step, x, params[f"run{r}"])
            aux_total = aux_total + jnp.sum(auxs["loss"])
            if auxs["expert_load"].shape[-1]:
                run_load = jnp.sum(auxs["expert_load"], axis=0)  # (E,)
                expert_load = run_load if not expert_load.shape[-1] \
                    else expert_load + run_load

        x = apply_norm(params["final_norm"], x, cfg.norm_eps,
                       cfg.kernel_cfg)
        return x, {"aux_loss": aux_total, "expert_load": expert_load}

    def _head(self, params, x):
        cfg = self.cfg
        if cfg.tie_embeddings:
            logits = unembed(params["embed"], x)
        else:
            logits = x @ params["lm_head"]["w"]
        return logits * cfg.logit_scale

    def loss(self, params, batch, *, mesh, dims, schedule=None):
        cfg = self.cfg
        self._mesh, self._dims = mesh, dims
        tokens = batch["tokens"]
        labels = batch["labels"]
        B, L = tokens.shape

        # run the backbone once; compute CE in sequence chunks so the
        # (B, L, V) f32 logits are never materialized (134 GB/chip for
        # command-r train_4k otherwise — see EXPERIMENTS.md §Perf).
        hidden, aux = self._backbone(params, batch, mesh=mesh,
                                     dims=dims, schedule=schedule)
        # the LM head and cross-entropy, both paths, under the ``head``
        # scope the device trace reads
        with jax.named_scope("head"):
            logits_fn_input = hidden
            b_local = max(B // max(_axis_size(mesh, dims.batch_axes), 1), 1)
            chunk = L
            while b_local * chunk * cfg.vocab_size > (1 << 28) \
                    and chunk % 2 == 0:
                chunk //= 2
            n_chunks = L // chunk if L % chunk == 0 else 1
            if n_chunks <= 1:
                logits = self._head(params, logits_fn_input)
                logp = jax.nn.log_softmax(logits.astype(jnp.float32),
                                          axis=-1)
                ll = jnp.take_along_axis(logp, labels[..., None],
                                         axis=-1)[..., 0]
                mask = (labels >= 0).astype(jnp.float32)
                ce = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
            else:
                def chunk_ce(x_c, y_c):
                    logits = self._head(params, x_c)
                    logp = jax.nn.log_softmax(logits.astype(jnp.float32),
                                              -1)
                    ll = jnp.take_along_axis(logp, y_c[..., None],
                                             -1)[..., 0]
                    m = (y_c >= 0).astype(jnp.float32)
                    return jnp.sum(-ll * m), jnp.sum(m)

                def step(carry, idx):
                    x_c = lax.dynamic_slice_in_dim(logits_fn_input,
                                                   idx * chunk, chunk, 1)
                    y_c = lax.dynamic_slice_in_dim(labels, idx * chunk,
                                                   chunk, 1)
                    s, n = jax.checkpoint(chunk_ce)(x_c, y_c)
                    return (carry[0] + s, carry[1] + n), None

                (tot, n), _ = lax.scan(step, (jnp.float32(0.0),
                                              jnp.float32(0.0)),
                                       jnp.arange(n_chunks))
                ce = tot / jnp.maximum(n, 1.0)
        total = ce + aux["aux_loss"]
        return total, {"ce": ce, "aux": aux["aux_loss"],
                       "ppl_proxy": jnp.exp(jnp.minimum(ce, 20.0)),
                       # per-expert routed-row counts, summed over layers
                       # ((0,) for dense models) — Trainer prints these at
                       # step 0 and the dryrun artifact records them
                       "expert_load": aux["expert_load"]}

    # --- decode ----------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=None) -> dict:
        cfg = self.cfg
        dtype = dtype or jnp.dtype(cfg.dtype)
        cache = {}
        for r, (kind, n) in enumerate(self.runs):
            one = blk.init_block_cache(cfg, kind, batch, max_len, dtype)
            cache[f"run{r}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n,) + a.shape), one)
        return cache

    def ctx_kv(self, params, batch, *, mesh=None, dims=None):
        """Precompute static cross-attention K/V per run (serving-side)."""
        cfg = self.cfg
        if mesh is not None:
            self._mesh, self._dims = mesh, dims
        ctx = self._encode_ctx(params, batch)
        if ctx is None:
            return None
        out = {}
        for r, (kind, n) in enumerate(self.runs):
            base = blk.base_kind(kind)
            if base not in ("cross", "xdec"):
                continue
            acfg = blk.attn_config(cfg, kind, cross=True)
            K, hd = acfg.n_kv_heads, acfg.head_dim

            def kv_one(p):
                k = (ctx @ p["xattn"]["wk"]).reshape(
                    ctx.shape[0], ctx.shape[1], K, hd)
                v = (ctx @ p["xattn"]["wv"]).reshape(
                    ctx.shape[0], ctx.shape[1], K, hd)
                return {"k": k, "v": v}

            out[f"run{r}"] = jax.vmap(kv_one)(params[f"run{r}"])
        return out

    def prefill_step(self, params, cache, batch, *, lengths, mesh,
                     dims: ParallelDims, schedule: Optional[str] = None):
        """Batched one-shot prefill: ONE forward over the right-padded
        prompts that fills every layer's KV cache (the serving engine's
        admission path — never a per-token loop).

        ``lengths`` (B,) are the valid prompt lengths; returns
        ``(last_logits, new_cache)`` where ``last_logits[b]`` is the
        (V,)-vector at row b's own final prompt position — the logits
        the first generated token is sampled from.
        """
        cfg = self.cfg
        self._mesh, self._dims = mesh, dims
        bad = [k for k, _ in self.runs
               if blk.base_kind(k) not in ("dense", "moe")]
        if bad:
            raise NotImplementedError(
                f"prefill_step: unsupported block kinds {bad} "
                "(cache-filling prefill covers dense/moe decoder stacks)")
        tokens = batch["tokens"]
        B, L = tokens.shape
        x = embed(params["embed"], tokens)
        if not cfg.use_rope:
            x = x + sinusoidal_positions(L, cfg.d_model).astype(x.dtype)
        new_cache = {}
        for r, (kind, n) in enumerate(self.runs):
            def step(h, scanned, kind=kind):
                layer_params, layer_cache = scanned
                return blk.prefill_block(
                    layer_params, cfg, kind, h, layer_cache, lengths,
                    mesh=mesh, dims=dims, schedule=schedule)

            x, new_cache[f"run{r}"] = lax.scan(
                step, x, (params[f"run{r}"], cache[f"run{r}"]))
        x = apply_norm(params["final_norm"], x, cfg.norm_eps,
                       cfg.kernel_cfg)
        idx = jnp.clip(lengths - 1, 0, L - 1)
        h_last = x[jnp.arange(B), idx]                     # (B, D)
        logits = self._head(params, h_last[:, None, :])[:, 0]
        return logits, new_cache

    def paged_step(self, params, cache, batch, *, mesh, dims,
                   schedule: Optional[str] = None, infer: bool = False,
                   with_aux: bool = False):
        """One step over a PAGED KV arena (the serving engine's unified
        path): per-row token spans written/read through page tables.

        ``batch`` holds ``tokens`` (B, C), ``starts`` (B,) absolute
        position of each row's first token, ``lens`` (B,) valid counts,
        and ``tables`` (B, max_blocks) int32 page tables into the arena
        (``cache`` leaves are ``(layers, n_pages, block_size, ...)``).
        ``C = 1``/``lens = 1``/``infer=True`` is a decode round; larger
        C is a prefill chunk (``infer=False`` keeps the prefill-shaped
        MoE autosched decision).  Returns ``(last_logits, new_cache)``
        with ``last_logits[b]`` at row b's final valid chunk position —
        only meaningful for rows whose span ends their prompt (or the
        decoded token).  ``with_aux=True`` returns ``(last_logits,
        new_cache, aux)`` where ``aux["expert_load"]`` is the (E,)
        per-expert routed-row count summed over layers ((0,) for dense
        stacks) — the serving engine's load-EMA feed; the default keeps
        existing callers' arity.
        """
        cfg = self.cfg
        self._mesh, self._dims = mesh, dims
        bad = [k for k, _ in self.runs
               if blk.base_kind(k) not in ("dense", "moe")]
        if bad:
            raise NotImplementedError(
                f"paged_step: unsupported block kinds {bad} "
                "(paged serving covers dense/moe decoder stacks)")
        tokens = batch["tokens"]
        starts, lens, tables = batch["starts"], batch["lens"], batch["tables"]
        B, C = tokens.shape
        x = embed(params["embed"], tokens)
        if not cfg.use_rope:
            pe = sinusoidal_positions(2048, cfg.d_model)
            qpos = jnp.minimum(starts[:, None] + jnp.arange(C), 2047)
            x = x + jnp.take(pe, qpos, axis=0).astype(x.dtype)
        new_cache = {}
        expert_load = jnp.zeros((0,), jnp.float32)
        for r, (kind, n) in enumerate(self.runs):
            def step(h, scanned, kind=kind):
                layer_params, layer_cache = scanned
                out = blk.paged_block(
                    layer_params, cfg, kind, h, layer_cache, tables,
                    starts, lens, mesh=mesh, dims=dims, schedule=schedule,
                    infer=infer, with_aux=with_aux)
                if with_aux:
                    h2, c2, load = out
                    return h2, (c2, load)
                return out

            if with_aux:
                x, (new_cache[f"run{r}"], loads) = lax.scan(
                    step, x, (params[f"run{r}"], cache[f"run{r}"]))
                if loads.shape[-1]:
                    run_load = jnp.sum(loads, axis=0)        # (E,)
                    expert_load = run_load if not expert_load.shape[-1] \
                        else expert_load + run_load
            else:
                x, new_cache[f"run{r}"] = lax.scan(
                    step, x, (params[f"run{r}"], cache[f"run{r}"]))
        x = apply_norm(params["final_norm"], x, cfg.norm_eps,
                       cfg.kernel_cfg)
        idx = jnp.clip(lens - 1, 0, C - 1)
        h_last = x[jnp.arange(B), idx]                    # (B, D)
        logits = self._head(params, h_last[:, None, :])[:, 0]
        if with_aux:
            return logits, new_cache, {"expert_load": expert_load}
        return logits, new_cache

    def decode_step(self, params, cache, batch, *, mesh, dims,
                    schedule=None, ctx_kv=None):
        """One serve step: (B, 1) token -> (B, 1, V) logits + new cache.
        ``batch["step"]`` is the absolute position — a scalar (lockstep)
        or a (B,) vector (continuous batching, one position per row)."""
        cfg = self.cfg
        self._mesh, self._dims = mesh, dims
        tokens = batch["tokens"]
        step = batch["step"]
        x = embed(params["embed"], tokens)
        if not cfg.use_rope and cfg.arch_type not in ("ssm",):
            pe = sinusoidal_positions(2048, cfg.d_model)
            idx = jnp.minimum(step, 2047)
            if jnp.ndim(idx) > 0:
                x = x + jnp.take(pe, idx, axis=0)[:, None, :].astype(x.dtype)
            else:
                x = x + lax.dynamic_index_in_dim(
                    pe, idx, keepdims=True).astype(x.dtype)
        new_cache = {}
        for r, (kind, n) in enumerate(self.runs):
            ckv = ctx_kv.get(f"run{r}") if ctx_kv else None

            def step_fn(h, scanned, kind=kind):
                layer_params, layer_cache, layer_ckv = scanned
                h2, c2 = blk.decode_block(
                    layer_params, cfg, kind, h, layer_cache, step,
                    mesh=mesh, dims=dims, ctx_kv=layer_ckv,
                    schedule=schedule)
                return h2, c2

            scanned = (params[f"run{r}"], cache[f"run{r}"], ckv)
            if ckv is None:
                def step_fn2(h, sc, kind=kind):
                    lp, lc = sc
                    h2, c2 = blk.decode_block(lp, cfg, kind, h, lc, step,
                                              mesh=mesh, dims=dims,
                                              schedule=schedule)
                    return h2, c2
                x, new_cache[f"run{r}"] = lax.scan(
                    step_fn2, x, (params[f"run{r}"], cache[f"run{r}"]))
            else:
                x, new_cache[f"run{r}"] = lax.scan(step_fn, x, scanned)
        x = apply_norm(params["final_norm"], x, cfg.norm_eps,
                       cfg.kernel_cfg)
        return self._head(params, x), new_cache


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
