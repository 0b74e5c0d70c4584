"""Unified kernel-backend registry for every hot-path op.

One seam between "what the schedules/models compute" and "how it is
computed": each op (``expert_ffn``, ``moe_dispatch``, ``moe_combine``,
``rmsnorm``, ``flash_attention``) is registered once per backend and
fetched with ``get_op(name, backend=...)``.  Backends:

  * ``"ref"``    — the pure-jnp oracles from ``repro.kernels.ref`` (the
    implementations the schedule bodies used to inline).  Differentiable,
    lowerable anywhere, and the ground truth the Pallas kernels are
    asserted against.
  * ``"pallas"`` — the Pallas TPU kernels.  On non-TPU backends they run
    in interpret mode (Python emulation) unless ``KernelConfig.interpret``
    pins it.  ``pallas_call`` has no autodiff rule, so every pallas op is
    wrapped in a ``custom_vjp``: ``moe_dispatch``/``moe_combine`` use
    their closed-form transposes (a gather / a scatter + weight dot),
    the rest recompute through the ref oracle — grads flow through
    schedule bodies regardless of backend.
  * ``"auto"``   — resolve at call time: ``pallas`` on TPU, ``ref``
    otherwise (overridable with ``REPRO_KERNEL_BACKEND``).  This is the
    default everywhere, so tests/CPU dry-runs stay on jnp while TPU runs
    get the fused kernels with zero config.

Per-op block sizes ride along in ``KernelConfig``; built ops are jitted
and cached by ``(name, backend, config, static-kwargs)``.

Adding a kernel = write the Pallas module, write/point at the jnp oracle
in ``ref.py``, and register both:

    @register("my_op", "ref")
    def _(cfg, static):
        return jax.jit(functools.partial(ref.my_op_ref, **static))

    @register("my_op", "pallas")
    def _(cfg, static):
        fwd = functools.partial(my_op_kernel, block=cfg.block_t, **static)
        return jax.jit(_with_ref_vjp(fwd, functools.partial(
            ref.my_op_ref, **static)))
"""

from __future__ import annotations

import contextlib
import functools
import os
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.kernels import expert_ffn as _expert_ffn_mod
from repro.kernels import expert_ffn_grouped as _grouped_mod
from repro.kernels import flash_attention as _flash_mod
from repro.kernels import moe_dispatch as _dispatch_mod
from repro.kernels import ref
from repro.kernels import rmsnorm as _rmsnorm_mod

BACKENDS = ("ref", "pallas")
_ENV_BACKEND = "REPRO_KERNEL_BACKEND"


@dataclass(frozen=True)
class KernelConfig:
    """Backend choice + per-op tile sizes, threaded from the model configs
    down into shard_map bodies (hashable: lives inside frozen configs and
    keys the built-op cache)."""

    backend: str = "auto"          # "auto" | "pallas" | "ref"
    interpret: Optional[bool] = None  # None = interpret iff not on TPU
    # expert_ffn tiles (token dim, hidden dim; M stays unblocked)
    block_t: int = 128
    block_f: int = 256
    # moe_dispatch / moe_combine token-stream tile
    block_s: int = 256
    # rmsnorm row tile
    block_r: int = 256
    # flash_attention query/key tiles
    block_q: int = 128
    block_k: int = 128


DEFAULT = KernelConfig()

# (op name, backend) -> builder(cfg: KernelConfig, static: dict) -> callable
_REGISTRY: dict = {}
# open ``record_resolved`` blocks, each an {op: backend} dict
_RECORDERS: list = []


def register(name: str, backend: str):
    """Decorator registering a builder for ``(name, backend)``."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}, want one of {BACKENDS}")

    def deco(build: Callable):
        _REGISTRY[(name, backend)] = build
        return build

    return deco


def list_ops() -> tuple:
    return tuple(sorted({n for n, _ in _REGISTRY}))


@contextlib.contextmanager
def record_resolved():
    """Yield a dict that fills with ``{op: backend}`` for every
    ``get_op`` inside the block — traced inside it, a step records the
    backend each of its ops resolved to."""
    rec: dict = {}
    _RECORDERS.append(rec)
    try:
        yield rec
    finally:
        _RECORDERS.remove(rec)


def available_backends(name: str) -> tuple:
    return tuple(b for b in BACKENDS if (name, b) in _REGISTRY)


def resolve_backend(backend: Optional[str] = None,
                    cfg: Optional[KernelConfig] = None) -> str:
    """Concrete backend for a request: explicit arg > config > env > auto.

    ``auto`` picks ``pallas`` on TPU and ``ref`` everywhere else — the ref
    oracles are the same math and XLA already fuses them well on CPU/GPU,
    while interpret-mode Pallas is emulation-speed and only worth running
    when explicitly asked for (tests, kernel debugging).
    """
    b = backend or (cfg or DEFAULT).backend or "auto"
    if b == "auto":
        b = os.environ.get(_ENV_BACKEND, "auto")
    if b == "auto":
        b = "pallas" if jax.default_backend() == "tpu" else "ref"
    if b not in BACKENDS:
        raise ValueError(f"unknown backend {b!r}, want one of {BACKENDS}")
    return b


def get_op(name: str, *, backend: Optional[str] = None,
           cfg: Optional[KernelConfig] = None, **static) -> Callable:
    """Fetch the jitted op ``name`` for the resolved backend.

    ``static`` holds compile-time parameters (``act``, ``n_slots``,
    ``causal``, ``eps``, ...) baked into the returned callable, which then
    takes array arguments only.  Built ops are cached, so calling this in
    a traced function body is free after the first hit.
    """
    cfg = cfg or DEFAULT
    b = resolve_backend(backend, cfg)
    for rec in _RECORDERS:
        rec[name] = b
    if (name, b) not in _REGISTRY:
        known = ", ".join(f"{n}:{bk}" for n, bk in sorted(_REGISTRY))
        raise KeyError(f"no kernel op {name!r} for backend {b!r} ({known})")
    return _build(name, b, cfg, tuple(sorted(static.items())))


@functools.lru_cache(maxsize=None)
def _build(name, backend, cfg, static_items):
    return _REGISTRY[(name, backend)](cfg, dict(static_items))


def _with_ref_vjp(fwd_fn: Callable, ref_fn: Callable) -> Callable:
    """Differentiate a Pallas op by recompute through its jnp oracle.

    Forward runs the kernel; backward re-traces ``ref_fn`` (numerically
    identical by the parity tests) and applies its VJP.  Residuals are the
    raw inputs, so nothing kernel-internal is saved.
    """

    @jax.custom_vjp
    def op(*args):
        return fwd_fn(*args)

    def fwd(*args):
        return fwd_fn(*args), args

    def bwd(args, g):
        return jax.vjp(ref_fn, *args)[1](g)

    op.defvjp(fwd, bwd)
    return op


# --- expert_ffn --------------------------------------------------------------

@register("expert_ffn", "ref")
def _expert_ffn_ref(cfg, static):
    act = static.get("act", "silu")
    return jax.jit(functools.partial(ref.expert_ffn_ref, act=act))


@register("expert_ffn", "pallas")
def _expert_ffn_pallas(cfg, static):
    act = static.get("act", "silu")
    fwd = functools.partial(
        _expert_ffn_mod.expert_ffn, act=act, block_t=cfg.block_t,
        block_f=cfg.block_f, interpret=cfg.interpret)
    return jax.jit(_with_ref_vjp(
        fwd, functools.partial(ref.expert_ffn_ref, act=act)))


# --- expert_ffn_ragged / expert_ffn_grouped ----------------------------------
# The dropless pair (PR 6).  ``expert_ffn_ragged`` is the pool-path form
# (the executor hands it the A2A receive buffer + routed-row counts);
# ``expert_ffn_grouped`` is the single-device megakernel fusing dispatch
# gather and combine scatter around the ragged FFN.  Both carry analytic
# custom_vjps: the ragged bwd is the hand-written transpose of the two
# GEMMs with the routed-row mask folded into the cotangent (counts are
# integral — cotangent None), and the fused bwd composes the oracle's
# closed-form dispatch/combine transposes via its VJP with ``flat_idx``
# held out as a non-differentiable operand.

def _ragged_analytic_vjp(fwd_fn: Callable, act: str) -> Callable:
    actf = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[act]

    @jax.custom_vjp
    def op(xb, counts, w1, w3, w2):
        return fwd_fn(xb, counts, w1, w3, w2)

    def fwd(xb, counts, w1, w3, w2):
        return fwd_fn(xb, counts, w1, w3, w2), (xb, counts, w1, w3, w2)

    def bwd(res, g):
        xb, counts, w1, w3, w2 = res
        E, G, c, M = xb.shape
        mask = jnp.arange(c)[None, None, :] < counts[:, :, None]
        gm = (g * mask[..., None].astype(g.dtype)).reshape(
            E, G * c, M).astype(jnp.float32)
        xf = xb.reshape(E, G * c, M).astype(jnp.float32)
        w1f = w1.astype(jnp.float32)
        w2f = w2.astype(jnp.float32)
        h1 = jnp.einsum("etm,emf->etf", xf, w1f)
        if w3 is not None:
            w3f = w3.astype(jnp.float32)
            h3 = jnp.einsum("etm,emf->etf", xf, w3f)
            mid, mid_vjp = jax.vjp(lambda a, b: actf(a) * b, h1, h3)
        else:
            mid, mid_vjp = jax.vjp(actf, h1)
        d_w2 = jnp.einsum("etf,etm->efm", mid, gm).astype(w2.dtype)
        d_mid = jnp.einsum("etm,efm->etf", gm, w2f)
        if w3 is not None:
            d_h1, d_h3 = mid_vjp(d_mid)
            d_x = (jnp.einsum("etf,emf->etm", d_h1, w1f)
                   + jnp.einsum("etf,emf->etm", d_h3, w3f))
            d_w3 = jnp.einsum("etm,etf->emf", xf, d_h3).astype(w3.dtype)
        else:
            (d_h1,) = mid_vjp(d_mid)
            d_x = jnp.einsum("etf,emf->etm", d_h1, w1f)
            d_w3 = None
        d_w1 = jnp.einsum("etm,etf->emf", xf, d_h1).astype(w1.dtype)
        d_x = d_x.reshape(E, G, c, M).astype(xb.dtype)
        return d_x, None, d_w1, d_w3, d_w2

    op.defvjp(fwd, bwd)
    return op


def _grouped_fused_vjp(fwd_fn: Callable, ref_fn: Callable) -> Callable:
    @jax.custom_vjp
    def op(x, flat_idx, weights, w1, w3, w2):
        return fwd_fn(x, flat_idx, weights, w1, w3, w2)

    def fwd(x, flat_idx, weights, w1, w3, w2):
        return (fwd_fn(x, flat_idx, weights, w1, w3, w2),
                (x, flat_idx, weights, w1, w3, w2))

    def bwd(res, g):
        x, flat_idx, weights, w1, w3, w2 = res
        d = jax.vjp(
            lambda x_, ws_, w1_, w3_, w2_: ref_fn(
                x_, flat_idx, ws_, w1_, w3_, w2_),
            x, weights, w1, w3, w2)[1](g)
        return d[0], None, d[1], d[2], d[3], d[4]

    op.defvjp(fwd, bwd)
    return op


@register("expert_ffn_ragged", "ref")
def _expert_ffn_ragged_ref(cfg, static):
    act = static.get("act", "silu")
    return jax.jit(_ragged_analytic_vjp(
        functools.partial(ref.expert_ffn_ragged_ref, act=act), act))


@register("expert_ffn_ragged", "pallas")
def _expert_ffn_ragged_pallas(cfg, static):
    act = static.get("act", "silu")
    fwd = functools.partial(
        _grouped_mod.expert_ffn_ragged, act=act, block_t=cfg.block_t,
        block_f=cfg.block_f, interpret=cfg.interpret)
    return jax.jit(_ragged_analytic_vjp(fwd, act))


def _grouped_ref_fn(static):
    return functools.partial(
        ref.expert_ffn_grouped_ref, cap=static["cap"],
        act=static.get("act", "silu"), wire=static.get("wire", "f32"))


@register("expert_ffn_grouped", "ref")
def _expert_ffn_grouped_ref(cfg, static):
    ref_fn = _grouped_ref_fn(static)
    return jax.jit(_grouped_fused_vjp(ref_fn, ref_fn))


@register("expert_ffn_grouped", "pallas")
def _expert_ffn_grouped_pallas(cfg, static):
    fwd = functools.partial(
        _grouped_mod.expert_ffn_grouped, cap=static["cap"],
        act=static.get("act", "silu"), wire=static.get("wire", "f32"),
        block_t=cfg.block_t, block_f=cfg.block_f, interpret=cfg.interpret)
    return jax.jit(_grouped_fused_vjp(fwd, _grouped_ref_fn(static)))


# --- moe_dispatch / moe_combine ----------------------------------------------
# The pallas backends of these two ops do NOT use the ref-recompute VJP:
# both have closed-form transposes that are strictly cheaper than
# re-tracing the oracle.  Dispatch is a scatter-add of each token into
# its flat slots, so its backward w.r.t. the token stream is the gather
# of the output cotangent at the same slots; combine is a weighted
# gather, so its backward is a scatter (w.r.t. the buffer) plus a dot
# (w.r.t. the weights).  ``flat_idx`` is integral — cotangent None.

def _dispatch_analytic_vjp(fwd_fn: Callable, n_slots: int) -> Callable:
    @jax.custom_vjp
    def op(x, flat_idx):
        return fwd_fn(x, flat_idx)

    def fwd(x, flat_idx):
        return fwd_fn(x, flat_idx), flat_idx

    def bwd(flat_idx, g):
        # row n_slots of the padded cotangent is the drop sentinel: zero
        gpad = jnp.concatenate(
            [g, jnp.zeros((1, g.shape[-1]), g.dtype)], axis=0)
        return gpad[flat_idx].sum(axis=1), None   # (S, k, M) -> (S, M)

    op.defvjp(fwd, bwd)
    return op


def _combine_analytic_vjp(fwd_fn: Callable) -> Callable:
    @jax.custom_vjp
    def op(buf, flat_idx, weights):
        return fwd_fn(buf, flat_idx, weights)

    def fwd(buf, flat_idx, weights):
        return fwd_fn(buf, flat_idx, weights), (buf, flat_idx, weights)

    def bwd(res, g):
        buf, flat_idx, weights = res
        n_slots, M = buf.shape
        S, k = flat_idx.shape
        kept = flat_idx < n_slots
        w = jnp.where(kept, weights, 0.0).astype(buf.dtype)
        # d/d buf: scatter-add of w[s,j] * g[s] into the flat slots (the
        # dispatch scatter, drop sentinel row discarded).
        src = (w[:, :, None] * g[:, None, :].astype(buf.dtype))
        cot_buf = jnp.zeros((n_slots + 1, M), buf.dtype).at[
            flat_idx.reshape(-1)].add(src.reshape(S * k, M),
                                      mode="drop")[:-1]
        # d/d weights: the gathered rows dotted with the cotangent.
        vals = buf[jnp.minimum(flat_idx, n_slots - 1).reshape(-1)]
        cot_w = jnp.einsum("sm,skm->sk", g.astype(buf.dtype),
                           vals.reshape(S, k, M))
        cot_w = jnp.where(kept, cot_w, 0.0).astype(weights.dtype)
        return cot_buf, None, cot_w

    op.defvjp(fwd, bwd)
    return op


@register("moe_dispatch", "ref")
def _moe_dispatch_ref(cfg, static):
    n_slots = static["n_slots"]
    return jax.jit(lambda x, flat_idx: ref.moe_dispatch_ref(
        x, flat_idx, n_slots))


@register("moe_dispatch", "pallas")
def _moe_dispatch_pallas(cfg, static):
    n_slots = static["n_slots"]
    fwd = functools.partial(
        _dispatch_mod.moe_dispatch, n_slots=n_slots, block_s=cfg.block_s,
        interpret=cfg.interpret)
    return jax.jit(_dispatch_analytic_vjp(fwd, n_slots))


@register("moe_combine", "ref")
def _moe_combine_ref(cfg, static):
    return jax.jit(ref.moe_combine_ref)


@register("moe_combine", "pallas")
def _moe_combine_pallas(cfg, static):
    fwd = functools.partial(_dispatch_mod.moe_combine, block_s=cfg.block_s,
                            interpret=cfg.interpret)
    return jax.jit(_combine_analytic_vjp(fwd))


# --- rmsnorm -----------------------------------------------------------------

@register("rmsnorm", "ref")
def _rmsnorm_ref(cfg, static):
    eps = static.get("eps", 1e-5)
    return jax.jit(functools.partial(ref.rmsnorm_ref, eps=eps))


@register("rmsnorm", "pallas")
def _rmsnorm_pallas(cfg, static):
    eps = static.get("eps", 1e-5)
    fwd = functools.partial(_rmsnorm_mod.rmsnorm, eps=eps,
                            block_r=cfg.block_r, interpret=cfg.interpret)
    return jax.jit(_with_ref_vjp(
        fwd, functools.partial(ref.rmsnorm_ref, eps=eps)))


# --- flash_attention ---------------------------------------------------------

def _flash_ref_fn(static):
    causal = static.get("causal", True)
    window = static.get("window")
    scale = static.get("scale")

    def f(q, k, v):
        H, K = q.shape[2], k.shape[2]
        if H != K:  # the oracle wants KV pre-repeated; the kernel is GQA-aware
            k = jnp.repeat(k, H // K, axis=2)
            v = jnp.repeat(v, H // K, axis=2)
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       scale=scale)

    return f


@register("flash_attention", "ref")
def _flash_ref(cfg, static):
    return jax.jit(_flash_ref_fn(static))


@register("flash_attention", "pallas")
def _flash_pallas(cfg, static):
    fwd = functools.partial(
        _flash_mod.flash_attention, causal=static.get("causal", True),
        window=static.get("window"), scale=static.get("scale"),
        block_q=cfg.block_q, block_k=cfg.block_k, interpret=cfg.interpret)
    return jax.jit(_with_ref_vjp(fwd, _flash_ref_fn(static)))
