"""Plain float32 reference of one chip's share of a Qwen3-MoE model.

Written from Qwen3's published description (arXiv 2505.09388 and the
Hugging Face ``Qwen3MoeForCausalLM`` it names) in straightforward
``jax.numpy``: no kernels, no sharding, no cache, nothing of the
program under test.  Every layer is

  x = x + Attn(RMSNorm(x));  x = x + MoE(RMSNorm(x))

with RMSNorm ``x * rsqrt(mean(x^2) + eps) * scale``.  Attention is GQA
(H query heads, K key/value heads of hd; query head j reads KV head
j // (H / K)) with RMSNorm over each head's q and k before RoPE
(rotate-half, theta from the file), causal, scaled by 1 / sqrt(hd), no
bias.  The MoE layer routes each token with a softmax over all E router
outputs, takes the top-k and renormalises their weights over the k,
and sends it to SwiGLU experts ``(silu(x w1) * (x w3)) w2``.  The head is
untied and the loss is the mean causal-LM cross-entropy over the
vocabulary slice plus each layer's load-balance loss
``aux * E * sum_e mean_prob_e * first_choice_share_e`` (and the router
z-loss at its weight).

The share (``bench/configs/<config>.json``, ``deployment``): the layer
holds experts ``[0, n_experts_held)``.  The router keeps its E outputs
and its top-k; routes to experts not held here add nothing, and the
layer's output is the held experts' part.  Capacity is GShard's over
all E experts: ``cap`` slots an expert in each pool of contiguous rows,
taken first choices first and in pool order within a choice; a route
past it adds nothing either.

Weights come from the seed by the file's ``init`` convention.  Attention
runs a block of query rows of one sequence at a time, the experts one
at a time and the logits in blocks of rows, each remade in the
backward, so that three AdamW steps of the whole batch fit one chip.
It runs in float32 at ``highest`` matmul precision; ``fault="half_batch"``
is the loss and gradient over the first half of each pool's rows.
"""

from __future__ import annotations

import functools
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference.model import (_capacity_slots, _change_norms, _frozen,
                                   _lr, _normal, flat_norms)

Q_ROWS = 256          # query rows of one attention block
LOGIT_ROWS = 2048     # rows of one block of logits

# --- init -------------------------------------------------------------------


def _init_block(key, m: dict) -> dict:
    D, H, K, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    E, Eh, F = m["n_experts"], m["n_experts_held"], m["expert_d_ff"]
    ks = jax.random.split(key, 8)
    a = jax.random.split(ks[0], 4)
    e = jax.random.split(ks[5], 6)
    return {
        "norm1": {"scale": jnp.ones((D,), jnp.float32)},
        "attn": {"wq": _normal(a[0], (D, H * hd), 1.0 / math.sqrt(D)),
                 "wk": _normal(a[1], (D, K * hd), 1.0 / math.sqrt(D)),
                 "wv": _normal(a[2], (D, K * hd), 1.0 / math.sqrt(D)),
                 "wo": _normal(a[3], (H * hd, D), 1.0 / math.sqrt(H * hd)),
                 "q_norm": jnp.ones((hd,), jnp.float32),
                 "k_norm": jnp.ones((hd,), jnp.float32)},
        "norm2": {"scale": jnp.ones((D,), jnp.float32)},
        "moe": {"wg": _normal(e[0], (D, E), 1.0 / math.sqrt(D)),
                "w1": _normal(e[1], (Eh, D, F), 1.0 / math.sqrt(D)),
                "w2": _normal(e[2], (Eh, F, D), 1.0 / math.sqrt(F)),
                "w3": _normal(e[3], (Eh, D, F), 1.0 / math.sqrt(D))}}


def init_params(key, m: dict) -> dict:
    """Weights from ``key`` by the configuration's init convention (one
    run of ``n_layers`` MoE layers)."""
    keys = jax.random.split(key, 5)
    D, V = m["d_model"], m["vocab_size"]
    return {"embed": {"table": _normal(keys[0], (V, D), 0.02)},
            "final_norm": {"scale": jnp.ones((D,), jnp.float32)},
            "lm_head": {"w": jax.random.normal(keys[1], (D, V), jnp.float32)
                        / math.sqrt(D)},
            "run0": jax.vmap(lambda k: _init_block(k, m))(
                jax.random.split(keys[2], m["n_layers"]))}


# --- forward ----------------------------------------------------------------


def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * scale


def _rope(x, theta):
    """Rotate-half RoPE over (B, L, heads, hd) at positions 0..L-1."""
    L, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(p, x, m):
    """Causal GQA with QK-norm and RoPE; Q_ROWS query rows of one
    sequence at a time against all of its keys."""
    b, L, D = x.shape
    H, K, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    q = _rmsnorm((x @ p["wq"]).reshape(b, L, H, hd), p["q_norm"], eps)
    k = _rmsnorm((x @ p["wk"]).reshape(b, L, K, hd), p["k_norm"], eps)
    v = (x @ p["wv"]).reshape(b, L, K, hd)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    k = jnp.repeat(k, H // K, axis=2)
    v = jnp.repeat(v, H // K, axis=2)
    bq = min(Q_ROWS, L)
    nq = L // bq

    def block(args):
        qb, row, i = args                                   # (bq, H, hd)
        s = jnp.einsum("qhd,khd->hqk", qb, k[row]) / math.sqrt(hd)
        causal = (i * bq + jnp.arange(bq))[:, None] >= jnp.arange(L)[None]
        s = jnp.where(causal, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", w, v[row])

    qs = q.reshape(b * nq, bq, H, hd)
    rows = jnp.repeat(jnp.arange(b), nq)
    idx = jnp.tile(jnp.arange(nq), b)
    o = lax.map(jax.checkpoint(block), (qs, rows, idx))
    return o.reshape(b, L, H * hd) @ p["wo"]


def _moe(p, x, m, cap):
    """One pool of tokens x (S, D) through the MoE layer's held share.
    Returns (y, aux_loss + z_loss)."""
    S, D = x.shape
    E, Eh, k = m["n_experts"], m["n_experts_held"], m["top_k"]
    logits = x @ p["wg"]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, idx = lax.top_k(probs, k)
    if m["normalize_topk"]:
        gate_w = gate_w / jnp.sum(gate_w, axis=-1, keepdims=True)
    slot = _capacity_slots(idx, E)
    here = (slot < cap) & (idx < Eh)
    flat = jnp.where(here, idx * cap + slot, Eh * cap)   # Eh*cap: no slot
    # each held slot's token and gate weight (the last row takes the
    # routes that have no slot here)
    tok = jnp.full((Eh * cap + 1,), S, jnp.int32).at[flat.reshape(-1)].set(
        jnp.repeat(jnp.arange(S, dtype=jnp.int32), k))[:-1]
    w = jnp.zeros((Eh * cap + 1,), x.dtype).at[flat.reshape(-1)].set(
        gate_w.reshape(-1))[:-1]
    xp = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])
    buf = xp[tok].reshape(Eh, cap, D)

    def expert(args):           # one expert's slots, remade in backward
        xb, w1, w3, w2 = args
        return (jax.nn.silu(xb @ w1) * (xb @ w3)) @ w2
    out = lax.map(jax.checkpoint(expert),
                  (buf, p["w1"], p["w3"], p["w2"])).reshape(Eh * cap, D)
    y = jnp.zeros((S + 1, D), x.dtype).at[tok].add(w[:, None] * out)[:-1]
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32), axis=0)
    aux = m["aux_loss_weight"] * E * jnp.sum(me * ce)
    z = m["z_loss_weight"] * jnp.mean(
        jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return y, aux + z


def _layer(p, x, *, m, cap):
    b, L, D = x.shape
    eps = m["norm_eps"]
    x = x + _attention(p["attn"], _rmsnorm(x, p["norm1"]["scale"], eps), m)
    h = _rmsnorm(x, p["norm2"]["scale"], eps)
    y, aux = _moe(p["moe"], h.reshape(b * L, D), m, cap)
    return x + y.reshape(b, L, D), aux


def pool_loss(p, tokens, labels, m, *, cap, n_tokens, n_pools):
    """This pool's share of the batch loss: its summed token
    cross-entropy over ``n_tokens`` (all tokens of the batch) plus its
    router losses over ``n_pools``."""
    b, L = tokens.shape
    D = m["d_model"]
    x = p["embed"]["table"][tokens]

    def layer(carry, lp):
        x, aux_total = carry
        x, aux = jax.checkpoint(partial(_layer, m=m, cap=cap))(lp, x)
        return (x, aux_total + aux), None

    (x, aux_total), _ = lax.scan(layer, (x, jnp.float32(0.0)), p["run0"])
    x = _rmsnorm(x, p["final_norm"]["scale"], m["norm_eps"])

    def ce_rows(args):
        xc, yc = args
        logp = jax.nn.log_softmax(xc @ p["lm_head"]["w"], axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, yc[:, None], axis=-1))

    n = max((b * L) // LOGIT_ROWS, 1)
    ce = jnp.sum(lax.map(jax.checkpoint(ce_rows),
                         (x.reshape(n, -1, D), labels.reshape(n, -1))))
    return ce / n_tokens + aux_total / n_pools


# --- optimizer -----------------------------------------------------------------


@partial(jax.jit, static_argnames=("m", "o", "cap", "n_pools", "fault"),
         donate_argnums=(0, 1, 2))
def _step(params, mu, nu, tokens, labels, step, *, m, o, cap, n_pools,
          fault):
    """One AdamW step; ``step`` (1, 2, ...) is traced, so that every step
    runs one compiled program; the state is donated."""
    m, o = dict(m), dict(o)
    B, L = tokens.shape
    rows = B // n_pools
    tok = tokens.reshape(n_pools, rows, L)
    lab = labels.reshape(n_pools, rows, L)
    n_tokens = B * L
    if fault == "half_batch":
        tok, lab = tok[:, : rows // 2], lab[:, : rows // 2]
        n_tokens //= 2

    loss, grads = jnp.float32(0.0), None
    for i in range(n_pools):     # one chip gates one pool: no accumulator
        li, g = jax.value_and_grad(pool_loss)(
            params, tok[i], lab[i], m, cap=cap, n_tokens=n_tokens,
            n_pools=n_pools)
        loss = loss + li
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    lr = _lr(o, step)
    b1, b2 = o["beta1"], o["beta2"]
    b1c, b2c = 1 - jnp.power(b1, step), 1 - jnp.power(b2, step)

    def upd(p, g, m0, v0):
        g = g * scale
        m1 = b1 * m0 + (1 - b1) * g
        v1 = b2 * v0 + (1 - b2) * jnp.square(g)
        delta = (m1 / b1c) / (jnp.sqrt(v1 / b2c) + o["eps"])
        if p.ndim >= o["decay_min_rank"]:
            delta = delta + o["weight_decay"] * p
        return p - lr * delta, m1, v1, jnp.sqrt(jnp.sum(jnp.square(g)))

    out = jax.tree.map(upd, params, grads, mu, nu)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,  # noqa: E731
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), loss, pick(3)


@functools.lru_cache(maxsize=8)
def _init_jit(m: tuple):
    return jax.jit(partial(init_params, m=dict(m)))


def train_steps(key, model_cfg: dict, opt_cfg: dict, batches, *, pools,
                fault: str = "", device=None) -> dict:
    """Run ``len(batches)`` AdamW steps from the seeded weights, each
    batch gated in ``pools = (n_pools, cap)``.  Returns ``{"loss": [...],
    "grad_norms": {leaf: norm}, "change_norms": {leaf: norm}}``: each
    step's loss, the per-leaf norms of step one's clipped gradient, and
    of the parameters' change over all steps."""
    m, o = _frozen(model_cfg), _frozen(opt_cfg)
    n_pools, cap = pools
    with jax.default_matmul_precision("highest"), \
            jax.default_device(device):
        params = _init_jit(m)(key)   # made again for the change, not kept
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        losses, grad_norms = [], None
        for i, (tok, lab) in enumerate(batches):
            params, mu, nu, loss, gn = _step(
                params, mu, nu, jnp.asarray(tok), jnp.asarray(lab),
                jnp.float32(i + 1), m=m, o=o, cap=cap, n_pools=n_pools,
                fault=fault)
            losses.append(float(loss))
            if i == 0:
                grad_norms = flat_norms(gn)
        change = flat_norms(_change_norms(params, _init_jit(m)(key)))
    return {"loss": losses, "grad_norms": grad_norms,
            "change_norms": change}
