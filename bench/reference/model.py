"""Plain float32 reference of the benchmark's MoE transformers.

Written from the published description of the models (GPT-2 / BERT-Base
blocks with every other FFN an MoE layer of top-k experts with GShard
capacity), in straightforward ``jax.numpy``: no kernels, no sharding, no
cache.  It imports nothing of the program under test.  Everything it
needs is in the configuration file (``bench/configs/<config>.json``):
the widths under ``model``, the optimizer under ``optimizer``, and the
departures the program makes from the published models under
``departures`` (sinusoidal positions, causal LM loss, the expert
activation), which the reference follows so that the two compute the
same function.

Weights come from the seed by the init convention that the
configuration states (``init``): each tensor a normal draw scaled by
1/sqrt(fan_in), the embedding by 0.02, biases zero and norm scales one,
with keys split per layer run and per block as listed there.

One call of :func:`train_steps` runs a number of AdamW steps over given
batches and returns, per step, the loss, and after step one the
per-leaf norm of the clipped gradient the optimizer took, and after the
last step the per-leaf norm of the parameters' change.  The batch is
gated in ``n_pools`` pools of contiguous rows with per-expert capacity
``cap``, as the harness states the program gates it (each pool's
routing decided over the whole pool); gradients are accumulated pool by
pool so that the whole batch never has to be live at once, and the
experts' hidden activations one expert at a time.  One period
of the layer pattern is compiled and scanned over, and the step number
is traced, so that the three steps run one compiled program.

It runs in float32 at ``highest`` matmul precision.  The ``fault``
argument plants a fault in the reference put in the program's place:
``half_batch`` (the loss and gradient over the first half of the rows
only).
"""

from __future__ import annotations

import functools
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

# --- init -------------------------------------------------------------------


def layer_kinds(m: dict) -> list:
    return ["moe" if i % m["moe_period"] == 0 else "dense"
            for i in range(m["n_layers"])]


def layer_runs(m: dict) -> list:
    runs = []
    for k in layer_kinds(m):
        if runs and runs[-1][0] == k:
            runs[-1][1] += 1
        else:
            runs.append([k, 1])
    return [tuple(r) for r in runs]


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def _norm(d):
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def _init_block(key, m: dict, kind: str) -> dict:
    D, H, hd = m["d_model"], m["n_heads"], m["d_model"] // m["n_heads"]
    ks = jax.random.split(key, 8)
    a = jax.random.split(ks[0], 4)
    p = {"norm1": _norm(D),
         "attn": {"wq": _normal(a[0], (D, H * hd), 1.0 / math.sqrt(D)),
                  "wk": _normal(a[1], (D, H * hd), 1.0 / math.sqrt(D)),
                  "wv": _normal(a[2], (D, H * hd), 1.0 / math.sqrt(D)),
                  "wo": _normal(a[3], (H * hd, D), 1.0 / math.sqrt(H * hd)),
                  "bq": jnp.zeros((H * hd,), jnp.float32),
                  "bk": jnp.zeros((H * hd,), jnp.float32),
                  "bv": jnp.zeros((H * hd,), jnp.float32)},
         "norm2": _norm(D)}
    if kind == "moe":
        E, F = m["n_experts"], m["expert_d_ff"]
        e = jax.random.split(ks[5], 6)
        p["moe"] = {"wg": _normal(e[0], (D, E), 1.0 / math.sqrt(D)),
                    "w1": _normal(e[1], (E, D, F), 1.0 / math.sqrt(D)),
                    "w2": _normal(e[2], (E, F, D), 1.0 / math.sqrt(F))}
    else:
        F = m["d_ff"]
        f = jax.random.split(ks[6], 3)
        p["ffn"] = {"w_in": _normal(f[0], (D, F), 1.0 / math.sqrt(D)),
                    "w_out": _normal(f[1], (F, D), 1.0 / math.sqrt(F)),
                    "b_in": jnp.zeros((F,), jnp.float32),
                    "b_out": jnp.zeros((D,), jnp.float32)}
    return p


def init_params(key, m: dict) -> dict:
    """Weights from ``key`` by the configuration's init convention."""
    runs = layer_runs(m)
    keys = jax.random.split(key, len(runs) + 4)
    D, V = m["d_model"], m["vocab_size"]
    params = {"embed": {"table": _normal(keys[0], (V, D), 0.02)},
              "final_norm": _norm(D)}
    if not m["tie_embeddings"]:
        params["lm_head"] = {"w": jax.random.normal(
            keys[1], (D, V), jnp.float32) / math.sqrt(D)}
    for r, (kind, n) in enumerate(runs):
        ks = jax.random.split(keys[2 + r], n)
        params[f"run{r}"] = jax.vmap(
            lambda k, kind=kind: _init_block(k, m, kind))(ks)
    return params


# --- forward ----------------------------------------------------------------


def _act(name, x):
    if name == "silu":
        return x * jax.nn.sigmoid(x)
    if name == "gelu_tanh":
        c = math.sqrt(2.0 / math.pi)
        return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))
    raise ValueError(f"unknown activation {name!r}")


def _layernorm(p, x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return ((x - mu) * lax.rsqrt(var + eps) * p["scale"].astype(x.dtype)
            + p["bias"].astype(x.dtype))


def _positions(L, D):
    pos = jnp.arange(L, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, D, 2, dtype=jnp.float32)
                  * (-math.log(10000.0) / D))
    pe = jnp.zeros((L, D), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


def _attention(p, x, m, rows_per_block):
    """Causal multi-head attention, ``rows_per_block`` rows at a time so
    that the (rows, H, L, L) scores of the whole pool are never live."""
    b, L, D = x.shape
    H = m["n_heads"]
    hd = D // H

    def one(xb):
        r = xb.shape[0]
        q = (xb @ p["wq"] + p["bq"]).reshape(r, L, H, hd)
        k = (xb @ p["wk"] + p["bk"]).reshape(r, L, H, hd)
        v = (xb @ p["wv"] + p["bv"]).reshape(r, L, H, hd)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.asarray(
            math.sqrt(hd), xb.dtype)
        causal = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]
        s = jnp.where(causal, s, jnp.asarray(-1e30, s.dtype))
        s = s - jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s)
        w = e / jnp.sum(e, axis=-1, keepdims=True)
        o = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(r, L, H * hd)
        return o @ p["wo"]

    n = max(b // rows_per_block, 1)
    out = lax.map(jax.checkpoint(one), x.reshape(n, b // n, L, D))
    return out.reshape(b, L, D)


def _capacity_slots(expert_idx, n_experts):
    """Slot of each (token, choice) in its expert's buffer: all first
    choices of the pool take slots before any second choice, and within
    a choice tokens go in pool order (GShard's priority)."""
    S, k = expert_idx.shape
    flat = expert_idx.T.reshape(-1)                       # choice-major
    onehot = jax.nn.one_hot(flat, n_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1
    slot = jnp.sum(pos * onehot, axis=1)
    return slot.reshape(k, S).T


def _moe(p, x, m, cap):
    """One pool of tokens x (S, D) through the top-k MoE layer.
    Returns (y, aux_loss + z_loss)."""
    S, D = x.shape
    E, k = m["n_experts"], m["top_k"]
    logits = x @ p["wg"]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, idx = lax.top_k(probs, k)
    slot = _capacity_slots(idx, E)
    kept = slot < cap
    w = jnp.where(kept, gate_w, 0.0)
    flat = jnp.where(kept, idx * cap + slot, E * cap)     # E*cap = dropped
    buf = jnp.zeros((E * cap + 1, D), x.dtype)
    buf = buf.at[flat.reshape(-1)].set(
        jnp.repeat(x, k, axis=0), mode="drop")[:-1].reshape(E, cap, D)

    def expert(args):           # one expert's buffer, remade in backward
        xb, w1, w2 = args
        return _act(m["expert_act"], xb @ w1) @ w2
    out = lax.map(jax.checkpoint(expert), (buf, p["w1"], p["w2"]))
    out = out.reshape(E * cap, D)
    out = jnp.concatenate([out, jnp.zeros((1, D), out.dtype)])
    y = jnp.einsum("sk,skd->sd", w.astype(x.dtype), out[flat])
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32), axis=0)
    aux = m["aux_loss_weight"] * E * jnp.sum(me * ce)
    z = m["z_loss_weight"] * jnp.mean(
        jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return y, aux + z


def _ffn(p, x, m):
    h = _act(m["dense_act"], x @ p["w_in"] + p["b_in"])
    return h @ p["w_out"] + p["b_out"]


def _layer(p, x, *, m, kind, cap, rows_per_block):
    b, L, D = x.shape
    eps = m["norm_eps"]
    x = x + _attention(p["attn"], _layernorm(p["norm1"], x, eps), m,
                       rows_per_block)
    h = _layernorm(p["norm2"], x, eps)
    if kind == "moe":
        y, aux = _moe(p["moe"], h.reshape(b * L, D), m, cap)
        return x + y.reshape(b, L, D), aux
    return x + _ffn(p["ffn"], h, m), jnp.float32(0.0)


def pool_loss(p, tokens, labels, m, *, cap, n_tokens, n_pools,
              logit_rows=2048):
    """This pool's share of the batch loss: its summed token
    cross-entropy over ``n_tokens`` (all tokens of the batch) plus its
    router losses over ``n_pools``, so that the shares add up to the
    batch loss."""
    b, L = tokens.shape
    D = m["d_model"]
    rows_per_block = max(1, (4 << 20) // (L * L))   # 4 rows at L = 1024
    x = p["embed"]["table"][tokens] + _positions(L, D)
    # one period of the layer pattern is scanned over, so that the
    # program compiles one period and not every layer
    kinds, P = layer_kinds(m), m["moe_period"]
    if len(kinds) % P:
        raise ValueError("n_layers must be whole periods of moe_period")
    layers = [jax.tree.map(lambda a, i=i: a[i], p[f"run{r}"])
              for r, (_, n) in enumerate(layer_runs(m)) for i in range(n)]
    stacks = [jax.tree.map(lambda *a: jnp.stack(a), *layers[j::P])
              for j in range(P)]

    def period(carry, lps):
        x, aux_total = carry
        for j, lp in enumerate(lps):
            x, aux = jax.checkpoint(partial(
                _layer, m=m, kind=kinds[j], cap=cap,
                rows_per_block=rows_per_block))(lp, x)
            aux_total = aux_total + aux
        return (x, aux_total), None

    (x, aux_total), _ = lax.scan(period, (x, jnp.float32(0.0)), stacks)
    x = _layernorm(p["final_norm"], x, m["norm_eps"])
    head = (p["embed"]["table"].T if m["tie_embeddings"]
            else p["lm_head"]["w"])

    def ce_rows(args):
        xc, yc = args
        logp = jax.nn.log_softmax((xc @ head).astype(jnp.float32), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, yc[:, None], axis=-1))

    xs = x.reshape(b * L, D)
    ys = labels.reshape(b * L)
    n = max((b * L) // logit_rows, 1)
    ce = jnp.sum(lax.map(jax.checkpoint(ce_rows),
                         (xs.reshape(n, -1, D), ys.reshape(n, -1))))
    return ce / n_tokens + aux_total / n_pools


# --- optimizer -----------------------------------------------------------------


def _lr(o, step):
    warm = jnp.minimum(step / max(o["warmup_steps"], 1), 1.0)
    prog = jnp.clip((step - o["warmup_steps"])
                    / max(o["total_steps"] - o["warmup_steps"], 1), 0.0, 1.0)
    cos = o["min_lr_frac"] + (1 - o["min_lr_frac"]) * 0.5 * (
        1 + jnp.cos(math.pi * prog))
    return o["lr"] * warm * cos


def _leaf_norms(tree):
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32)))), tree)


@partial(jax.jit, static_argnames=("m", "o", "cap", "n_pools", "fault"),
         donate_argnums=(0, 1, 2))
def _step(params, mu, nu, tokens, labels, step, *, m, o, cap, n_pools,
          fault):
    """One AdamW step; ``step`` (1, 2, ...) is traced, so that every step
    runs one compiled program.  The state is donated: its new values take
    the old ones' memory."""
    m, o = dict(m), dict(o)
    B, L = tokens.shape
    rows = B // n_pools
    tok = tokens.reshape(n_pools, rows, L)
    lab = labels.reshape(n_pools, rows, L)
    n_tokens = B * L
    if fault == "half_batch":
        tok, lab = tok[:, : rows // 2], lab[:, : rows // 2]
        n_tokens //= 2

    def body(acc, xs):
        t, y = xs
        loss, g = jax.value_and_grad(pool_loss)(
            params, t, y, m, cap=cap, n_tokens=n_tokens, n_pools=n_pools)
        return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g)), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (loss, grads), _ = lax.scan(body, (jnp.float32(0.0), zeros), (tok, lab))
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
        return p - lr * delta, m1, v1, g

    out = jax.tree.map(upd, params, grads, mu, nu)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,  # noqa: E731
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), loss, _leaf_norms(pick(3))


@functools.lru_cache(maxsize=8)
def _init_jit(m: tuple):
    return jax.jit(partial(init_params, m=dict(m)))


@jax.jit
def _change_norms(a, b):
    return _leaf_norms(jax.tree.map(jnp.subtract, a, b))


def _frozen(d: dict):
    return tuple(sorted((k, v) for k, v in d.items()
                        if isinstance(v, (int, float, str, bool))))


def train_steps(key, model_cfg: dict, opt_cfg: dict, batches, *, pools,
                fault: str = "", device=None) -> dict:
    """Run ``len(batches)`` AdamW steps from the seeded weights, each
    batch gated in ``pools = (n_pools, cap)``: ``n_pools`` pools of
    contiguous rows, ``cap`` slots per expert in each.

    Returns ``{"loss": [...], "grad_norms": {leaf: norm}, "change_norms":
    {leaf: norm}}``: the loss of every step, the per-leaf norms of the
    clipped gradient of step one, and the per-leaf norms of the change of
    the parameters over all steps.
    """
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


def flat_norms(tree) -> dict:
    """{"run0/attn/wq": float, ...} from a tree of scalar norms."""
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(k, "key", k)) for k in path)] = float(v)
    return out
