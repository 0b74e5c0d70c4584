"""AdamW with gradient clipping, cosine LR schedule, and sharding-aware
optimizer state (moments inherit the parameter PartitionSpecs; optional
ZeRO-1 shards the leading dim over the DP axes when divisible).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_frac: float = 0.1
    zero1: bool = False


def cosine_schedule(cfg: AdamWConfig, step):
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                    0.0, 1.0)
    cos = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (
        1 + jnp.cos(math.pi * prog))
    return cfg.lr * warm * cos


def adamw_init(params):
    zeros = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
    return {"mu": zeros,
            "nu": jax.tree.map(jnp.copy, zeros),
            "step": jnp.zeros((), jnp.int32)}


def opt_state_specs(param_specs, mesh=None, dp_axes=(), zero1=False,
                    params_shape=None):
    """Moments inherit param specs; ZeRO-1 additionally shards dim 0 over
    the DP axes when the dim is divisible and currently unsharded."""
    from repro.parallel.mesh import axis_size

    def z1(spec, shaped):
        if not zero1 or not dp_axes or mesh is None:
            return spec
        parts = list(spec) + [None] * (len(shaped.shape) - len(spec))
        n = axis_size(mesh, dp_axes)
        # shard the largest still-unsharded dim divisible by n (dim 0 is
        # often the layer-stack axis, rarely divisible)
        best = None
        for i, (d, sp) in enumerate(zip(shaped.shape, parts)):
            if sp is None and d % max(n, 1) == 0 and d >= n:
                if best is None or d > shaped.shape[best]:
                    best = i
        if best is not None:
            parts[best] = tuple(dp_axes)
            return P(*parts)
        return spec

    if zero1 and params_shape is not None:
        mom = jax.tree.map(z1, param_specs, params_shape,
                           is_leaf=lambda x: isinstance(x, P))
    else:
        mom = param_specs
    return {"mu": mom, "nu": jax.tree.map(lambda s: s, mom,
                                          is_leaf=lambda x: isinstance(x, P)),
            "step": P()}


def global_norm(grads):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grads)))


def adamw_update(params, grads, state, cfg: AdamWConfig,
                 decay_mask=None, lr_scale=1.0, finite=None):
    """One AdamW step. decay_mask: pytree of bool (True = apply WD);
    defaults to ndim >= 2 leaves (no WD on norms/biases/gates).
    ``lr_scale`` multiplies the scheduled LR (the guard rails' dynamic
    backoff knob); the default 1.0 is bit-exact with no scaling.

    ``finite`` (a traced bool scalar, e.g. ``isfinite(loss)``) opts into
    the guard rails' skip-step: it is AND-ed with ``isfinite(grad_norm)``
    and the select ``where(finite, new, old)`` is applied *inside* each
    leaf's update expression — XLA fuses it into the same elementwise
    loop as the update itself, so the guarded step costs no extra memory
    pass over the trees (a separate post-hoc tree-select measurably does
    not fuse).  A masked-out step leaves params, moments, and the step
    counter bit-identical to never having run; the combined mask comes
    back in the metrics as ``"finite"``.  The whole update, the global
    norm included, carries the ``adamw`` scope, which the device trace
    reads."""
    with jax.named_scope("adamw"):
        step = state["step"] + 1
        lr = cosine_schedule(cfg, step) * lr_scale
        gnorm = global_norm(grads)
        scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-9))
        if finite is not None:
            finite = finite & jnp.isfinite(gnorm)

        if decay_mask is None:
            decay_mask = jax.tree.map(lambda p: p.ndim >= 2, params)

        b1c = 1 - cfg.beta1 ** step.astype(jnp.float32)
        b2c = 1 - cfg.beta2 ** step.astype(jnp.float32)

        def upd(p, g, mu0, nu0, wd):
            g = g.astype(jnp.float32) * scale
            mu = cfg.beta1 * mu0 + (1 - cfg.beta1) * g
            nu = cfg.beta2 * nu0 + (1 - cfg.beta2) * jnp.square(g)
            mhat = mu / b1c
            nhat = nu / b2c
            delta = mhat / (jnp.sqrt(nhat) + cfg.eps)
            if wd:
                delta = delta + cfg.weight_decay * p.astype(jnp.float32)
            p2 = (p.astype(jnp.float32) - lr * delta).astype(p.dtype)
            if finite is not None:
                p2 = jnp.where(finite, p2, p)
                mu = jnp.where(finite, mu, mu0)
                nu = jnp.where(finite, nu, nu0)
            return p2, mu, nu

        flat_p, tdef = jax.tree.flatten(params)
        flat_g = tdef.flatten_up_to(grads)
        flat_mu = tdef.flatten_up_to(state["mu"])
        flat_nu = tdef.flatten_up_to(state["nu"])
        flat_wd = tdef.flatten_up_to(decay_mask)
        new = [upd(p, g, mu, nu, wd) for p, g, mu, nu, wd
               in zip(flat_p, flat_g, flat_mu, flat_nu, flat_wd)]
        new_p = tdef.unflatten([t[0] for t in new])
        new_state = {"mu": tdef.unflatten([t[1] for t in new]),
                     "nu": tdef.unflatten([t[2] for t in new]),
                     "step": step if finite is None
                     else jnp.where(finite, step, state["step"])}
        om = {"grad_norm": gnorm, "lr": lr}
        if finite is not None:
            om["finite"] = finite
        return new_p, new_state, om
