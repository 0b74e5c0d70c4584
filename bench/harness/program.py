"""The system under test, driven through its normal entry.

Everything the benchmark takes from the program goes through here: the
configuration it runs (built from the benchmark's configuration file),
``Trainer`` (``setup`` makes the weights on the device from the seed,
``compile`` builds the step, ``run`` is the timed entry), the schedule
decisions autosched made, the kernels in the compiled step, and the
per-expert routed-row counter of each step."""

from __future__ import annotations

import functools
import re
from dataclasses import replace

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from bench.harness.spec import SpecError


def seed_key(seed: int):
    """A PRNG key from any whole seed below 2**64 (PRNGKey alone keeps
    only the low 32 bits)."""
    if not 0 <= seed < 2 ** 64:
        raise SpecError(f"--seed {seed}: want 0 <= seed < 2**64")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


ACT = {"gelu_tanh": "gelu"}   # the program's names for the activations


def program_config(conf: dict, dtype: str = "float32"):
    """The program's ModelConfig with the widths of the benchmark's
    configuration file; refuses a file the program cannot run as
    written.  ``dtype`` other than float32 is the program's own
    lower-precision path (the control), never a benchmark run."""
    from repro.configs import get_config
    m = conf["model"]
    cfg = get_config(conf["program_config"])
    cfg = replace(
        cfg, dtype=dtype, n_layers=m["n_layers"], d_model=m["d_model"],
        n_heads=m["n_heads"], n_kv_heads=m["n_heads"], d_ff=m["d_ff"],
        vocab_size=m["vocab_size"], tie_embeddings=m["tie_embeddings"],
        norm_eps=m["norm_eps"], moe_period=m["moe_period"],
        moe=replace(cfg.moe, d_model=m["d_model"], d_ff=m["expert_d_ff"],
                    n_experts=m["n_experts"], top_k=m["top_k"],
                    capacity_factor=m["capacity_factor"],
                    aux_loss_weight=m["aux_loss_weight"],
                    z_loss_weight=m["z_loss_weight"],
                    act=ACT.get(m["expert_act"], m["expert_act"])))
    fixed = {"arch_type": "moe", "norm_type": "layernorm",
             "use_rope": False, "qkv_bias": True, "ffn_bias": True,
             "glu": False, "ffn_act": ACT.get(m["dense_act"],
                                              m["dense_act"]),
             "dtype": dtype, "logit_scale": 1.0,
             "parallel_block": False, "attn_window": None,
             "attn_chunk": None}
    for k, want in fixed.items():
        if getattr(cfg, k) != want:
            raise SpecError(f"{conf['name']}: the program's {k} is "
                            f"{getattr(cfg, k)!r}, the reference's {want!r}")
    if cfg.moe.glu or \
            cfg.moe.n_shared_experts or cfg.moe.normalize_topk:
        raise SpecError(f"{conf['name']}: the program's experts are not "
                        f"the reference's ({cfg.moe})")
    return cfg


def adamw_config(conf: dict):
    from repro.optim import AdamWConfig
    o = conf["optimizer"]
    if o["decay_min_rank"] != 2:
        raise SpecError("the program decays leaves of rank >= 2 only")
    return AdamWConfig(
        lr=o["lr"], beta1=o["beta1"], beta2=o["beta2"], eps=o["eps"],
        weight_decay=o["weight_decay"], clip_norm=o["clip_norm"],
        warmup_steps=o["warmup_steps"], total_steps=o["total_steps"],
        min_lr_frac=o["min_lr_frac"])


class Feed:
    """The data object ``Trainer.run`` pulls batches from: the traffic
    generator's batch ``offset + step``, laid out on the mesh, inside a
    host span ``input``.  ``offset`` moves on after each call of ``run``
    so that no batch is fed twice."""

    def __init__(self, gen):
        self.gen = gen
        self.offset = 0

    def sharded_batch(self, step, mesh, batch_axes):
        with jax.profiler.TraceAnnotation("input"):
            toks, labels = self.gen.batch(self.offset + step)
            sh = NamedSharding(mesh, P(tuple(batch_axes) or None, None))
            return {k: jax.make_array_from_callback(
                        v.shape, sh, lambda idx, v=v: v[idx])
                    for k, v in (("tokens", toks), ("labels", labels))}


def leaf_norms(tree) -> dict:
    """{"run0/attn/wq": norm, ...}, one host read for the whole tree."""
    norms = _norms(tree)
    flat = jax.tree_util.tree_flatten_with_path(jax.device_get(norms))[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path): float(v)
            for path, v in flat}


@functools.lru_cache(maxsize=4)
def _change_fn(init_fn):
    return jax.jit(lambda p, k: jax.tree.map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32)))), p, init_fn(k)))


@jax.jit
def _norms(tree):
    return jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


def change_norms(params, init_fn, key) -> dict:
    """Per-leaf norm of params - init(key), the weights re-made from the
    seed by the program's own init."""
    norms = _change_fn(init_fn)(params, key)
    flat = jax.tree_util.tree_flatten_with_path(jax.device_get(norms))[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path): float(v)
            for path, v in flat}


def kernels_in_hlo(hlo: str) -> dict:
    """Pallas calls in a compiled HLO text: kernel name -> list of calls,
    each {"operands": [(dtype, shape), ...]} (every ``pallas_call`` is
    named after its registry op)."""
    calls = {}
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r"/(\w+)/pallas_call", line)
        if not m:
            continue
        lc = re.search(r"operand_layout_constraints=\{(.*?)\}\}", line)
        ops = [(dt, tuple(int(x) for x in dims.split(",") if x))
               for dt, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]",
                                          lc.group(1) + "}" if lc else "")]
        calls.setdefault(m.group(1), []).append({"operands": ops})
    return calls
