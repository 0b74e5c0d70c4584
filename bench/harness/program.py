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

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from bench.harness.spec import SpecError, family_part


def seed_key(seed: int):
    """A PRNG key from any whole seed below 2**64 (PRNGKey alone keeps
    only the low 32 bits)."""
    if not 0 <= seed < 2 ** 64:
        raise SpecError(f"--seed {seed}: want 0 <= seed < 2**64")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def program_config(conf: dict, dtype: str = "float32"):
    """The program's ModelConfig for a configuration file, built by its
    family's ``bench/programs/<family>.py``.  ``dtype`` other than
    float32 is the program's own lower-precision path (the control),
    never a benchmark run."""
    return family_part("programs", conf).program_config(conf, dtype)


def adamw_config(conf: dict):
    """The program's AdamWConfig for a configuration file, built by its
    family's ``bench/programs/<family>.py``."""
    return family_part("programs", conf).adamw_config(conf)


MP_STAGES = ("mp_split", "ag_mp", "rs_mp")   # plan stages at the MP boundary


def gate_layout(schedule: str) -> tuple:
    """(kind, over_mp): the token pool a training schedule's gate sees,
    as the program's plan for it declares.  ``kind`` is the ``cap`` of
    its ``gate`` stage: ``pool`` (the device's whole pool), ``mp_shard``
    (this MP rank's 1/N_MP slice of it) or ``esp_pool`` (the pool
    gathered over ESP).  ``over_mp``: the plan has no stage that moves
    between the layout replicated over MP and the split one (``MP_STAGES``),
    so its input is already distinct over MP (the sequence-parallel
    contract) and the pools split over MP as well as over the batch
    axes."""
    from repro.core import plan as planlib
    from repro.core.perfmodel import MoELayerShape
    from repro.core.pipeline import UNCHUNKED_OF
    name = UNCHUNKED_OF.get(schedule, schedule)
    if planlib.PLANS[name].decode_only:
        raise SpecError(f"schedule {schedule!r} is for decoding only")
    shape = MoELayerShape(B=1, L=8, M=8, H=8, E=2, k=1, f=1.0)
    plan = planlib.plan_for_shape(name, shape)
    kind = next(s.p("cap", "pool") for s in plan.stages if s.kind == "gate")
    return kind, not any(s.kind in MP_STAGES for s in plan.stages)


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
