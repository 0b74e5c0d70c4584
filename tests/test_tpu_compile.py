"""Compile the main-path Pallas kernels for a TPU v5e, without the chip.

The TPU compiler is installed with JAX, and it compiles for a topology
that is described but not attached (``v5e:2x2``).  Interpret mode on the
CPU cannot see what Mosaic refuses — block shapes off the (8, 128) tile,
more VMEM than a kernel may hold, a kernel GSPMD would have to partition
— so each kernel is compiled here at gpt2-moe's published widths
(M=768, expert FFN 3072 with GELU, E=8, top-2, capacity factor 1.2,
8192 tokens per step; attention B=8, L=1024, H=12, hd=64, and both
cells' and a GQA shape with several heads a grid step), rmsnorm at
qwen3's 2048.  Nothing runs: a pass says the chip's compiler takes the
kernel, not that it computes the right thing (the interpret-mode parity
tests say that).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and pytest-xdist workers each import
this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels.registry import KernelConfig, get_op

E, M, F, K, TOKENS = 8, 768, 3072, 2, 8192
CAP = 2464            # moe.capacity(8192 tokens, E=8, top-2, cf 1.2)
PALLAS = KernelConfig(backend="pallas", interpret=False)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _cases(sh):
    f32, i32 = jnp.float32, jnp.int32

    def s(shape, dt=f32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    return {
        "flash_attention": (dict(causal=True),
                            [s((8, 1024, 12, 64))] * 3),
        "expert_ffn": (dict(act="gelu"),
                       [s((E, CAP, M)), s((E, M, F)), None, s((E, F, M))]),
        "expert_ffn_ragged": (dict(act="gelu"),
                              [s((E, 1, CAP, M)), s((E, 1), i32),
                               s((E, M, F)), None, s((E, F, M))]),
        "expert_ffn_grouped": (dict(act="gelu", cap=CAP, wire="f32"),
                               [s((TOKENS, M)), s((TOKENS, K), i32),
                                s((TOKENS, K)), s((E, M, F)), None,
                                s((E, F, M))]),
        "moe_dispatch": (dict(n_slots=E * CAP),
                         [s((TOKENS, M)), s((TOKENS, K), i32)]),
        "moe_combine": (dict(), [s((E * CAP, M)), s((TOKENS, K), i32),
                                 s((TOKENS, K))]),
        "rmsnorm": (dict(eps=1e-6), [s((TOKENS, 2048)), s((2048,))]),
    }


@pytest.mark.parametrize("op", [
    "flash_attention", "expert_ffn", "expert_ffn_ragged",
    "expert_ffn_grouped", "moe_dispatch", "moe_combine", "rmsnorm"])
def test_kernel_compiles_for_v5e(op, one_chip, no_compile_cache):
    static, args = _cases(one_chip)[op]
    compiled = jax.jit(get_op(op, cfg=PALLAS, **static)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("B,L,H,K,hd", [
    (16, 1024, 12, 12, 64),    # gpt2-moe.train-s1024
    (128, 128, 12, 12, 64),    # bert-moe.train-s128
    (4, 1024, 32, 8, 128),     # GQA, rep 4
])
def test_flash_attention_heads_per_step_compiles_for_v5e(
        B, L, H, K, hd, one_chip, no_compile_cache):
    """Several heads a grid step: the blocks, scratch and score tiles
    that ``heads_per_step`` sizes must fit Mosaic's VMEM and tiling."""
    q = jax.ShapeDtypeStruct((B, L, H, hd), jnp.float32, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, L, K, hd), jnp.float32, sharding=one_chip)
    op = get_op("flash_attention", cfg=PALLAS, causal=True)
    compiled = jax.jit(op).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_attention_kernel_compiles_on_four_chip_mesh(topo, no_compile_cache):
    """On a (data, model) mesh the kernel runs per shard: GSPMD would
    refuse to partition it ("Mosaic kernels cannot be automatically
    partitioned")."""
    from repro.models.attention import AttnConfig, _flash_op
    from repro.parallel.mesh import ParallelDims, make_mesh

    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices[:4])
    dims = ParallelDims(ep=("data",), esp=("model",), mp=("model",))
    cfg = AttnConfig(d_model=M, n_heads=12, n_kv_heads=12, head_dim=64)
    x = jax.ShapeDtypeStruct((8, 1024, 12, 64), jnp.float32,
                             sharding=NamedSharding(
                                 mesh, P("data", None, "model", None)))
    op = _flash_op(cfg, PALLAS, x, x, mesh, dims)
    compiled = jax.jit(op).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()
