"""Operation and byte counts against hand counts at small shapes."""

import bench_helpers  # noqa: F401  (the repo root and src on the path)

import pytest

from bench.flops import model as model_flops
from bench.flops.kernels import expert_ffn_grouped
from bench.flops.kernels import flash_attention


def test_model_flops_by_hand():
    m = {"n_layers": 2, "d_model": 8, "n_heads": 2, "d_ff": 16,
         "vocab_size": 10, "moe_period": 2, "n_experts": 4, "top_k": 2,
         "expert_d_ff": 12}
    L = 3
    # layer 0 MoE, layer 1 dense; per token, forward:
    attn = 2 * 4 * 64 + 2 * 2 * 8 * 2          # projections + (L+1)/2 = 2 keys
    dense = 2 * 2 * 8 * 16
    moe = 2 * 8 * 4 + 2 * 2 * 2 * 8 * 12
    logits = 2 * 8 * 10
    fwd = 2 * attn + dense + moe + logits
    assert model_flops.forward_flops_per_token(m, L) == fwd
    assert model_flops.train_flops_per_token(m, L) == 3 * fwd


def test_gpt2_moe_flops_per_token():
    import json, os
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "..", "bench", "configs",
                           "gpt2-moe.json")) as f:
        m = json.load(f)["model"]
    got = model_flops.train_flops_per_token(m, 1024)
    assert 0.95e9 < got < 1.0e9       # 967.9 MFLOP by hand (PERF.md)


def test_flash_attention_causal_band():
    call = {"operands": [("f32", (1, 1, 256, 64))] * 3}
    ops, nbytes = flash_attention.ops_bytes(call)
    pairs = 1 + 2                      # query block 0 sees 1 KV block, 1 sees 2
    assert ops == pairs * 2 * (2 * 128 * 128 * 64)
    assert nbytes == 4 * (256 * 64 + pairs * 2 * 128 * 64 + 256 * 64)


def test_expert_ffn_grouped_by_hand():
    E, M, F = 2, 8, 16
    call = {"operands": [("s32", (E * 256,)), ("s32", (E,)),
                         ("f32", (100, 1, M)), ("f32", (E, 256, 1)),
                         ("f32", (E, M, F)), ("f32", (E, F, M)),
                         ("f32", (101, 1, M))]}
    m = {"n_layers": 2, "moe_period": 2}       # one MoE layer
    # two steps: expert 0 routes 130 then 150 rows (140 on average:
    # 2 tiles of 128), expert 1 routes 10 (1 tile)
    ctx = {"loads": [[130, 10], [150, 10]], "model": m}
    ops, nbytes = expert_ffn_grouped.ops_bytes(call, ctx)
    rows, tiles = 150, 3
    assert ops == 2 * rows * M * F * 2
    assert nbytes == 4 * (tiles * M * F * 2 + rows * M) + 4 * 2 * rows * M
    assert expert_ffn_grouped.ops_bytes(call, {"loads": [None], "model": m}
                                        ) is None


def test_model_flops_gqa_glu_by_hand():
    """GQA (4 query heads, 2 key/value heads), a head width that is not
    d / H, and GLU dense and expert FFNs."""
    m = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 4, "d_ff": 16, "dense_glu": True, "vocab_size": 10,
         "moe_period": 2, "n_experts": 4, "top_k": 2, "expert_d_ff": 12,
         "expert_glu": True}
    L = 3
    # q and o are 8 -> 16 and 16 -> 8, k and v 8 -> 8; scores and values
    # over (L+1)/2 = 2 keys at q width 16
    attn = 2 * (2 * 8 * 16 + 2 * 8 * 8) + 2 * 2 * 16 * 2
    dense = 2 * 3 * 8 * 16
    moe = 2 * 8 * 4 + 2 * 2 * 3 * 8 * 12
    logits = 2 * 8 * 10
    fwd = 2 * attn + dense + moe + logits
    assert fwd == 3936
    assert model_flops.train_flops_per_token(m, L) == 3 * fwd


@pytest.mark.parametrize("name, L, V, want", [
    ("gpt2-moe", 1024, 50257, 967_961_088),
    ("bert-moe", 128, 30522, 827_476_992)])
def test_todays_configs_exact_counts(name, L, V, want):
    """The benchmark's two configurations (12 layers, d 768, 12 heads,
    FFN 3072, every other FFN 8 experts top-2 of 3072): the counts the
    per-layer metric ``train.mfu`` has read since it came."""
    import json, os
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "..", "bench", "configs",
                           f"{name}.json")) as f:
        m = json.load(f)["model"]
    d = 768
    attn = 2 * 4 * d * d + 2 * 2 * d * (L + 1) / 2
    dense = 2 * 2 * d * 3072
    moe = 2 * d * 8 + 2 * 2 * 2 * d * 3072
    fwd = 12 * attn + 6 * dense + 6 * moe + 2 * d * V
    assert 3 * fwd == want
    assert model_flops.train_flops_per_token(m, L) == want
