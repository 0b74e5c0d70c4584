"""Operation and byte counts against hand counts at small shapes."""

import bench_helpers  # noqa: F401  (the repo root and src on the path)

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
