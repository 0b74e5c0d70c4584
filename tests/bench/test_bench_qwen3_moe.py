"""The Qwen3-MoE family at a small size on the CPU: the program through
``Trainer`` against the plain reference, the experts held here as a
share of the layer, QK-norm, the attention VJP in query blocks, the
family's program entry and its FLOPs."""

import bench_helpers  # noqa: F401  (the repo root and src on the path)

import json
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_helpers import ROOT, tiny_cell

SMALL = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
             vocab_size=256, n_experts=16, n_experts_held=4, top_k=4,
             expert_d_ff=32)


def small_cell(**over):
    return tiny_cell("qwen3-moe", seq=64, **{**SMALL, **over})


def test_reference_init_is_the_programs():
    from bench.harness import program
    from bench.reference.qwen3_moe import init_params
    from repro.models import build_model
    conf = small_cell().config
    key = program.seed_key(2 ** 33 + 9)
    ours = init_params(key, conf["model"])
    theirs = build_model(program.program_config(conf)).init(key)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("held", [4, 16])
def test_program_matches_reference(held):
    """Loss, step-1 gradient and the 3-step update through the
    harness's correctness path (``Trainer.run`` on the program)."""
    from bench.harness import train
    cell = small_cell(n_experts_held=held)
    res = train.run(cell, seed=2 ** 32 + 17, seconds=0.5, trace=False,
                    devices=jax.devices()[:1], t_start=0.0, out_dir=None)
    assert res["correct"], res["compare"]
    for _, value, _, _, _ in res["compare"]:
        assert value < 1e-4


def _layer_inputs(seed=3, S=96, D=64, E=16, F=32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (S, D))
    w = {"wg": jax.random.normal(ks[1], (D, E)) / 8,
         "w1": jax.random.normal(ks[2], (E, D, F)) / 8,
         "w3": jax.random.normal(ks[3], (E, D, F)) / 8,
         "w2": jax.random.normal(ks[4], (E, F, D)) / 6}
    return x, w


def _share(w, lo, hi):
    """Share [lo, hi) as the held experts [0, hi - lo) of a layer whose
    router's columns are rolled so that expert lo comes first: routing
    and GShard slots are the same up to the relabelling."""
    order = np.roll(np.arange(w["wg"].shape[1]), -lo)
    return {"wg": w["wg"][:, order], "w1": w["w1"][lo:hi],
            "w3": w["w3"][lo:hi], "w2": w["w2"][lo:hi]}


@pytest.mark.parametrize("side", ["s1g", "s1", "s2h", "reference"])
def test_shares_sum_to_the_whole_layer(side):
    """The held outputs of the four shares [0,4) ... [12,16) add up to
    the uncut layer's output, in the reference and in the program under
    the schedule named."""
    x, w = _layer_inputs()
    S, D = x.shape
    E, k = 16, 4
    cap = 24                        # k * S / E: some routes past it
    if side != "reference":
        from repro.core.moe import MoEConfig, apply_moe
        from repro.launch.mesh import local_mesh
        from repro.configs import get_config
        mesh, dims = local_mesh(get_config("qwen3-moe-30b-a3b"),
                                jax.devices()[:1])

        def layer(p, held):
            cfg = MoEConfig(d_model=D, d_ff=32, n_experts=E, top_k=k,
                            capacity_factor=1.0,
                            normalize_topk=True, n_held=held,
                            schedule=side)
            return apply_moe(x[None], p, mesh=mesh, dims=dims, cfg=cfg)[0][0]
    else:
        from bench.reference.qwen3_moe import _moe
        m = {"n_experts": E, "top_k": k, "normalize_topk": True,
             "aux_loss_weight": 0.0, "z_loss_weight": 0.0}

        def layer(p, held):
            with jax.default_matmul_precision("highest"):
                return _moe(p, x, dict(m, n_experts_held=held), cap)[0]
    whole = layer(w, E)
    parts = sum(layer(_share(w, lo, lo + 4), 4) for lo in range(0, E, 4))
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(layer(_share(w, 4, 8), 4)))) > 0


def test_qk_norm_is_rmsnorm_over_each_head():
    from repro.models.attention import AttnConfig, qk_norm
    cfg = AttnConfig(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                     qk_norm=True, norm_eps=1e-6)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (2, 8, 4, 16)) * 3
    k = jax.random.normal(ks[1], (2, 8, 2, 16))
    p = {"q_norm": jax.random.normal(ks[2], (16,)),
         "k_norm": jax.random.normal(ks[3], (16,))}
    qn, kn = qk_norm(p, cfg, q, k)
    for a, b, s in ((q, qn, p["q_norm"]), (k, kn, p["k_norm"])):
        a, s = np.asarray(a, np.float64), np.asarray(s, np.float64)
        want = a / np.sqrt(np.mean(a ** 2, -1, keepdims=True) + 1e-6) * s
        np.testing.assert_allclose(np.asarray(b), want, rtol=1e-5,
                                   atol=1e-6)
    assert qk_norm(p, replace(cfg, qk_norm=False), q, k) == (q, k)


@pytest.mark.parametrize("window", [None, 24])
def test_blocked_attention_vjp_equals_one_block(monkeypatch, window):
    """The VJP forced to 8 query blocks gives the one-block VJP."""
    from repro.kernels import registry
    from repro.kernels.registry import KernelConfig, get_op
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (2, 64, 4, 16))
    k = jax.random.normal(ks[1], (2, 64, 2, 16))
    v = jax.random.normal(ks[2], (2, 64, 2, 16))
    g = jax.random.normal(ks[3], (2, 64, 4, 16))
    cfg = KernelConfig(backend="pallas", block_q=16, block_k=16)

    def grads(limit):
        monkeypatch.setattr(registry, "VJP_SCORE_BYTES", limit)
        registry._build.cache_clear()
        op = get_op("flash_attention", cfg=cfg, causal=True, window=window)
        return jax.vjp(op, q, k, v)[1](g)

    one = grads(1 << 30)
    blocked = grads(2 * 4 * 8 * 64 * 4)          # 8 blocks of 8 rows
    assert registry.vjp_query_blocks(2, 4, 64, 64) == 8
    registry._build.cache_clear()
    for a, b in zip(one, blocked):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-5,
                                   atol=1e-5)


@pytest.mark.parametrize("B,H,L,want", [
    (16, 12, 1024, 1),      # gpt2-moe.train-s1024, one chip
    (128, 12, 128, 1),      # bert-moe.train-s128
    (32, 6, 1024, 1),       # gpt2-moe.train-s1024-x4, one shard
    (4, 32, 4096, 8),       # qwen3-moe.train-s4096: 512 rows a block
])
def test_vjp_query_blocks_at_the_cells_shapes(B, H, L, want):
    from repro.kernels.registry import vjp_query_blocks
    assert vjp_query_blocks(B, H, L, L) == want


@pytest.mark.parametrize("key,value", [
    ("qk_norm", False), ("normalize_topk", False), ("expert_glu", False),
    ("attention_bias", True), ("tie_embeddings", True), ("n_shared_experts", 1),
])
def test_program_config_refuses_what_the_reference_lacks(key, value):
    from bench.harness import program
    from bench.harness.spec import SpecError
    conf = small_cell(**{key: value}).config
    with pytest.raises(SpecError):
        program.program_config(conf)


def test_program_config_holds_the_share():
    from bench.harness import program
    with open(os.path.join(ROOT, "bench", "configs", "qwen3-moe.json")) as f:
        conf = json.load(f)
    cfg = program.program_config(conf)
    assert (cfg.moe.n_experts, cfg.moe.held, cfg.moe.top_k) == (128, 16, 8)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.vocab_size) == \
        (32, 4, 128, 18992)
    assert cfg.qk_norm and cfg.norm_eps == 1e-6 and cfg.rope_theta == 1e6
    assert conf["published"] == {"n_layers": 48, "n_experts_held": 128,
                                 "vocab_size": 151936}


def test_flops_by_hand():
    from bench.flops import qwen3_moe
    m = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 4, "n_experts": 8, "n_experts_held": 2, "top_k": 4,
         "expert_d_ff": 6, "vocab_size": 10}
    L = 3
    attn = 2 * (2 * 8 * 16 + 2 * 8 * 8) + 2 * 2 * 16 * 2
    moe = 2 * 8 * 8 + 4 * (2 / 8) * 2 * 3 * 8 * 6
    fwd = 2 * (attn + moe) + 2 * 8 * 10
    assert qwen3_moe.forward_flops_per_token(m, L) == fwd
    assert qwen3_moe.train_flops_per_token(m, L) == 3 * fwd


def test_moe_share_event_and_refusal(tmp_path):
    """``apply_moe`` states its share at trace time (``moe.share``), and
    refuses a share larger than the router."""
    from repro import obs
    from repro.core.moe import MoEConfig, apply_moe, init_moe_params
    from repro.obs.sink import read_events
    from repro.parallel.mesh import ParallelDims, make_mesh
    cfg = MoEConfig(d_model=64, d_ff=32, n_experts=16, top_k=4, n_held=4)
    p = init_moe_params(jax.random.PRNGKey(0), cfg)
    assert p["wg"].shape == (64, 16) and p["w1"].shape == (4, 64, 32)
    mesh = make_mesh((1, 1), ("data", "model"))
    dims = ParallelDims(ep=("data",), esp=("model",), mp=("model",))
    x = jnp.ones((2, 48, 64))
    sink = obs.configure(str(tmp_path))
    try:
        jax.eval_shape(lambda x: apply_moe(x, p, mesh=mesh, dims=dims,
                                           cfg=cfg)[0], x)
        obs.flush()
        ev = [e for e in read_events(sink.paths)
              if e["event"] == "moe.share"]
    finally:
        obs.close()
    assert [(e["n_experts"], e["n_held"], e["cap"]) for e in ev] == \
        [(16, 4, 32)]                    # 4 * 1.25 * 96 / 16 = 30 -> 32
    with pytest.raises(ValueError, match="n_held"):
        apply_moe(x, p, mesh=mesh, dims=dims, cfg=replace(cfg, n_held=17))


def test_layer_readers_of_the_share():
    """The cell's readers on a hand-laid trace: one op of each kind."""
    from bench.harness.spec import metric_reader
    ms = 1_000_000
    step = "jit(train_step)/"
    body = step + "jvp()/while/body/closed_call/"
    bwd = step + "transpose(jvp())/while/body/closed_call/checkpoint/"
    laid = {"f.1": (body + "attn/qk_norm/rsqrt", 1),
            "f.2": (body + "attn/dot_general", 2),
            "f.3": (bwd + "attn/scan/dot_general", 4),
            "f.4": (bwd + "transpose(jvp(attn))/qk_norm/mul", 1),
            "f.5": (body + "s1g.gate/top_k", 3),
            "f.6": (step + "transpose(jvp(s1g.gate))/dot_general", 2),
            "f.7": (body + "s1g.comb/jit(op)/add", 5),
            "f.8": (body + "norm/rsqrt", 1)}
    ops, t = [], 0
    for name, (_, d) in laid.items():
        ops.append((name, "fusion", t, d * ms))
        t += (d + 1) * ms
    run = {"traced": {"devices": {"/device:TPU:0": ops},
                      "index": {k: p for k, (p, _) in laid.items()},
                      "host": [], "window": (0, t), "n_steps": 1},
           "schedules": ["s1g"]}

    def read(name):
        return metric_reader(name)(run)
    assert read("attention.qk_norm_device_ms") == 2
    assert read("attention.blocked_bwd_device_ms") == 5
    assert read("moe.gate_device_ms") == 5
    assert read("moe.held_device_ms") == read("moe.device_ms")
    assert read("moe.gate_device_ms") <= read("moe.held_device_ms")


@pytest.mark.parametrize("case", ["sound", "unchanged", "half_batch",
                                  "control"])
def test_cell_limits_judge_the_small_program(case, monkeypatch):
    """The cell's own limits at a small size: the sound program passes
    them; the state left unchanged, the half batch and the control (the
    program's bfloat16 path) each fail at least one."""
    import functools
    from bench import faults
    from bench.harness import spec, train
    limits = spec.load_cell("qwen3-moe.train-s4096").limits
    real = train.Program
    if case == "control":
        monkeypatch.setattr(train, "Program",
                            functools.partial(real, dtype="bfloat16"))
    elif case != "sound":
        def planted(*a, **k):
            with faults.planted(case):
                return real(*a, **k)
        monkeypatch.setattr(train, "Program", planted)
    cell = replace(small_cell(), limits=limits)
    res = train.run(cell, seed=2 ** 32 + 29, seconds=0.3, trace=False,
                    devices=jax.devices()[:1], t_start=0.0, out_dir=None)
    assert res["correct"] == (case == "sound"), res["compare"]


@pytest.mark.parametrize("H,K", [(12, 12), (32, 4)])
def test_gqa_attention_roofline_by_hand(H, K):
    """The GQA reader's count: the attention counter's ops, and each
    in-band K/V block read once per KV head (the attention counter's
    bytes where H = K); its share on a hand-laid trace of two calls."""
    from bench.flops.kernels import flash_attention as mha
    from bench.harness.spec import load_module
    gqa = load_module(os.path.join(ROOT, "bench", "metrics",
                                   "flash_attention_gqa_roofline.py"),
                      "gqa_roofline_by_hand")
    B, L, hd = 2, 256, 128             # two blocks of 128: 3 in-band pairs
    call = {"operands": [("f32", (B, H, L, hd)), ("f32", (B, K, L, hd)),
                         ("f32", (B, K, L, hd))]}
    ops, nbytes = gqa.ops_bytes(call)
    assert ops == mha.ops_bytes(call)[0] == B * H * 3 * 2 * 2 * 128 * 128 * hd
    assert nbytes == 4 * B * (2 * H * L * hd + K * 3 * 2 * 128 * hd)
    assert (nbytes == mha.ops_bytes(call)[1]) == (H == K)
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    ns = round(2e9 * max(ops / 1e12, nbytes / 1e9))   # twice the least time
    ops_laid = [("flash_attention.1", "custom-call", 0, ns),
                ("flash_attention.2", "custom-call", ns, ns)]
    run = {"traced": {"devices": {"/device:TPU:0": ops_laid},
                      "window": (0, 2 * ns), "loads": []},
           "kernels": {"flash_attention": [call, call]}, "peaks": peaks,
           "cell": small_cell()}
    assert gqa.read(run) == pytest.approx(50.0)
