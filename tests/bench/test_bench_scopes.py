"""The program's layer scopes as the readers find them: a tiny MoE
config's train step lowered and compiled on the CPU (Pallas kernels in
interpret mode, remat on, as the chip runs it), read through
``bench.harness.trace.hlo_index``, and the patterns on paths by hand."""

import bench_helpers  # noqa: F401  (the repo root and src on the path)

import re
from dataclasses import replace

import pytest

from bench.harness import scopes as S
from bench.harness.readers import moe_pattern
from bench.harness import trace as T


@pytest.fixture(scope="module")
def step_index():
    """(instruction -> scope path, schedules) of the compiled step."""
    import jax
    import jax.numpy as jnp
    from bench.harness import program
    from repro.core import autosched
    from repro.kernels.registry import KernelConfig
    from repro.launch.mesh import local_mesh
    from repro.models import build_model
    from repro.train import Trainer

    conf = bench_helpers.tiny_config("gpt2-moe", n_layers=2)
    cfg = program.program_config(conf)
    kern = KernelConfig(backend="pallas", interpret=True)
    cfg = replace(cfg, kernel=kern, moe=replace(cfg.moe, kernel=kern))
    assert cfg.remat
    mesh, dims = local_mesh(cfg, jax.devices()[:1])
    tr = Trainer(build_model(cfg), mesh, dims, program.adamw_config(conf))
    params, opt_state = tr.setup(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((2, 32), jnp.int32),
             "labels": jnp.zeros((2, 32), jnp.int32)}
    compiled = tr.compile(params, opt_state, batch)
    schedules = {d.schedule for d in autosched.cache_info().values()}
    return T.hlo_index(compiled.as_text()), schedules


def _step_paths(index):
    """Paths of the step's own instructions (not its parameters')."""
    return [p for p in index.values() if p.startswith("jit(train_step)/")]


@pytest.mark.parametrize("scope", S.LAYERS)
def test_each_layer_scope_is_found(step_index, scope):
    index, _ = step_index
    rx = re.compile(S.scope_pattern(scope))
    assert any(rx.search(p) for p in _step_paths(index)), scope


def test_attention_has_forward_and_backward(step_index):
    index, _ = step_index
    rx = re.compile(S.scope_pattern("attn"))
    attn = [p for p in _step_paths(index) if rx.search(p)]
    assert any(S.is_backward(p) for p in attn)
    assert any(not S.is_backward(p) for p in attn)
    # the Pallas kernel's forward sits under the scope, not outside it
    assert any("flash_attention" in p and not S.is_backward(p)
               for p in attn)


def test_remat_recompute_counts_as_forward(step_index):
    index, _ = step_index
    remat = [p for p in _step_paths(index)
             if "rematted_computation" in p
             and re.search(S.scope_pattern("attn"), p)]
    assert remat and all("transpose(" in p for p in remat)
    assert not any(S.is_backward(p) for p in remat)


def test_no_instruction_matches_two_layer_patterns(step_index):
    index, schedules = step_index
    assert schedules
    pats = {s: re.compile(S.scope_pattern(s)) for s in S.LAYERS}
    pats["moe"] = re.compile(moe_pattern(schedules))
    for name, path in index.items():
        hits = [s for s, rx in pats.items() if rx.search(path)]
        assert len(hits) <= 1, (name, path, hits)


def test_no_layer_scope_reads_as_a_plan_scope(step_index):
    _, schedules = step_index
    rx = re.compile(moe_pattern(schedules))
    for s in S.LAYERS:
        assert not rx.search(f"jit(train_step)/{s}/add"), s


@pytest.mark.parametrize("path, scope, hit", [
    ("jit(train_step)/jvp(head)/dot_general", "head", True),
    ("jit(train_step)/transpose(jvp(head))/jit(log_softmax)/sub", "head",
     True),
    ("jit(train_step)/jvp()/while/body/closed_call/attn/dot_general",
     "attn", True),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/norm/rsqrt", "norm", True),
    ("jit(train_step)/adamw/sqrt", "adamw", True),
    ("jit(norm)/reduce_sum", "norm", False),
    ("jit(train_step)/jvp()/jit(rmsnorm_ref)/mul", "norm", False),
    ("params['run0']['attn']['wq']", "attn", False),
    ("jit(train_step)/ffn_gate/dot_general", "ffn", False),
])
def test_scope_pattern_by_hand(path, scope, hit):
    assert bool(re.search(S.scope_pattern(scope), path)) == hit


@pytest.mark.parametrize("path, bwd", [
    ("jit(train_step)/jvp()/while/body/closed_call/attn/dot_general",
     False),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "attn/dot_general", True),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attn/dot_general", False),
    ("jit(train_step)/transpose(jvp(head))/dot_general", True),
    ("jit(train_step)/adamw/mul", False),
])
def test_is_backward_by_hand(path, bwd):
    assert S.is_backward(path) == bwd
