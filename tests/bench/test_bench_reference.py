"""The plain reference against the program at a small size on the CPU:
the same weights from the seed, and the same losses, first gradient
and three AdamW updates, through the harness's own correctness path."""

import jax
import numpy as np
import pytest

from bench_helpers import tiny_cell, tiny_config


@pytest.mark.parametrize("name", ["gpt2-moe", "bert-moe"])
def test_reference_init_is_the_programs(name):
    from bench.harness import program
    from bench.reference.model import init_params
    from repro.models import build_model
    conf = tiny_config(name)
    key = program.seed_key(2 ** 33 + 5)
    ours = init_params(key, conf["model"])
    theirs = build_model(program.program_config(conf)).init(key)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_seed_key_keeps_every_bit():
    from bench.harness import program
    k = [np.asarray(jax.random.key_data(program.seed_key(s)))
         for s in (7, 2 ** 32 + 7, 2 ** 31 + 7)]
    assert len({tuple(x) for x in k}) == 3


@pytest.mark.parametrize("name", ["gpt2-moe", "bert-moe"])
def test_program_matches_reference(name):
    from bench.harness import train
    cell = tiny_cell(name)
    res = train.run(cell, seed=11, seconds=0.5, trace=False,
                    devices=jax.devices()[:1], t_start=0.0, out_dir=None)
    assert res["correct"], res["compare"]
    for _, value, _, _, _ in res["compare"]:
        assert value < 1e-4
