"""The rest of a run, past the look for a chip, with the timed path
broken underneath: ``correct`` comes out false for the control and for
each fault a training cell can have, judged by the cells' own limits.
(A token altered where it is produced is a serving fault; these cells
serve nothing.)"""

import functools

import jax
import pytest

from bench_helpers import tiny_cell
from bench import faults
from bench.harness import report, spec, train


def _limits(cell_name):
    return spec.load_cell(cell_name).limits


def _run(cell):
    res = train.run(cell, seed=23, seconds=0.3, trace=False,
                    devices=jax.devices()[:1], t_start=0.0, out_dir=None)
    line = report.result_line(cell, res, {}, jax.devices()[:1], False)
    return res, line


def _plant(monkeypatch, fault):
    """Build the run's program with ``fault`` (``bench/faults.py``)."""
    real = train.Program

    def planted(*a, **k):
        with faults.planted(fault):
            return real(*a, **k)
    monkeypatch.setattr(train, "Program", planted)


@pytest.mark.parametrize("name", ["gpt2-moe.train-s1024",
                                  "bert-moe.train-s128"])
def test_sound_run_is_correct(name):
    cell = tiny_cell(name.split(".")[0], limits=_limits(name))
    res, line = _run(cell)
    assert line["correct"] and line["failed"] == 0, res["compare"]
    assert list(line)[-1] == "compared"


@pytest.mark.parametrize("name", ["gpt2-moe.train-s1024",
                                  "bert-moe.train-s128"])
def test_state_left_unchanged_is_caught(name, monkeypatch):
    _plant(monkeypatch, "unchanged")
    cell = tiny_cell(name.split(".")[0], limits=_limits(name))
    res, line = _run(cell)
    assert not line["correct"]
    assert line["compared"]["update_gap"]["value"] >= 0.99


@pytest.mark.parametrize("name", ["gpt2-moe.train-s1024",
                                  "bert-moe.train-s128"])
def test_half_batch_is_caught(name, monkeypatch):
    _plant(monkeypatch, "half_batch")
    cell = tiny_cell(name.split(".")[0], limits=_limits(name))
    res, line = _run(cell)
    assert not line["correct"], res["compare"]


@pytest.mark.parametrize("name", ["gpt2-moe.train-s1024",
                                  "bert-moe.train-s128"])
def test_control_is_caught(name, monkeypatch):
    """The control in the program's place: the program's own bfloat16
    path (``ModelConfig.dtype``), from the float32 path's weights
    rounded."""
    monkeypatch.setattr(train, "Program", functools.partial(
        train.Program, dtype="bfloat16"))
    cell = tiny_cell(name.split(".")[0], limits=_limits(name))
    res, line = _run(cell)
    assert not line["correct"], res["compare"]


@pytest.mark.parametrize("numbers, limits, ok", [
    ({"loss_gap": (1e-5, ""), "grad_gap": (0.5, "")}, {"loss_gap": 1e-4},
     True),
    ({"loss_gap": (1e-3, "")}, {"loss_gap": 1e-4}, False),
    ({"loss_gap": (float("nan"), "")}, {"loss_gap": 1e-4}, False),
    ({"loss_gap": (1e-5, "")}, {"loss_gap": 1e-4, "update_gap": 0.1},
     False),
    ({"loss_gap": (1e-5, "")}, {}, False),
])
def test_judge_compares_what_the_limits_name(numbers, limits, ok):
    from bench.harness import compare
    got, lines = compare.judge(numbers, limits)
    assert got is ok
    assert [name for name, *_ in lines] == list(limits)
