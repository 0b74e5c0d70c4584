"""A configuration, a traffic mix and a per-layer metric are files of
their own: dropped into a copy of ``bench/`` beside a new cell in
``BENCHMARK.json``, they are found by name with no edit to a file that
was there."""

import bench_helpers  # noqa: F401  (the repo root and src on the path)

import json
import os
import shutil

import pytest

from bench.harness import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture
def bench_copy(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "traces"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    return tmp_path, b


def test_new_config_mix_and_metric_are_found(bench_copy):
    tmp, b = bench_copy
    before = {p: p.read_bytes() for p in (tmp / "bench").rglob("*")
              if p.is_file()}
    with open(tmp / "bench" / "configs" / "gpt2-moe.json") as f:
        conf = json.load(f)
    conf["name"] = "gpt2-moe-wide"
    (tmp / "bench" / "configs" / "gpt2-moe-wide.json").write_text(
        json.dumps(conf))
    (tmp / "bench" / "traffic" / "train-s2048.json").write_text(json.dumps(
        {"generator": "synthetic_lm", "kind": "train", "seq_len": 2048,
         "global_batch": 8, "n_heavy": 64, "heavy_prob": 0.7}))
    (tmp / "bench" / "metrics" / "train.steps.py").write_text(
        "def read(run):\n    return run['n_steps']\n")
    b["workloads"].append({"name": "gpt2-moe-wide.train-s2048",
                           "config": "gpt2-moe-wide",
                           "traffic": "train-s2048", "chips": 1,
                           "why": "a cell added as data"})
    b["per_layer"].append({"name": "train.steps", "unit": "steps",
                           "better": "higher", "source": "host_clock",
                           "layer": "trainer step",
                           "moves": "train_tokens_per_s",
                           "workloads": ["gpt2-moe-wide.train-s2048"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(b))

    cell = spec.load_cell("gpt2-moe-wide.train-s2048",
                          bench_file=str(tmp / "BENCHMARK.json"),
                          bench_dir=str(tmp / "bench"))
    assert cell.config["name"] == "gpt2-moe-wide"
    assert cell.traffic["seq_len"] == 2048
    assert "train.steps" in [m["name"] for m in cell.per_layer]
    read = spec.metric_reader("train.steps", bench_dir=str(tmp / "bench"))
    assert read({"n_steps": 7}) == 7
    gen = spec.generator(cell.traffic, bench_dir=str(tmp / "bench")).make(
        cell.traffic, 512, seed=2 ** 40 + 3)
    toks, labels = gen.batch(0)
    assert toks.shape == (8, 2048) and (toks[:, 1:] == labels[:, :-1]).all()
    # nothing that was there changed
    for p, data in before.items():
        assert p.read_bytes() == data


def test_every_named_piece_exists():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    for w in b["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.limits, f"{w['name']} has no limits file"
    for m in b["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


def test_traffic_is_the_seeds():
    cell = spec.load_cell("bert-moe.train-s128")
    gen = spec.generator(cell.traffic)
    a = gen.make(cell.traffic, 30522, seed=3_000_000_001).batch(5)
    b = gen.make(cell.traffic, 30522, seed=3_000_000_001).batch(5)
    c = gen.make(cell.traffic, 30522, seed=3_000_000_002).batch(5)
    assert (a[0] == b[0]).all() and not (a[0] == c[0]).all()
