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


TOY = {
    "programs/toy.py": (
        '"""A toy family\'s program mapping."""\n\n\n'
        'def program_config(conf, dtype="float32"):\n'
        '    return ["toy program", conf["name"], dtype]\n\n\n'
        'def adamw_config(conf):\n'
        '    return ["toy adamw", conf["optimizer"]["lr"]]\n'),
    "reference/toy.py": (
        '"""A toy family\'s reference."""\n\n\n'
        'def train_steps(key, model, opt, batches, *, pools, fault, device):\n'
        '    return {"loss": [len(batches)], "pools": list(pools),\n'
        '            "width": model["d_model"], "fault": fault}\n'),
    "flops/toy.py": (
        '"""A toy family\'s model FLOPs."""\n\n\n'
        'def train_flops_per_token(m, seq_len):\n'
        '    return 1e9 * m["n_layers"]\n'),
}
PROBE = """\
import json
from bench.harness import program, spec, train
cell = spec.load_cell("toy.train-s1024")


class Gen:
    def batch(self, i):
        return i, i


run = {"cell": cell, "tokens_per_s": 1000.0, "chips": 1,
       "peaks": {"bf16_flops_per_s": 1e13}}
print(json.dumps({
    "bench": spec.BENCH,
    "program": program.program_config(cell.config),
    "adamw": program.adamw_config(cell.config),
    "reference": train.reference(cell, None, Gen(), (2, 8), None),
    "mfu": spec.metric_reader("train.mfu")(run)}))
"""


def test_new_family_is_found(bench_copy):
    """A family (program mapping, reference, model FLOPs) dropped into a
    copy of ``bench/`` beside a configuration that names it: the
    harness's dispatchers find each piece by the family's name, with no
    file under ``bench/harness/`` changed."""
    import subprocess
    import sys
    tmp, b = bench_copy
    harness = {p: p.read_bytes() for p in (tmp / "bench" / "harness").rglob(
        "*.py")}
    for rel, text in TOY.items():
        (tmp / "bench" / rel).write_text(text)
    with open(tmp / "bench" / "configs" / "gpt2-moe.json") as f:
        conf = json.load(f)
    conf.update(name="toy", family="toy")
    conf["model"]["n_layers"] = 3
    (tmp / "bench" / "configs" / "toy.json").write_text(json.dumps(conf))
    b["workloads"].append({"name": "toy.train-s1024", "config": "toy",
                           "traffic": "train-s1024", "chips": 1,
                           "why": "a family added as files"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(b))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(tmp), os.path.join(ROOT, "src")]))
    r = subprocess.run([sys.executable, "-c", PROBE], cwd=tmp, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["bench"] == str(tmp / "bench")
    assert got["program"] == ["toy program", "toy", "float32"]
    assert got["adamw"] == ["toy adamw", conf["optimizer"]["lr"]]
    assert got["reference"] == {"loss": [3], "pools": [2, 8],
                                "width": 768, "fault": ""}
    # 1000 tokens/s x 3e9 FLOPs / 1e13 FLOP/s = 30%
    assert got["mfu"] == pytest.approx(30.0)
    for p, data in harness.items():
        assert p.read_bytes() == data
