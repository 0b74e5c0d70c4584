"""Small CPU-sized cells for the benchmark's tests, which run on the
CPU; importing this module puts the repo root (for ``bench``) and
``src`` (for the program) on the path."""

import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def tiny_config(name="gpt2-moe", **over):
    """A benchmark configuration file cut to CPU-test size."""
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        conf = json.load(f)
    conf = copy.deepcopy(conf)
    conf["model"].update(n_layers=4, d_model=64, n_heads=4, d_ff=128,
                         vocab_size=256, expert_d_ff=96, n_experts=4)
    conf["model"].update(over)
    return conf


def tiny_cell(name="gpt2-moe", chips=1, batch=4, seq=32, limits=None,
              **over):
    from bench.harness.spec import Cell
    return Cell(name=f"{name}.tiny", chips=chips,
                config=tiny_config(name, **over),
                traffic={"generator": "synthetic_lm", "kind": "train",
                         "seq_len": seq, "global_batch": batch,
                         "n_heavy": 8, "heavy_prob": 0.7},
                limits=limits if limits is not None else {
                    "loss_gap": 1e-4, "grad_gap": 1e-3,
                    "update_gap": 1e-3},
                end_to_end=[], per_layer=[])
