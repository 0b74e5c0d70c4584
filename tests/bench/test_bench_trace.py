"""The trace reduction, on a small trace recorded on a TPU v5 lite (one
train step of gpt2-moe cut to 2 layers, ``bench/traces``) and on
hand-made traces with known answers."""

import bench_helpers  # noqa: F401  (the repo root and src on the path)

import gzip
import json
import os

import pytest

from bench.harness import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "..", "..", "bench", "traces",
                        "gpt2-moe-2layer-step.json.gz")


@pytest.fixture(scope="module")
def rec():
    with gzip.open(RECORDED) as f:
        r = json.load(f)
    r["ops"] = [tuple(o) for o in r["devices"]["/device:TPU:0"]]
    r["window"] = T.window([tuple(h) for h in r["host"]], "window")
    return r


def _sweep_busy(ops, lo, hi):
    """Busy ns by a sweep over +1/-1 edges (independent of T.union)."""
    edges = []
    for _, op, s, d in ops:
        if op in T.CONTAINERS or d <= 0:
            continue
        s, e = max(s, lo), min(s + d, hi)
        if e > s:
            edges += [(s, 1), (e, -1)]
    edges.sort()
    busy, depth, last = 0, 0, None
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_busy_union_matches_a_sweep(rec):
    lo, hi = rec["window"]
    got = T.length(T.busy(rec["ops"], lo, hi))
    assert got == _sweep_busy(rec["ops"], lo, hi)
    assert 0 < got < hi - lo


def test_idle_share_is_the_rest_of_the_window(rec):
    lo, hi = rec["window"]
    gaps = T.idle_gaps(rec["ops"], lo, hi)
    assert T.length(gaps) + T.length(T.busy(rec["ops"], lo, hi)) == hi - lo
    assert all(lo <= s < e <= hi for s, e in gaps)


def test_scope_time_sums_the_moe_ops(rec):
    """Leaf ops of one device do not overlap, so the time under the
    ``s1g.*`` scopes is the plain sum of their durations."""
    lo, hi = rec["window"]
    pat = r"(^|/)s1g\.[^/]"
    import re
    want = sum(d for n, op, s, d in rec["ops"]
               if op not in T.CONTAINERS and lo <= s and s + d <= hi
               and re.search(pat, rec["index"].get(n, "")))
    got = T.scope_time(rec["ops"], rec["index"], pat, lo, hi)
    assert want > 0 and abs(got - want) <= 0.001 * want


def test_kernel_calls_count_each_call(rec):
    lo, hi = rec["window"]
    n, ns = T.kernel_calls(rec["ops"], "flash_attention", lo, hi)
    # 2 layers, each forward and again in the remat of the backward
    assert n == 4 and ns > 0
    n, _ = T.kernel_calls(rec["ops"], "expert_ffn_grouped", lo, hi)
    assert n == 1


def test_breakdown_names_ops_and_gaps(rec):
    lo, hi = rec["window"]
    b = T.breakdown(rec["ops"], rec["index"], set(rec["kernels"]), {"s1g"},
                    lo, hi, [tuple(h) for h in rec["host"]])
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    names = [g for g, _ in b["device_ops"]]
    assert "flash_attention" in names
    assert any(n.startswith("s1g.") for n in names)
    secs = [t for _, t in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_busy_and_idle_by_hand():
    # ops 0-10, 8-14 and 20-30; a while op over everything, which counts
    # as nothing: busy 0-14 and 20-30, idle 14-20 and 30-50
    ops = [("fusion.1", "fusion", 0, 10), ("fusion.2", "fusion", 20, 10),
           ("copy.3", "copy", 8, 6), ("while.1", "while", 0, 50)]
    assert T.busy(ops, 0, 50) == [[0, 14], [20, 30]]
    assert T.idle_gaps(ops, 0, 50) == [[14, 20], [30, 50]]
    assert T.length(T.busy(ops, 5, 25)) == 9 + 5


@pytest.mark.parametrize("text, op", [
    ("%copy-start.5 = (f32[1,768]{1,0:T(8,128)S(1)}, u32[]{:S(2)}) "
     "copy-start(f32[1,768]{1,0} %p)", "copy-start"),
    ("%fusion.8 = f32[16,1024]{1,0:T(8,128)} fusion(f32[16,1024] %a)",
     "fusion"),
    ("%all-to-all.2 = f32[4,8]{1,0} all-to-all(f32[4,8]{1,0} %x)",
     "all-to-all"),
])
def test_opcode(text, op):
    assert T.opcode(text) == op
