"""Collectives in a compiled step (``trace.collective_ops``) and the
device time in which they run exposed (``trace.exposed_collective``,
``collective.exposed_ms``), on hand-made HLO and traces with known
answers, and on a trace recorded on four v5e chips."""

import bench_helpers  # noqa: F401  (the repo root and src on the path)

import gzip
import json
import os

import pytest

from bench.harness import spec
from bench.harness import trace as T
from bench.harness.spec import metric_reader

MS = 1_000_000

HLO = """\
HloModule jit_train_step

%gathered (p0: f32[4]) -> f32[8] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %all-gather.1 = f32[8]{0} all-gather(%p0), dimensions={0}
}

%overlapped (p0: f32[4], p1: f32[8,8]) -> (f32[8], f32[8,8]) {
  %p0 = f32[4]{0} parameter(0)
  %all-gather.2 = f32[8]{0} all-gather(%p0), dimensions={0}
  %p1 = f32[8,8]{1,0} parameter(1)
  %convolution.3 = f32[8,8]{1,0} convolution(%p1, %p1), dim_labels=bf_io->bf
  ROOT %tuple.4 = (f32[8]{0}, f32[8,8]{1,0}) tuple(%all-gather.2, %convolution.3)
}

%started (p0: f32[4]) -> f32[8] {
  %p0 = f32[4]{0} parameter(0)
  %all-gather.5 = f32[8]{0} all-gather(%p0), dimensions={0}
  ROOT %custom-call.6 = f32[8]{0} custom-call(%all-gather.5), custom_call_target="AsyncCollectiveStart"
}

ENTRY %main (a: f32[4], b: f32[8,8]) -> f32[8] {
  %a = f32[4]{0} parameter(0)
  %b = f32[8,8]{1,0} parameter(1)
  %all-to-all.7 = f32[4]{0} all-to-all(%a), dimensions={0}
  %fusion.8 = f32[8]{0} fusion(%a), kind=kCustom, calls=%gathered
  %fusion.9 = (f32[8]{0}, f32[8,8]{1,0}) fusion(%a, %b), kind=kOutput, calls=%overlapped
  %async-collective-start.10 = f32[8]{0} fusion(%a), kind=kCustom, calls=%started
  %collective-permute-start.11 = (f32[4]{0}, f32[4]{0}) collective-permute-start(%a), source_target_pairs={{0,1}}
  %collective-permute-done.12 = f32[4]{0} collective-permute-done(%collective-permute-start.11)
  %pallas.13 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call"
  ROOT %add.14 = f32[4]{0} add(%all-to-all.7, %collective-permute-done.12)
}
"""


def test_collective_ops_in_hlo():
    got = T.collective_ops(HLO)
    # ops of their own, a fusion of a collective alone, and an async
    # start; not a fusion that carries a matmul beside its collective,
    # not a kernel, not compute
    for name in ("all-to-all.7", "fusion.8", "async-collective-start.10",
                 "collective-permute-start.11",
                 "collective-permute-done.12"):
        assert name in got
    for name in ("fusion.9", "pallas.13", "add.14", "convolution.3"):
        assert name not in got


COLL = {"all-to-all.7", "fusion.8", "collective-permute-start.11",
        "collective-permute-done.12", "all-reduce-start.20",
        "all-reduce-done.21", "all-reduce-start.22", "all-reduce-done.23"}


def _ops(*laid):
    """(name, start ms, length ms) -> trace ops."""
    return [(n, "", int(s * MS), int(d * MS)) for n, s, d in laid]


@pytest.mark.parametrize("laid, exposed_ms", [
    # a collective alone: all of it
    ([("all-to-all.7", 0, 3), ("add.1", 3, 2)], 3),
    # overlapped by compute for 2 of its 3 ms
    ([("all-to-all.7", 0, 3), ("fusion.9", 1, 4)], 1),
    # wholly under compute
    ([("fusion.9", 0, 5), ("fusion.8", 1, 2)], 0),
    # an async pair: in flight from the start's beginning to the done's
    # end (10 ms), compute covers 4 ms of it, the device idle 4 ms
    ([("collective-permute-start.11", 0, 1), ("fusion.9", 2, 4),
      ("collective-permute-done.12", 9, 1)], 6),
    # two pairs of one kind, nested: the union of what is in flight
    ([("all-reduce-start.20", 0, 1), ("all-reduce-start.22", 1, 1),
      ("all-reduce-done.23", 4, 1), ("add.1", 5, 1),
      ("all-reduce-done.21", 7, 1)], 7),
    # no collective
    ([("add.1", 0, 3)], 0),
])
def test_exposed_collective_by_hand(laid, exposed_ms):
    ops = _ops(*laid)
    assert T.exposed_collective(ops, COLL, 0, 20 * MS) == exposed_ms * MS


def test_exposed_collective_clips_to_the_window():
    ops = _ops(("all-to-all.7", 0, 10))
    assert T.exposed_collective(ops, COLL, 2 * MS, 5 * MS) == 3 * MS


def test_collective_exposed_ms_reader():
    read = metric_reader("collective.exposed_ms")
    laid = [("all-to-all.7", 0, 3), ("fusion.9", 1, 4)]
    run = {"traced": {"devices": {"/device:TPU:0": _ops(*laid),
                                  "/device:TPU:1": _ops(("all-to-all.7", 0,
                                                         3))},
                      "collectives": COLL, "window": (0, 20 * MS),
                      "n_steps": 2}}
    # (1 ms + 3 ms) / 2 chips / 2 steps
    assert read(run) == pytest.approx(1.0)
    run["traced"]["collectives"] = set()
    assert read(run) is None
    assert read({"traced": None}) is None


# --- the recorded four-chip trace -----------------------------------------------

RECORDED_X4 = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                           "..", "bench", "traces",
                           "gpt2-moe-2layer-step-x4.json.gz")
DEVICE = ("attention.fwd_device_ms", "attention.bwd_device_ms",
          "ffn.device_ms", "norm.device_ms", "head.device_ms",
          "optimizer.device_ms", "step.unnamed_device_ms", "moe.device_ms")
OPCODES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
           "collective-permute")


@pytest.fixture(scope="module")
def rec_x4():
    """The recorded trace as ``train.summarize`` leaves a traced run:
    the collectives found in the chip's compiled HLO beside the ops."""
    with gzip.open(RECORDED_X4) as f:
        r = json.load(f)
    host = [tuple(h) for h in r["host"]]
    cell = spec.load_cell("gpt2-moe.train-s1024-x4")
    return {"traced": {
        "devices": {d: [tuple(o) for o in ops]
                    for d, ops in r["devices"].items()},
        "index": r["index"], "host": host, "n_steps": r["n_steps"],
        "collectives": set(r["collectives"]), "loads": None,
        "window": T.window(host, "bench.traced_window")},
        "schedules": r["schedules"], "kernels": r["kernels"],
        "cell": cell, "peaks": spec.peaks("TPU v5 lite")}


def _busy_ms(rec):
    t = rec["traced"]
    lo, hi = t["window"]
    busy = [T.length(T.busy(ops, lo, hi)) for ops in t["devices"].values()]
    return sum(busy) / len(busy) / MS / t["n_steps"]


def test_recorded_x4_runs_the_cells_schedule(rec_x4):
    assert len(rec_x4["traced"]["devices"]) == 4
    assert rec_x4["schedules"] == ["s2h"]


def test_recorded_x4_collectives_cover_the_named_ones(rec_x4):
    """Every op on the chips named after a collective opcode is among
    the collectives ``collective_ops`` found in the compiled HLO, and
    the MoE all-to-alls (named after their plan stage) and the MP
    all-reduces are there."""
    t = rec_x4["traced"]
    names = {o[0] for ops in t["devices"].values() for o in ops}
    named = {n for n in names if n.startswith(OPCODES)}
    assert named <= t["collectives"]
    assert any("_a2a_" in n for n in t["collectives"])   # <plan>_a2a_*
    assert any(n.startswith("all-reduce") for n in t["collectives"])


def test_recorded_x4_exposed_collectives_lie_within_busy(rec_x4):
    got = metric_reader("collective.exposed_ms")(rec_x4)
    assert 0 < got < _busy_ms(rec_x4)


@pytest.mark.parametrize("name", DEVICE + ("host.input_idle_ms",))
def test_recorded_x4_readers_read_something(rec_x4, name):
    assert metric_reader(name)(rec_x4) > 0


def test_recorded_x4_device_readers_sum_to_the_busy_time(rec_x4):
    busy = _busy_ms(rec_x4)
    total = sum(metric_reader(n)(rec_x4) for n in DEVICE)
    assert abs(total - busy) <= 0.02 * busy


def test_recorded_x4_flash_attention_roofline_is_a_share(rec_x4):
    assert 0 < metric_reader("flash_attention_roofline")(rec_x4) < 100
