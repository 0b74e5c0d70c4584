"""The readers of the layer scopes, step spans and set-up phases
(``bench/metrics``) on hand-made traces and phase lists with known
answers, and on a small trace of the scoped program recorded on a TPU
v5 lite (two train steps of gpt2-moe cut to 2 layers,
``bench/traces``)."""

import bench_helpers  # noqa: F401  (the repo root and src on the path)

import gzip
import json
import os

import pytest

from bench.harness import trace as T
from bench.harness.spec import metric_reader

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "..", "..", "bench", "traces",
                        "gpt2-moe-2layer-step-scoped.json.gz")
MS = 1_000_000      # ns
DEVICE = ("attention.fwd_device_ms", "attention.bwd_device_ms",
          "ffn.device_ms", "norm.device_ms", "head.device_ms",
          "optimizer.device_ms", "step.unnamed_device_ms")
IDLE = ("host.input_idle_ms", "host.expert_load_read_idle_ms")
SETUP = ("setup.init_s", "setup.lower_s", "setup.compile_s")


def _run(devices, index, host, window, n_steps=1, schedules=("s1g",)):
    return {"traced": {"devices": devices, "index": index, "host": host,
                       "window": window, "n_steps": n_steps},
            "schedules": list(schedules)}


def _read(name, run):
    return metric_reader(name)(run)


# --- layer scopes ----------------------------------------------------------

STEP = "jit(train_step)/"
BODY = STEP + "jvp()/while/body/closed_call/"
BWD = STEP + "transpose(jvp())/while/body/closed_call/checkpoint/"
REMAT = BWD + "rematted_computation/"
# instruction -> (scope path, ms): one op of each kind, 1 ms apart
LAID = {
    "fusion.1": (BODY + "attn/dot_general", 3),
    "fusion.2": (REMAT + "attn/jit(op)/flash_attention/pallas_call", 2),
    "fusion.3": (BWD + "attn/dot_general", 5),
    "fusion.4": (BODY + "ffn/dot_general", 4),
    "fusion.5": (BWD + "ffn/dot_general", 1),
    "fusion.6": (BODY + "norm/rsqrt", 1),
    "fusion.7": (STEP + "transpose(jvp(norm))/mul", 2),
    "fusion.8": (STEP + "jvp(head)/dot_general", 6),
    "fusion.9": (STEP + "transpose(jvp(head))/dot_general", 7),
    "fusion.10": (STEP + "adamw/sub", 3),
    "fusion.11": (BODY + "s1g.comb/jit(op)/add", 8),
    "fusion.12": (STEP + "jvp()/gather", 1),
    "copy.13": ("", 2),
}
WANT = {"attention.fwd_device_ms": 5, "attention.bwd_device_ms": 5,
        "ffn.device_ms": 5, "norm.device_ms": 3, "head.device_ms": 13,
        "optimizer.device_ms": 3, "step.unnamed_device_ms": 3,
        "moe.device_ms": 8}


def _laid_out(n_chips=1):
    """The ops of LAID back to back from 0, 1 ms apart, with a while op
    over them all (a container, which counts as nothing)."""
    ops, t = [], 0
    for name, (_, ms) in LAID.items():
        ops.append((name, name.split(".")[0], t, ms * MS))
        t += (ms + 1) * MS
    ops.append(("while.1", "while", 0, t))
    index = {n: p for n, (p, _) in LAID.items()}
    index["while.1"] = BODY.rstrip("/")
    devices = {f"/device:TPU:{i}": ops for i in range(n_chips)}
    return devices, index, t


@pytest.mark.parametrize("name", sorted(WANT))
def test_layer_readers_by_hand(name):
    devices, index, t = _laid_out()
    got = _read(name, _run(devices, index, [], (0, t), n_steps=2))
    assert got == pytest.approx(WANT[name] / 2)


def test_layer_readers_partition_the_busy_time():
    devices, index, t = _laid_out(n_chips=2)
    run = _run(devices, index, [], (0, t))
    busy = T.length(T.busy(devices["/device:TPU:0"], 0, t)) / MS
    total = sum(_read(n, run) for n in DEVICE + ("moe.device_ms",))
    assert total == pytest.approx(busy)


def test_window_clips_the_ops():
    devices, index, t = _laid_out()
    # the window ends 1 ms into fusion.3, the attention backward
    start3 = next(s for n, _, s, _ in devices["/device:TPU:0"]
                  if n == "fusion.3")
    run = _run(devices, index, [], (0, start3 + MS))
    assert _read("attention.bwd_device_ms", run) == pytest.approx(1)
    assert _read("head.device_ms", run) == 0


def test_a_program_without_the_scopes_reads_nothing():
    """The parent's program names only its plan stages: the layer
    readers and the unnamed time read None, moe.device_ms still reads."""
    devices, index, t = _laid_out()
    bare = {n: p.replace("attn/", "").replace("ffn/", "")
            .replace("norm", "").replace("head", "").replace("adamw/", "")
            for n, p in index.items()}
    run = _run(devices, bare, [], (0, t))
    for name in DEVICE:
        assert _read(name, run) is None, name
    assert _read("moe.device_ms", run) == pytest.approx(8)


def test_untraced_run_reads_nothing():
    for name in DEVICE + IDLE:
        assert _read(name, {"traced": None, "schedules": ["s1g"]}) is None


# --- step spans -----------------------------------------------------------

def _idle_run(host, n_steps=1, n_chips=1):
    # busy 0-10 and 20-30 ms of a 0-40 ms window: idle 10-20 and 30-40
    ops = [("fusion.1", "fusion", 0, 10 * MS),
           ("fusion.2", "fusion", 20 * MS, 10 * MS)]
    devices = {f"/device:TPU:{i}": ops for i in range(n_chips)}
    return _run(devices, {}, host, (0, 40 * MS), n_steps=n_steps)


def test_gap_half_inside_the_input_span_reads_half():
    run = _idle_run([("train.input", 15 * MS, 10 * MS)])
    assert _read("host.input_idle_ms", run) == pytest.approx(5)


def test_idle_reader_sums_gaps_per_step_over_chips():
    host = [("train.expert_load_read", 5 * MS, 10 * MS),    # 5 ms idle
            ("train.expert_load_read", 8 * MS, 4 * MS),     # inside it
            ("train.expert_load_read", 28 * MS, 12 * MS),   # 10 ms idle
            ("train.input", 0, 40 * MS)]
    run = _idle_run(host, n_steps=3, n_chips=2)
    assert _read("host.expert_load_read_idle_ms", run) == \
        pytest.approx(15 / 3)
    assert _read("host.input_idle_ms", run) == pytest.approx(20 / 3)


def test_busy_span_reads_zero_and_missing_span_none():
    run = _idle_run([("train.input", 0, 10 * MS)])
    assert _read("host.input_idle_ms", run) == 0
    assert _read("host.expert_load_read_idle_ms", run) is None


# --- set-up phases ------------------------------------------------------------

def test_setup_readers_take_the_first_phase(monkeypatch):
    from repro import obs
    phases = [obs.Phase("setup.init", 0, 2_500_000_000, None),
              obs.Phase("setup.lower", 3_000_000_000, 10_000_000_000,
                        None),
              obs.Phase("setup.compile", 10_000_000_000, 11_250_000_000,
                        None),
              obs.Phase("setup.init", 20_000_000_000, 90_000_000_000,
                        None)]
    monkeypatch.setattr(obs, "phases", lambda: list(phases))
    got = {n: _read(n, {}) for n in SETUP}
    assert got == {"setup.init_s": pytest.approx(2.5),
                   "setup.lower_s": pytest.approx(7.0),
                   "setup.compile_s": pytest.approx(1.25)}


def test_setup_readers_without_phases_read_nothing(monkeypatch):
    from repro import obs
    monkeypatch.setattr(obs, "phases", lambda: [
        obs.Phase("train.checkpoint", 0, 10, None)])
    assert all(_read(n, {}) is None for n in SETUP)
    monkeypatch.delattr(obs, "phases")   # a program without obs.phase
    assert all(_read(n, {}) is None for n in SETUP)


# --- the recorded trace -----------------------------------------------------------

@pytest.fixture(scope="module")
def rec():
    with gzip.open(RECORDED) as f:
        r = json.load(f)
    host = [tuple(h) for h in r["host"]]
    return {"traced": {
        "devices": {d: [tuple(o) for o in ops]
                    for d, ops in r["devices"].items()},
        "index": r["index"], "host": host, "n_steps": r["n_steps"],
        "window": T.window(host, "bench.traced_window")},
        "schedules": r["schedules"]}


@pytest.mark.parametrize("name", DEVICE + IDLE + ("moe.device_ms",))
def test_recorded_readers_read_something(rec, name):
    assert _read(name, rec) > 0


def test_recorded_device_readers_sum_to_the_busy_time(rec):
    t = rec["traced"]
    lo, hi = t["window"]
    busy = [T.length(T.busy(ops, lo, hi)) for ops in t["devices"].values()]
    busy_ms = sum(busy) / len(busy) / MS / t["n_steps"]
    total = sum(_read(n, rec) for n in DEVICE + ("moe.device_ms",))
    assert abs(total - busy_ms) <= 0.02 * busy_ms
