"""The benchmark runs on a TPU whose kind the peak table holds, with
the chips the cell asks for, or not at all."""

import bench_helpers  # noqa: F401  (the repo root and src on the path)

from dataclasses import dataclass

import jax
import pytest

from bench.harness.device import DeviceError, chips


@dataclass
class FakeDevice:
    platform: str
    device_kind: str


def test_cpu_is_refused():
    with pytest.raises(DeviceError, match="no TPU"):
        chips(1, jax.devices("cpu"))


def test_unknown_device_kind_is_refused():
    with pytest.raises(DeviceError, match="not in bench/peaks.json"):
        chips(1, [FakeDevice("tpu", "TPU v99")])


def test_too_few_chips_are_refused():
    with pytest.raises(DeviceError, match="needs 4 chips"):
        chips(4, [FakeDevice("tpu", "TPU v5 lite")])


def test_known_kind_gets_its_peaks():
    devs, pk = chips(1, [FakeDevice("tpu", "TPU v5 lite")] * 2)
    assert len(devs) == 1
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9


def test_cli_without_tpu_exits_nonzero_and_prints_no_result(capsys):
    from bench import run
    rc = run.main(["--workload", "gpt2-moe.train-s1024", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no TPU" in out.err
