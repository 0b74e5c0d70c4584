"""The benchmark's training path on four CPU devices, the 2x2 mesh of
the four-chip cell, against the reference gated in the pools that
``train.gate_pools`` states: the schedule autosched picks at this size
(S1 with grouped experts: each MP rank gates its slice of the pool), the
four-chip cell's ``s2h`` (the pool of each data shard) and ``s1_seqpar``
(the pool split over MP).  Each run is a subprocess
(``four_device_run.py``) with four host devices."""

import json
import os
import subprocess
import sys

import pytest

from conftest import subprocess_env

HERE = os.path.dirname(os.path.abspath(__file__))


def four_device_run(case: str) -> dict:
    r = subprocess.run([sys.executable, os.path.join(HERE,
                                                     "four_device_run.py"),
                        case], env=subprocess_env(4), capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case, pools", [
    ("auto", [4, 40]), ("s2h", [2, 80]), ("s1_seqpar", [4, 40])])
def test_program_matches_reference_on_four_devices(case, pools):
    got = four_device_run(case)
    assert got["pools"] == pools, got
    if case != "auto":
        assert got["schedules"] == [case]
    assert got["correct"], got
    for value in got["compared"].values():
        assert value < 1e-4, got
