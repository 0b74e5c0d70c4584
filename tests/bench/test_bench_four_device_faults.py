"""The rest of a four-chip run, past the look for a chip, with the timed
path broken underneath: ``correct`` comes out false, judged by the
four-chip cell's own limits, for the control (the program's bfloat16
path) and each fault the cell can have (``bench/faults.py``): the state
left unchanged, half of the batch left out, and each exchange between
chips left out (the MoE layer's all-to-alls, the dense FFN's MP
all-reduce)."""

import pytest

from test_bench_four_devices import four_device_run


@pytest.mark.parametrize("case", ["unchanged", "half_batch", "no_exchange",
                                  "no_mp_exchange", "control"])
def test_fault_is_caught_on_four_devices(case):
    got = four_device_run(case)
    assert not got["correct"], got
