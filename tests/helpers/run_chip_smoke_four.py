"""chip_smoke.py's four-chip phase on four CPU devices, at a reduced size.

Run with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.  The
Pallas kernels run in interpret mode (``REPRO_KERNEL_BACKEND=pallas``),
so the phase's checks — step-0 losses equal across s1/s2/s2h/s1g/auto,
E/n_ep experts on every device, finite losses — run through the same
shard_map'd kernel path as on the chip.  Prints FOUR CHIP PHASE OK.
"""

import os
import sys

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..")
sys.path.insert(0, ROOT)
os.environ["REPRO_KERNEL_BACKEND"] = "pallas"

import jax  # noqa: E402

import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402


def main():
    devices = jax.devices()
    assert len(devices) == 4, devices
    step0 = chip_smoke.four_chip_phase(
        get_config("gpt2-moe").reduced(), devices, seq=32, batch=4,
        steps=2, seed=0)
    assert set(step0) == set(chip_smoke.FOUR_CHIP_SCHEDULES), step0
    print("FOUR CHIP PHASE OK")


if __name__ == "__main__":
    main()
