"""The chips a run measures on: a TPU whose kind the peak table holds,
with as many chips as the cell asks for, or no run at all."""

from __future__ import annotations

import jax

from bench.harness.spec import SpecError, peaks


class DeviceError(Exception):
    pass


def chips(n: int, devices=None):
    """(the first ``n`` devices, their peaks) or DeviceError."""
    devices = list(jax.devices() if devices is None else devices)
    d0 = devices[0]
    if d0.platform != "tpu":
        raise DeviceError(f"no TPU found (JAX platform {d0.platform!r}); "
                          f"this benchmark measures the chip only")
    try:
        pk = peaks(d0.device_kind)
    except SpecError as e:
        raise DeviceError(str(e)) from e
    if len(devices) < n:
        raise DeviceError(f"the cell needs {n} chips, JAX found "
                          f"{len(devices)}")
    return devices[:n], pk
