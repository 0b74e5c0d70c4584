"""device.peak_hbm_gb: the fullest chip's ``peak_bytes_in_use`` after
the window (before the reference runs), in GB (1e9 bytes)."""


def read(run):
    return run["peak"] / 1e9 if run["peak"] else None
