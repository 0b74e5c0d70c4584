"""flash_attention_roofline: the forward Pallas attention kernel's share
of its roofline (its backward runs as XLA ops and is not in it); ops
and bytes from its call shapes (``bench/flops/kernels``), time from
the device trace."""

from bench.harness.readers import kernel_roofline


def read(run):
    return kernel_roofline(run, "flash_attention")
