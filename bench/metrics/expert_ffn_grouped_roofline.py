"""expert_ffn_grouped_roofline: the fused dispatch -> expert FFN ->
combine Pallas kernel's share of its roofline (forward only; its
backward runs as XLA ops), ops and bytes from its call shapes and the
routed rows of each traced step, time from the device trace."""

from bench.harness.readers import kernel_roofline


def read(run):
    return kernel_roofline(run, "expert_ffn_grouped")
