"""moe.held_device_ms: device time per step of the MoE layer that holds
a share of the experts: the ops under its plan scopes, forward and
backward, read as ``moe.device_ms`` reads them."""

from bench.harness.spec import metric_reader


def read(run):
    return metric_reader("moe.device_ms")(run)
