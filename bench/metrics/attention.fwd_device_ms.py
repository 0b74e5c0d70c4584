"""attention.fwd_device_ms: device time per step of the attention layer's
forward, its remat recompute included: the ops under the program's
``attn`` scope (projections and the Pallas or XLA core) outside the
backward, from the device trace, per chip, averaged over chips."""

from bench.harness.scopes import layer_ms


def read(run):
    return layer_ms(run, "attn", "fwd")
