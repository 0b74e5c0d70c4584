"""attention.bwd_device_ms: device time per step of the attention layer's
backward (XLA ops through the registry's custom VJPs): the ops under
the ``attn`` scope inside ``transpose(`` and outside the remat
recompute, from the device trace, per chip, averaged over chips."""

from bench.harness.scopes import layer_ms


def read(run):
    return layer_ms(run, "attn", "bwd")
