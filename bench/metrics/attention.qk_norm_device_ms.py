"""attention.qk_norm_device_ms: device time per step of the RMSNorm over
each head's q and k (the ``qk_norm`` scope inside ``attn``), forward and
backward, from the device trace, per chip, averaged over chips."""

from bench.harness.scopes import layer_ms


def read(run):
    return layer_ms(run, "qk_norm")
