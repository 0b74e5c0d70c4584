"""head.device_ms: device time per step of the LM head and the
cross-entropy, forward and backward: the ops under the program's
``head`` scope, from the device trace, per chip, averaged over chips."""

from bench.harness.scopes import layer_ms


def read(run):
    return layer_ms(run, "head")
