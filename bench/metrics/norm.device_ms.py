"""norm.device_ms: device time per step of every norm (each block's and
the final one), forward and backward: the ops under the program's
``norm`` scope, from the device trace, per chip, averaged over chips."""

from bench.harness.scopes import layer_ms


def read(run):
    return layer_ms(run, "norm")
