"""optimizer.device_ms: device time per step of AdamW, the global
gradient norm included: the ops under the program's ``adamw`` scope,
from the device trace, per chip, averaged over chips."""

from bench.harness.scopes import layer_ms


def read(run):
    return layer_ms(run, "adamw")
