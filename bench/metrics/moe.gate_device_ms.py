"""moe.gate_device_ms: device time per step of the MoE plan's gate stage
(``<plan>.gate``: the router over all E experts, top-k and GShard slots,
which do not shrink with the share of experts held here), forward and
backward, from the device trace, per chip, averaged over chips."""

from bench.harness.scopes import layer_ms


def read(run):
    ms = [layer_ms(run, f"{s}.gate") for s in run.get("schedules") or ()]
    ms = [v for v in ms if v is not None]
    return sum(ms) if ms else None
