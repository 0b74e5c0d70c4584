"""attention.blocked_bwd_device_ms: device time per step of the attention
backward where the kernel's VJP walks the scores in query blocks
(``kernels/registry.vjp_query_blocks``): the ops under ``attn`` inside
``transpose(`` and outside the remat recompute."""

from bench.harness.scopes import layer_ms


def read(run):
    return layer_ms(run, "attn", "bwd")
