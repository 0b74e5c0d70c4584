"""flash_attention_gqa_roofline: the forward Pallas attention kernel's
share of its roofline at GQA 8:1 and head_dim 128, time from the device
trace.  Ops as ``bench/flops/kernels/flash_attention.py`` counts them.
HBM bytes as the kernel reads them under GQA: a grid step takes a block
of query heads with the KV heads they share, so each in-band K/V block
is read once per KV head.  That counter reads it once per query head,
which is right only where H = K (``flash_attention_roofline``'s cells)
and at H = 8 K puts the K/V traffic 8x too high."""

from bench.flops.kernels import flash_attention as counter
from bench.harness.readers import kernel_roofline

KERNEL = "flash_attention"


def ops_bytes(call: dict, ctx: dict = None):
    """(ops, bytes) of one call: ``counter``'s, with its K/V bytes
    scaled from H query heads to the K KV heads the kernel reads."""
    ops, nbytes = counter.ops_bytes(call, ctx)
    (dt, (B, H, Lq, hd)), (_, (_, K, _, _)) = call["operands"][:2]
    q_out = B * H * counter.ITEMSIZE[dt] * 2 * Lq * hd   # q read, out written
    return ops, q_out + (nbytes - q_out) * K / H


def _least_s(run, ops_nbytes) -> float:
    ops, nbytes = ops_nbytes
    pk = run["peaks"]
    return max(ops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])


def read(run):
    """``kernel_roofline``'s share, its least time recounted with the
    K/V bytes above (the calls and their device time are the same)."""
    share = kernel_roofline(run, KERNEL)
    if share is None:
        return None
    call = run["kernels"][KERNEL][0]
    return share * (_least_s(run, ops_bytes(call))
                    / _least_s(run, counter.ops_bytes(call)))
