"""Operations and HBM bytes of one ``flash_attention`` Pallas call
(forward only: its backward runs as XLA ops), as the kernel computes
them.

The kernel's grid is (B, H, nq, nk) with (block_q, block_k) = (128, 128)
tiles clamped to the sequence.  Under a causal mask only the KV blocks
in the band [0, last block a query block sees] are computed, and blocks
outside it are neither computed nor fetched (their index map repeats
the last in-band block).  So per (batch, head):

  ops   = pairs * 2 * (2 * block_q * block_k * hd)   (s = q k^T; p v)
  bytes = 4 * (L * hd               q, read once per query block
               + pairs * 2 * block_k * hd   k and v, per in-band pair
               + L * hd)            the output, written once

with pairs = sum over query blocks of the in-band KV blocks.  Operands
are float32 here (4 bytes); the itemsize follows the call's dtype.
"""

from __future__ import annotations

ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2}


def ops_bytes(call: dict, ctx: dict = None, block_q: int = 128,
              block_k: int = 128):
    """``call``: {"operands": [(dtype, shape), ...], "result": [...]}
    from the compiled HLO; q, k, v are (B, H, L, hd) head-major."""
    (dt, q), (_, k), _ = call["operands"][:3]
    B, H, Lq, hd = q
    Lk = k[2]
    bq, bk = min(block_q, Lq), min(block_k, Lk)
    while Lq % bq:
        bq //= 2
    while Lk % bk:
        bk //= 2
    n_q, n_k = Lq // bq, Lk // bk
    pairs = sum(min(n_k - 1, (iq * bq + bq - 1) // bk) + 1
                for iq in range(n_q))
    item = ITEMSIZE[dt]
    ops = B * H * pairs * 2 * (2 * bq * bk * hd)
    nbytes = B * H * item * (Lq * hd + pairs * 2 * bk * hd + Lq * hd)
    return float(ops), float(nbytes)
