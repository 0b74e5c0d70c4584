"""Operations and HBM bytes of one ``expert_ffn_grouped`` Pallas call
(the fused dispatch -> expert FFN -> combine megakernel; forward only),
as the kernel computes them.

Grid (E, n_t, n_f): expert e's capacity slots in row tiles of
``block_t`` (128) and its hidden width in ``block_f`` slices.  Only
tiles that hold routed rows run (``it * block_t < count[e]``); each
such tile gathers its rows one DMA each, runs
  h = act(x @ w1[e]) [@ w3[e]]  and  acc += h @ w2[e]
over every hidden slice, so it reads all of w1[e] and w2[e] (and w3[e])
once, and read-modify-writes its rows of the output.  Per call:

  ops   = 2 * rows * M * F * (3 if glu else 2)     (useful rows only)
  bytes = item * (tiles * M * F * (3 if glu else 2)   weights per tile
                  + rows * M                           gathered rows
                  + 2 * rows * M)                      output rows, f32

The routed rows come from the program's per-step counter (``expert_load``:
routed rows per expert summed over the MoE layers), averaged over the
traced steps and the layers; tiles = sum over experts of
ceil(rows_e / block_t) at those averages, so a layer whose counts sit
off the average is counted at the average.
"""

from __future__ import annotations

import math

ITEMSIZE = {"f32": 4, "bf16": 2}


def ops_bytes(call: dict, ctx: dict, block_t: int = 128):
    ops_in = call["operands"]
    dt = ops_in[2][0]                        # tokens (S, 1, M)
    E, M, F = ops_in[4][1]                   # w1 (E, M, F)
    glu = len(ops_in) == 8                   # rid, cnt, x, ws, w1, w3, w2, y
    loads = [x for x in ctx["loads"] if x is not None]
    if not loads:
        return None
    m = ctx["model"]
    n_moe = sum(1 for i in range(m["n_layers"]) if i % m["moe_period"] == 0)
    per_e = [sum(step[e] for step in loads) / len(loads) / n_moe
             for e in range(E)]
    rows = sum(per_e)
    tiles = sum(math.ceil(r / block_t) for r in per_e)
    mats = 3 if glu else 2
    item = ITEMSIZE[dt]
    ops = 2.0 * rows * M * F * mats
    nbytes = item * (tiles * M * F * mats + rows * M) + 4 * 2 * rows * M
    return ops, float(nbytes)
