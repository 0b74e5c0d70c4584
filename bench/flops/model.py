"""Model FLOPs per trained token, from a configuration's widths.

Counts the multiply-adds the model requires (2 FLOPs each), forward
and backward (backward = 2 x forward), and nothing recomputed: remat,
capacity padding and dropped-token slots are not counted.  Per token
and per layer, forward:

  attention projections  2 * 4 * d * d           (q, k, v, o)
  causal attention       2 * 2 * d * (L + 1) / 2 (scores and values over
                                                  the (L + 1) / 2 keys a
                                                  token sees on average)
  dense FFN              2 * 2 * d * f
  MoE layer              2 * d * E (router) + top_k * 2 * 2 * d * f_e

plus the logits, 2 * d * V.  Embedding lookups, norms, softmax and the
optimizer are elementwise and not counted.
"""

from __future__ import annotations


def forward_flops_per_token(m: dict, seq_len: int) -> float:
    d, L = m["d_model"], seq_len
    per_layer_attn = 2 * 4 * d * d + 2 * 2 * d * (L + 1) / 2
    dense = 2 * 2 * d * m["d_ff"]
    moe = (2 * d * m["n_experts"]
           + m["top_k"] * 2 * 2 * d * m["expert_d_ff"])
    n_moe = sum(1 for i in range(m["n_layers"]) if i % m["moe_period"] == 0)
    n_dense = m["n_layers"] - n_moe
    return (m["n_layers"] * per_layer_attn + n_dense * dense + n_moe * moe
            + 2 * d * m["vocab_size"])


def train_flops_per_token(m: dict, seq_len: int) -> float:
    return 3 * forward_flops_per_token(m, seq_len)
