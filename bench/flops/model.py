"""Model FLOPs per trained token, from a configuration's widths.

Counts the multiply-adds the model requires (2 FLOPs each), forward
and backward (backward = 2 x forward), and nothing recomputed: remat,
capacity padding and dropped-token slots are not counted.  Per token
and per layer, forward, with q = H * hd and kv = K * hd wide (H query
heads, K key/value heads of width hd):

  attention projections  2 * (2 * d * q + 2 * d * kv)   (q and o; k and v)
  causal attention       2 * 2 * q * (L + 1) / 2   (scores and values over
                                                    the (L + 1) / 2 keys a
                                                    token sees on average)
  dense FFN              2 * g * d * f             (g = 3 with a GLU, else 2)
  MoE layer              2 * d * E (router) + top_k * 2 * g_e * d * f_e

plus the logits, 2 * d * V.  Embedding lookups, norms, softmax and the
optimizer are elementwise and not counted.  Keys the file may leave
out: ``n_kv_heads`` (default ``n_heads``), ``head_dim`` (default
``d_model // n_heads``), ``dense_glu`` and ``expert_glu`` (default
false); with them left out, K = H and H * hd = d, the GPT-2 / BERT
block.  Every other MoE layer pattern is ``i % moe_period == 0``.
"""

from __future__ import annotations


def forward_flops_per_token(m: dict, seq_len: int) -> float:
    d, L = m["d_model"], seq_len
    H = m["n_heads"]
    hd = m.get("head_dim", d // H)
    q, kv = H * hd, m.get("n_kv_heads", H) * hd
    per_layer_attn = 2 * (2 * d * q + 2 * d * kv) + 2 * 2 * q * (L + 1) / 2
    dense = 2 * (3 if m.get("dense_glu") else 2) * d * m["d_ff"]
    moe = (2 * d * m["n_experts"] + m["top_k"] * 2
           * (3 if m.get("expert_glu") else 2) * d * m["expert_d_ff"])
    n_moe = sum(1 for i in range(m["n_layers"]) if i % m["moe_period"] == 0)
    n_dense = m["n_layers"] - n_moe
    return (m["n_layers"] * per_layer_attn + n_dense * dense + n_moe * moe
            + 2 * d * m["vocab_size"])


def train_flops_per_token(m: dict, seq_len: int) -> float:
    return 3 * forward_flops_per_token(m, seq_len)
