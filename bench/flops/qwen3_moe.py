"""Model FLOPs per trained token of the Qwen3-MoE family, from a
configuration's widths: the work this chip's share does.

Multiply-adds count 2 FLOPs, backward is 2 x forward, and nothing
recomputed counts (remat, capacity padding, dropped slots).  Per token
and per layer, forward, with q = H * hd and kv = K * hd wide:

  attention projections  2 * (2 * d * q + 2 * d * kv)    (q and o; k and v)
  causal attention       2 * 2 * q * (L + 1) / 2   (scores and values over
                                                    the (L + 1) / 2 keys a
                                                    token sees on average)
  router                 2 * d * E                 (all E outputs)
  held experts           top_k * (n_held / E) * 2 * 3 * d * f   (SwiGLU: the
                                                    routes that land on the
                                                    experts held here)

plus the logits over the vocabulary slice, 2 * d * V.  Every layer is
an MoE layer.  Norms, QK-norm, RoPE, softmax and the optimizer are
elementwise and not counted.
"""

from __future__ import annotations


def forward_flops_per_token(m: dict, seq_len: int) -> float:
    d, L = m["d_model"], seq_len
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    E = m["n_experts"]
    attn = 2 * (2 * d * q + 2 * d * kv) + 2 * 2 * q * (L + 1) / 2
    moe = (2 * d * E + m["top_k"] * m["n_experts_held"] / E
           * 2 * 3 * d * m["expert_d_ff"])
    return m["n_layers"] * (attn + moe) + 2 * d * m["vocab_size"]


def train_flops_per_token(m: dict, seq_len: int) -> float:
    return 3 * forward_flops_per_token(m, seq_len)
