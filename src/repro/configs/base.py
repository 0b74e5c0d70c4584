"""Architecture config schema + input shape definitions.

Every assigned architecture is a ``ModelConfig`` instance in its own
module (src/repro/configs/<id>.py), registered in configs.registry.
The Parm-specific knobs live on the nested ``MoEConfig`` (``schedule``,
``saa_chunks``, ``pipeline_chunks``, ``autosched``, ``kernel``) and
thread from here through ``apply_moe`` into the shard_map schedule
bodies — see docs/architecture.md for the full path.
``input_specs`` builds the ShapeDtypeStruct stand-ins for the dry-run
(no device allocation), per input shape:

  train_4k     seq 4,096    global_batch 256   -> train_step
  prefill_32k  seq 32,768   global_batch 32    -> prefill (forward)
  decode_32k   seq 32,768   global_batch 128   -> serve_step (1 token + cache)
  long_500k    seq 524,288  global_batch 1     -> serve_step, sub-quadratic only
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.moe import MoEConfig
from repro.kernels.registry import KernelConfig


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str            # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


@dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str              # dense | moe | hybrid | ssm | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None
    rope_theta: float = 1e6
    use_rope: bool = True
    norm_type: str = "rmsnorm"
    norm_eps: float = 1e-5
    qkv_bias: bool = False
    qk_norm: bool = False               # RMSNorm over each head's q, k (Qwen3)
    ffn_bias: bool = False
    ffn_act: str = "silu"
    glu: bool = True
    tie_embeddings: bool = False
    parallel_block: bool = False      # command-r: attn || ffn
    logit_scale: float = 1.0
    # attention variants
    attn_window: Optional[int] = None     # sliding-window (SWA)
    attn_chunk: Optional[int] = None      # llama4 chunked local attention
    chunk_every: int = 0                  # every k-th layer full attn (iRoPE)
    # MoE (Parm's domain)
    moe: Optional[MoEConfig] = None
    moe_period: int = 1                   # every k-th layer is MoE
    # SSM / xLSTM / hybrid
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: float = 2.0
    slstm_every: int = 0                  # xLSTM: every k-th layer is sLSTM
    # VLM
    cross_every: int = 0                  # every k-th layer cross-attends
    n_ctx_tokens: int = 0                 # image/audio context length
    # audio enc-dec
    encoder_layers: int = 0
    encoder_seq: int = 0
    # execution
    dtype: str = "float32"
    remat: bool = True
    use_pallas: bool = False              # legacy: force the pallas backend
    kernel: KernelConfig = KernelConfig()  # hot-path op backend + tiles
    cache_masked_update: bool = False   # elementwise KV write (§Perf C2 opt)
    seq_parallel: bool = False          # Megatron-SP residual stream (§Perf B2)
    context_parallel_decode: bool = False  # shard decode scores on cache dim
    source: str = ""                      # citation

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def kernel_cfg(self) -> KernelConfig:
        """Effective kernel config: the legacy ``use_pallas`` flag pins the
        backend when the config itself is still on ``auto``."""
        if self.use_pallas and self.kernel.backend == "auto":
            return replace(self.kernel, backend="pallas")
        return self.kernel

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for long_500k (SSM/hybrid or windowed/chunked attention)."""
        if self.arch_type in ("ssm", "hybrid"):
            return True
        return self.attn_window is not None or self.attn_chunk is not None

    def layer_kinds(self) -> list:
        """Per-layer block kind, driving run-partitioned layer scans."""
        kinds = []
        for i in range(self.n_layers):
            if self.arch_type == "ssm":
                k = "slstm" if (self.slstm_every
                                and i % self.slstm_every == self.slstm_every - 1) \
                    else "mlstm"
            elif self.arch_type == "hybrid":
                k = "hymba"
            elif self.arch_type == "audio":
                k = "xdec"  # whisper decoder: self-attn + cross-attn + FFN
            elif self.cross_every and i % self.cross_every == self.cross_every - 1:
                k = "cross"
            elif self.moe is not None and i % self.moe_period == 0:
                k = "moe"
            else:
                k = "dense"
            # llama4 iRoPE: every chunk_every-th layer uses full (NoPE) attn
            if (self.attn_chunk and self.chunk_every
                    and i % self.chunk_every == self.chunk_every - 1
                    and k in ("dense", "moe")):
                k += "_full"
            kinds.append(k)
        return kinds

    def runs(self) -> list:
        """Consecutive same-kind layer runs: [(kind, count), ...]."""
        out = []
        for k in self.layer_kinds():
            if out and out[-1][0] == k:
                out[-1][1] += 1
            else:
                out.append([k, 1])
        return [(k, n) for k, n in out]

    def reduced(self, n_layers=2, d_model=None, n_experts=None) -> "ModelConfig":
        """Smoke-test variant: <=2 layers, d_model<=512, <=4 experts."""
        d = min(self.d_model, d_model or 256)
        heads = max(1, min(self.n_heads, 4))
        kv = max(1, min(self.n_kv_heads, heads))
        hd = max(16, d // heads)
        moe = self.moe
        if moe is not None:
            e = min(moe.n_experts, n_experts or 4)
            moe = replace(moe, d_model=d, d_ff=max(32, moe.d_ff // 16),
                          n_experts=e, top_k=min(moe.top_k, e),
                          n_held=None if moe.n_held is None
                          else min(moe.n_held, e))
        return replace(
            self, name=self.name + "-smoke", n_layers=n_layers, d_model=d,
            n_heads=heads, n_kv_heads=kv, head_dim=hd,
            d_ff=max(64, min(self.d_ff, 4 * d)) if self.d_ff else 0,
            vocab_size=min(self.vocab_size, 512), moe=moe,
            encoder_layers=min(self.encoder_layers, 2),
            encoder_seq=min(self.encoder_seq, 32) if self.encoder_seq else 0,
            n_ctx_tokens=min(self.n_ctx_tokens, 16) if self.n_ctx_tokens else 0,
            attn_window=min(self.attn_window, 64) if self.attn_window else None,
            attn_chunk=min(self.attn_chunk, 64) if self.attn_chunk else None,
            cross_every=min(self.cross_every, 2) if self.cross_every else 0,
            slstm_every=min(self.slstm_every, 2) if self.slstm_every else 0,
            remat=False)


def input_specs(cfg: ModelConfig, shape: InputShape, dtype=jnp.int32) -> dict:
    """ShapeDtypeStruct stand-ins for every model input (no allocation).

    Modality frontends are stubs per the assignment carve-out: VLM image
    patches and audio frames arrive as precomputed embeddings of the
    right shape.
    """
    B, L = shape.global_batch, shape.seq_len
    f32 = jnp.dtype(cfg.dtype)
    if shape.kind == "train":
        specs = {
            "tokens": jax.ShapeDtypeStruct((B, L), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, L), jnp.int32),
        }
    elif shape.kind == "prefill":
        specs = {"tokens": jax.ShapeDtypeStruct((B, L), jnp.int32)}
    else:  # decode: one new token against a seq_len cache
        specs = {
            "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
            "step": jax.ShapeDtypeStruct((), jnp.int32),
        }
    if cfg.arch_type == "vlm":
        specs["ctx_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.n_ctx_tokens, cfg.d_model), f32)
    if cfg.arch_type == "audio":
        specs["ctx_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.encoder_seq, cfg.d_model), f32)
    return specs
