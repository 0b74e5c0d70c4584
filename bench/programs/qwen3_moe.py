"""The program's entry for the Qwen3-MoE block family (configuration
files with ``"family": "qwen3_moe"``): its ``ModelConfig`` and
``AdamWConfig`` built from exactly the numbers of the benchmark's
configuration file, refusing any program setting that the reference
(``bench/reference/qwen3_moe.py``) does not compute."""

from __future__ import annotations

from dataclasses import replace

from bench.harness.spec import SpecError


def program_config(conf: dict, dtype: str = "float32"):
    """The program's ModelConfig with the widths and the share of the
    benchmark's configuration file.  ``dtype`` other than float32 is the
    program's own lower-precision path (the control), never a benchmark
    run."""
    from repro.configs import get_config
    m = conf["model"]
    cfg = get_config(conf["program_config"])
    cfg = replace(
        cfg, dtype=dtype, n_layers=m["n_layers"], d_model=m["d_model"],
        n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
        head_dim=m["head_dim"], vocab_size=m["vocab_size"],
        tie_embeddings=m["tie_embeddings"], norm_eps=m["norm_eps"],
        rope_theta=m["rope_theta"], qk_norm=m["qk_norm"],
        qkv_bias=m["attention_bias"], moe_period=m["moe_period"],
        moe=replace(cfg.moe, d_model=m["d_model"], d_ff=m["expert_d_ff"],
                    n_experts=m["n_experts"], n_held=m["n_experts_held"],
                    top_k=m["top_k"], capacity_factor=m["capacity_factor"],
                    normalize_topk=m["normalize_topk"],
                    n_shared_experts=m["n_shared_experts"],
                    glu=m["expert_glu"], act=m["expert_act"],
                    aux_loss_weight=m["aux_loss_weight"],
                    z_loss_weight=m["z_loss_weight"]))
    fixed = {"arch_type": "moe", "norm_type": "rmsnorm", "use_rope": True,
             "qkv_bias": False, "qk_norm": True, "tie_embeddings": False,
             "dtype": dtype, "logit_scale": 1.0, "parallel_block": False,
             "attn_window": None, "attn_chunk": None, "moe_period": 1}
    for k, want in fixed.items():
        if getattr(cfg, k) != want:
            raise SpecError(f"{conf['name']}: the program's {k} is "
                            f"{getattr(cfg, k)!r}, the reference's {want!r}")
    moe = {"glu": True, "act": "silu", "normalize_topk": True,
           "n_shared_experts": 0, "n_held": m["n_experts_held"]}
    for k, want in moe.items():
        if getattr(cfg.moe, k) != want:
            raise SpecError(f"{conf['name']}: the program's moe.{k} is "
                            f"{getattr(cfg.moe, k)!r}, the reference's "
                            f"{want!r}")
    return cfg


def adamw_config(conf: dict):
    from repro.optim import AdamWConfig
    o = conf["optimizer"]
    if o["decay_min_rank"] != 2:
        raise SpecError("the program decays leaves of rank >= 2 only")
    return AdamWConfig(
        lr=o["lr"], beta1=o["beta1"], beta2=o["beta2"], eps=o["eps"],
        weight_decay=o["weight_decay"], clip_norm=o["clip_norm"],
        warmup_steps=o["warmup_steps"], total_steps=o["total_steps"],
        min_lr_frac=o["min_lr_frac"])
