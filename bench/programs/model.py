"""The program's entry for the GPT-2 / BERT block family (configuration
files without a ``family`` key): its ``ModelConfig`` and ``AdamWConfig``
built from exactly the numbers of the benchmark's configuration file."""

from __future__ import annotations

from dataclasses import replace

from bench.harness.spec import SpecError

ACT = {"gelu_tanh": "gelu"}   # the program's names for the activations


def program_config(conf: dict, dtype: str = "float32"):
    """The program's ModelConfig with the widths of the benchmark's
    configuration file; refuses a file the program cannot run as
    written.  ``dtype`` other than float32 is the program's own
    lower-precision path (the control), never a benchmark run."""
    from repro.configs import get_config
    m = conf["model"]
    cfg = get_config(conf["program_config"])
    cfg = replace(
        cfg, dtype=dtype, n_layers=m["n_layers"], d_model=m["d_model"],
        n_heads=m["n_heads"], n_kv_heads=m["n_heads"], d_ff=m["d_ff"],
        vocab_size=m["vocab_size"], tie_embeddings=m["tie_embeddings"],
        norm_eps=m["norm_eps"], moe_period=m["moe_period"],
        moe=replace(cfg.moe, d_model=m["d_model"], d_ff=m["expert_d_ff"],
                    n_experts=m["n_experts"], top_k=m["top_k"],
                    capacity_factor=m["capacity_factor"],
                    aux_loss_weight=m["aux_loss_weight"],
                    z_loss_weight=m["z_loss_weight"],
                    act=ACT.get(m["expert_act"], m["expert_act"])))
    fixed = {"arch_type": "moe", "norm_type": "layernorm",
             "use_rope": False, "qkv_bias": True, "ffn_bias": True,
             "glu": False, "ffn_act": ACT.get(m["dense_act"],
                                              m["dense_act"]),
             "dtype": dtype, "logit_scale": 1.0,
             "parallel_block": False, "attn_window": None,
             "attn_chunk": None}
    for k, want in fixed.items():
        if getattr(cfg, k) != want:
            raise SpecError(f"{conf['name']}: the program's {k} is "
                            f"{getattr(cfg, k)!r}, the reference's {want!r}")
    if cfg.moe.glu or \
            cfg.moe.n_shared_experts or cfg.moe.normalize_topk:
        raise SpecError(f"{conf['name']}: the program's experts are not "
                        f"the reference's ({cfg.moe})")
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
