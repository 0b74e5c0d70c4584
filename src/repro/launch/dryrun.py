import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The lines above MUST run before any jax import: jax locks the platform
# and the device count at first init.  The dry-run is a CPU fake-device
# tool by design: pinning the CPU keeps it (and any child) off a TPU
# host's chip.  REPRO_DRYRUN_DEVICES overrides the count for scaled-down CI.
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])
# The dry-run needs the *SPMD-partitioned program* (shardings, collectives,
# memory), not fast host code: turning LLVM codegen effort down makes the
# 512-device CPU-emulated compiles tractable without changing the HLO-level
# analyses this harness records.  Disable with REPRO_DRYRUN_FULL_OPT=1.
if not os.environ.get("REPRO_DRYRUN_FULL_OPT"):
    os.environ["XLA_FLAGS"] += " --xla_backend_optimization_level=0"

"""Multi-pod dry-run: prove the distribution config is coherent.

Runs on CPU fake devices only (``JAX_PLATFORMS=cpu`` is pinned above,
for this process and its children), so it never takes a TPU host's chip.

For every (architecture x input shape x mesh) combination, lower + compile
the appropriate step function (train_step / prefill / serve_step) against
ShapeDtypeStruct stand-ins (no allocation), then record:

  * memory_analysis()  — proves the program fits per-device HBM,
  * cost_analysis()    — HLO FLOPs / bytes for the roofline,
  * collective bytes   — parsed from the compiled HLO per §Roofline.

Artifacts land in artifacts/dryrun/<arch>__<shape>__<mesh>[__<sched>].json.

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import dataclasses
import json
import time
from dataclasses import replace

import jax
import jax.numpy as jnp

from repro.analysis.hlo import parse_collectives
from repro.analysis.layerwise import layerwise_costs
from repro.analysis.roofline import roofline_terms
from repro.configs import INPUT_SHAPES, get_config, input_specs
from repro.configs.registry import ASSIGNED
from repro.core import autosched
from repro.core.perfmodel import MoELayerShape
from repro.launch.mesh import dims_for, make_production_mesh, make_test_mesh
from repro.models import build_model
from repro.optim.adamw import AdamWConfig, adamw_init, opt_state_specs
from repro.parallel.mesh import axis_size
from repro.train.loop import (cache_specs, make_guarded_train_step,
                              make_prefill_fn, make_serve_step,
                              make_train_step, named_tree)

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "artifacts", "dryrun")


def _moe_pool_cap(cfg, shape, sizes, nb, sched_name):
    """Per-device token pool and capacity exactly as apply_moe computes
    them: the token-shard group is the batch axes plus — under the
    seqpar contract — the MP axes (moe.shard_pool_capacity).  Decode
    shapes mirror the inference class (drop-free capacity)."""
    from repro.core.moe import shard_pool_capacity
    from repro.core.pipeline import UNCHUNKED_OF
    tokens_global = shape.global_batch * (
        shape.seq_len if shape.kind != "decode" else 1)
    seqpar = UNCHUNKED_OF.get(sched_name, sched_name) == "s1_seqpar"
    n_shard = max(nb, 1) * (max(sizes["mp"], 1) if seqpar else 1)
    s_local, cap = shard_pool_capacity(tokens_global, n_shard,
                                       sizes["mp"], cfg.moe.gate_config(),
                                       infer=shape.kind == "decode")
    return max(s_local, 1), cap


def _placement_summary(cfg):
    """JSON-ready expert-placement record for the artifact: None for
    dense/uniform configs, the resolved placement summary otherwise."""
    if cfg.moe is None or cfg.moe.placement is None:
        return None
    pl = cfg.moe.placement
    if pl == "auto":
        from repro.core import autosched
        live = autosched.current_placement()
        return {"mode": "auto", "epoch": autosched.placement_epoch(),
                "current": live.summary() if live is not None else None}
    return {"mode": "forced", "current": pl.summary()}


def count_params(shapes) -> int:
    import math
    return sum(math.prod(l.shape) if l.shape else 1
               for l in jax.tree.leaves(shapes))


def active_param_count(cfg, shapes) -> float:
    """Active params per token: full count minus inactive expert fraction."""
    total = count_params(shapes)
    if cfg.moe is None:
        return float(total)
    moe = cfg.moe
    n_moe_layers = sum(1 for k in cfg.layer_kinds() if k.startswith("moe"))
    per_expert = moe.d_model * moe.d_ff * (3 if moe.glu else 2)
    inactive = n_moe_layers * per_expert * (moe.n_experts - moe.top_k)
    return float(total - inactive)


def variant_config(cfg, shape_name: str):
    """Apply the SWA variant for long_500k on full-attention archs."""
    shape = INPUT_SHAPES[shape_name]
    if shape.name != "long_500k" or cfg.sub_quadratic:
        return cfg, ""
    if cfg.arch_type == "audio":
        return None, "skip: enc-dec audio arch, 500k decode not meaningful"
    return replace(cfg, attn_window=8192), "swa"


def lower_one(arch: str, shape_name: str, multi_pod: bool,
              schedule: str = None, dtype: str = "bfloat16",
              save_hlo: bool = False, cache_seq_shard: bool = False,
              saa_chunks: int = None, seq_parallel: bool = False,
              pipeline_chunks: int = None, run_step: bool = False,
              reduced: bool = False, seq: int = None,
              batch_size: int = None, wire_dtype: str = None,
              dump_plan: bool = False, guards: bool = False,
              audit: bool = False) -> dict:
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    cfg, variant = variant_config(cfg, shape_name)
    if cfg is None:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "skipped": variant}
    cfg = replace(cfg, dtype=dtype)
    if cache_seq_shard:
        cfg = replace(cfg, context_parallel_decode=True)
    if seq_parallel:
        cfg = replace(cfg, seq_parallel=True)
    if saa_chunks is not None and cfg.moe is not None:
        cfg = replace(cfg, moe=replace(cfg.moe, saa_chunks=saa_chunks))
    if pipeline_chunks is not None and cfg.moe is not None:
        cfg = replace(cfg, moe=replace(cfg.moe,
                                       pipeline_chunks=pipeline_chunks))
    if wire_dtype is not None and cfg.moe is not None:
        from repro.core.collectives import CommConfig
        cfg = replace(cfg, moe=replace(
            cfg.moe, comm=replace(cfg.moe.comm or CommConfig(),
                                  wire_dtype=wire_dtype)))
    shape = INPUT_SHAPES[shape_name]
    if seq or batch_size:
        shape = dataclasses.replace(
            shape, seq_len=seq or shape.seq_len,
            global_batch=batch_size or shape.global_batch)
    n_dev = int(os.environ.get("REPRO_DRYRUN_DEVICES", "512"))
    mesh = (make_production_mesh(multi_pod=multi_pod) if n_dev >= 512
            else make_test_mesh(multi_pod=multi_pod))
    dims = dims_for(cfg, multi_pod)
    model = build_model(cfg)

    pspecs = model.specs(mesh, dims)
    p_sh = named_tree(mesh, pspecs)
    p_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    batch = input_specs(cfg, shape)
    baxes = tuple(dims.batch_axes)
    nb = axis_size(mesh, baxes) if baxes else 1

    def bshard(leaf):
        if leaf.ndim >= 1 and leaf.shape and leaf.shape[0] == shape.global_batch \
                and shape.global_batch % nb == 0 and baxes:
            return named_tree(mesh, jax.sharding.PartitionSpec(
                baxes, *([None] * (leaf.ndim - 1))))
        return named_tree(mesh, jax.sharding.PartitionSpec(
            *([None] * leaf.ndim)))
    b_sh = jax.tree.map(bshard, batch)

    sched = schedule
    chunks_pick = cfg.moe.pipeline_chunks if cfg.moe is not None else 0
    wire_pick = (cfg.moe.comm.wire_dtype if cfg.moe is not None
                 else "n/a")
    sched_auto = (cfg.moe is not None and not sched
                  and cfg.moe.schedule == "auto")
    if cfg.moe is not None and (sched_auto or wire_pick == "auto"):
        from repro.core.pipeline import UNCHUNKED_OF, clamp_chunks

        sizes = dims.sizes(mesh)
        # mirror apply_moe's pool/capacity + chunk-candidate clamping so
        # the recorded decision matches what the trace will compile
        # (shard_pool_capacity is the same helper apply_moe calls)
        s_local, cap = _moe_pool_cap(cfg, shape, sizes, nb,
                                     sched or cfg.moe.schedule)
        infer = shape.kind == "decode"
        # decode pools never chunk (mirrors apply_moe's infer grid)
        cands = ((1,) if infer else
                 tuple(sorted({clamp_chunks(cap // max(sizes["mp"], 1), n)
                               for n in autosched.DEFAULT_CHUNKS})))
        forced = None
        if not sched_auto:
            # forced schedule + wire="auto": wire-only decision, exactly
            # as apply_moe will make it
            base = sched or cfg.moe.schedule
            forced = (UNCHUNKED_OF.get(base, base),)
            cands = (clamp_chunks(cap // max(sizes["mp"], 1),
                                  cfg.moe.pipeline_chunks),)
        wire_cands = (autosched.AUTO_WIRE if wire_pick == "auto"
                      else (wire_pick,))
        decision = autosched.decide(MoELayerShape(
            B=1, L=s_local, M=cfg.d_model, H=cfg.moe.d_ff,
            E=cfg.moe.n_experts, k=cfg.moe.top_k,
            f=cfg.moe.capacity_factor, n_mp=sizes["mp"],
            n_esp=sizes["esp"], n_ep=sizes["ep"], infer=infer),
            chunk_candidates=cands, wire_candidates=wire_cands,
            schedules=forced)
        if sched_auto:
            sched_pick, chunks_pick = decision.schedule, decision.n_chunks
        else:
            sched_pick = sched or cfg.moe.schedule
        if wire_pick == "auto":
            wire_pick = decision.wire_dtype
    else:
        sched_pick = sched or (cfg.moe.schedule if cfg.moe is not None
                               else "n/a")

    plan_dump = None
    if dump_plan and cfg.moe is not None and sched_pick != "n/a":
        # serialize the chosen schedule's stage graph exactly as the MoE
        # layers will build it: same capacity, chunk clamp and wire dtype
        from repro.core.collectives import CommConfig
        from repro.core.pipeline import UNCHUNKED_OF
        from repro.core.plan import build_plan, format_plan, plan_summary
        from repro.core.schedules import MoEShardInfo
        sizes = dims.sizes(mesh)
        s_local, cap = _moe_pool_cap(cfg, shape, sizes, nb, sched_pick)
        winfo = MoEShardInfo(
            ep_axes=tuple(dims.ep), esp_axes=tuple(dims.esp),
            mp_axes=tuple(dims.mp), n_ep=sizes["ep"], n_esp=sizes["esp"],
            n_mp=sizes["mp"], tokens=s_local, cap=cap,
            gate=cfg.moe.gate_config(), glu=cfg.moe.glu,
            saa_chunks=cfg.moe.saa_chunks,
            pipeline_chunks=max(chunks_pick, 1),
            comm=CommConfig(
                wire_dtype=wire_pick if wire_pick != "auto" else "f32",
                scaling=(cfg.moe.comm or CommConfig()).scaling))
        p = build_plan(UNCHUNKED_OF.get(sched_pick, sched_pick), winfo)
        plan_dump = plan_summary(p)
        print(format_plan(p), flush=True)

    audit_reports = None
    if audit and cfg.moe is not None:
        # predicted-vs-measured schedule audit on a small subset of the
        # fake-device farm: compile + run the obs prefix-timing harness
        # and join against PerfModel.t_plan_stages.  Host-emulated
        # timings are noisy — the point is the joined REPORT (schema,
        # stage coverage, calibration scale), not CPU milliseconds.
        from repro.obs.audit import DEFAULT_AUDIT_SCHEDULES, \
            run_schedule_audit
        from repro.obs.trace import subset_mesh
        from repro.parallel.mesh import ParallelDims
        a_mesh = subset_mesh((4, 2), ("data", "model"))
        a_dims = ParallelDims(ep=("data",), esp=("model",),
                              mp=("model",))
        audit_reports = run_schedule_audit(
            a_mesh, a_dims, cfg.moe, tokens_global=256,
            schedules=DEFAULT_AUDIT_SCHEDULES, iters=3, warmup=1)
        for rep in audit_reports:
            worst = rep["worst"][:3]
            print(f"[audit] {rep['schedule']}: "
                  f"measured {rep['total_measured_s'] * 1e3:.3f} ms, "
                  f"predicted {rep['total_predicted_s'] * 1e3:.3f} ms, "
                  f"time_scale "
                  f"{rep['calibration']['time_scale']:.3g}, "
                  f"worst {worst}", flush=True)

    t0 = time.perf_counter()
    if shape.kind == "train":
        opt_cfg = AdamWConfig()
        o_shapes = jax.eval_shape(adamw_init, p_shapes)
        # ZeRO-1 (production default): shard optimizer moments' leading dim
        # over the pure-DP axes. For dense archs that's `data` (+`pod`);
        # for MoE archs `data` serves EP, so only `pod` remains multi-pod.
        zero_axes = tuple(dims.dp) + (
            () if cfg.moe is not None else tuple(dims.ep))
        if not zero_axes and cfg.moe is None and not multi_pod:
            zero_axes = ("data",)
        o_sh = named_tree(mesh, opt_state_specs(
            pspecs, mesh=mesh, dp_axes=zero_axes, zero1=bool(zero_axes),
            params_shape=p_shapes))
        if guards:
            # the fault-tolerant step (skip-step where-select + LR
            # backoff): proves the GUARDED program lowers/compiles/fits
            # on the production mesh, not just the plain one
            fn = make_guarded_train_step(model, mesh, dims, opt_cfg,
                                         schedule)
            scalar = jax.ShapeDtypeStruct((), jnp.float32)
            jitted = jax.jit(fn,
                             in_shardings=(p_sh, o_sh, b_sh, None, None),
                             out_shardings=(p_sh, o_sh, None))
            lowered = jitted.lower(p_shapes, o_shapes, batch, scalar,
                                   scalar)
        else:
            fn = make_train_step(model, mesh, dims, opt_cfg, schedule)
            jitted = jax.jit(fn, in_shardings=(p_sh, o_sh, b_sh),
                             out_shardings=(p_sh, o_sh, None))
            lowered = jitted.lower(p_shapes, o_shapes, batch)
        tokens = shape.global_batch * shape.seq_len
        flops_mult = 3.0   # fwd + bwd
    elif shape.kind == "prefill":
        fn = make_prefill_fn(model, mesh, dims, schedule)
        jitted = jax.jit(fn, in_shardings=(p_sh, b_sh))
        lowered = jitted.lower(p_shapes, batch)
        tokens = shape.global_batch * shape.seq_len
        flops_mult = 1.0
    else:  # decode: one token against a seq_len cache
        c_shapes = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len,
                                     jnp.dtype(cfg.dtype)))
        c_specs = cache_specs(model, mesh, dims, shape.global_batch,
                              shape.seq_len, seq_shard=cache_seq_shard)
        c_sh = named_tree(mesh, c_specs)
        fn = make_serve_step(model, mesh, dims, schedule)
        if model.has_cross:
            # per-request precomputed cross-attention K/V (image/audio ctx)
            kv_shapes = jax.eval_shape(
                lambda p, b: model.ctx_kv(p, b, mesh=mesh, dims=dims),
                p_shapes, batch)
            kv_specs = jax.tree.map(
                lambda l: jax.sharding.PartitionSpec(
                    None, baxes if (l.ndim >= 2 and baxes and
                                    l.shape[1] % nb == 0) else None,
                    *([None] * (l.ndim - 2))),
                kv_shapes)
            kv_sh = named_tree(mesh, kv_specs)
            jitted = jax.jit(fn, in_shardings=(p_sh, c_sh, b_sh, kv_sh),
                             out_shardings=(None, c_sh),
                             donate_argnums=(1,))
            lowered = jitted.lower(p_shapes, c_shapes, batch, kv_shapes)
        else:
            jitted = jax.jit(fn, in_shardings=(p_sh, c_sh, b_sh),
                             out_shardings=(None, c_sh),
                             donate_argnums=(1,))
            lowered = jitted.lower(p_shapes, c_shapes, batch)
        tokens = shape.global_batch
        flops_mult = 1.0
    t_lower = time.perf_counter() - t0

    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    step_metrics = None
    if run_step and shape.kind == "train":
        # prove the program end-to-end: init real (sharded) params and
        # optimizer state, run ONE optimizer step on synthetic tokens.
        params = jax.jit(model.init, out_shardings=p_sh)(
            jax.random.PRNGKey(0))
        opt_state = jax.jit(adamw_init, out_shardings=o_sh)(params)
        concrete = jax.tree.map(
            lambda l, s: jax.device_put(jnp.zeros(l.shape, l.dtype), s),
            batch, b_sh)
        if guards:
            one, zero = jnp.float32(1.0), jnp.float32(0.0)
            _, _, metrics = compiled(params, opt_state, concrete, one,
                                     zero)
        else:
            _, _, metrics = compiled(params, opt_state, concrete)
        step_metrics = {k: float(v) for k, v in metrics.items()
                        if getattr(v, "ndim", 0) == 0}
        el = metrics.get("expert_load")
        if el is not None and getattr(el, "ndim", 0) == 1 and el.shape[-1]:
            # per-expert routed-row counts (summed over layers): the
            # dropless grouped kernel's actual group sizes
            step_metrics["expert_load"] = [
                float(c) for c in jax.device_get(el)]
        print(f"[step] {arch} x {shape_name} sched={sched_pick} "
              f"wire={wire_pick} "
              f"loss={step_metrics.get('loss', float('nan')):.4f}",
              flush=True)

    mem = compiled.memory_analysis()
    mem_d = {}
    if mem is not None:
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            mem_d[f] = getattr(mem, f, None)
    ca_list = compiled.cost_analysis()
    ca = ca_list if isinstance(ca_list, dict) else (
        ca_list[0] if ca_list else {})
    hlo = compiled.as_text()
    stats = parse_collectives(hlo)

    chips = mesh.devices.size
    n_params = count_params(p_shapes)
    n_active = active_param_count(cfg, p_shapes)
    model_flops = flops_mult * 2.0 * n_active * tokens  # 6ND = 3 * 2ND

    # Trip-count-correct accounting: XLA cost_analysis counts scan bodies
    # once, so roofline terms come from the layer-wise sums (x n_layers),
    # while the full-program compile above remains the fits/coherence proof.
    # The roofline table is single-pod only (§Roofline), so multi-pod combos
    # skip the extra per-block compiles and report raw program costs.
    if not multi_pod:
        lw = layerwise_costs(model, cfg, mesh, dims, shape, kind=shape.kind,
                             schedule=schedule)
        # lw is per-device; model_flops is whole-program -> per-chip ratio
        # uses chips inside roofline_terms, so scale up to whole-program.
        rl = roofline_terms({"flops": lw["flops"] * chips,
                             "bytes accessed": lw["bytes"] * chips},
                            lw["coll"], chips, model_flops)
    else:
        rl = roofline_terms(ca, stats.total_bytes, chips, model_flops)

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "variant": (variant + ("+reduced" if reduced else "")).lstrip("+"),
        "schedule": sched_pick, "pipeline_chunks": chunks_pick,
        "wire_dtype": wire_pick,
        # the expert placement the MoE layers would trace under: the
        # config's own (None | "auto" | concrete) resolved against the
        # process-wide autosched registry, as a JSON-ready summary
        "placement": _placement_summary(cfg),
        "plan": plan_dump,
        "audit": audit_reports,
        "step_metrics": step_metrics,
        # guarded combos record the guard-rail outcome: step_metrics
        # carries the jitted "nonfinite" flag (0.0 = the update applied)
        "robustness": {"guards": True,
                       "nonfinite": (step_metrics or {}).get("nonfinite"),
                       "lr_scale": 1.0} if guards else None,
        "chips": chips, "dtype": dtype,
        "n_params": n_params, "n_active_params": n_active,
        "tokens_per_step": tokens,
        "lower_s": t_lower, "compile_s": t_compile,
        "memory_analysis": mem_d,
        "cost_flops": float(ca.get("flops", 0.0)),
        "cost_bytes": float(ca.get("bytes accessed", 0.0)),
        "collectives": {"counts": stats.counts,
                        "bytes": stats.bytes_by_kind,
                        "total_bytes": stats.total_bytes},
        "roofline": rl.as_dict(),
        "hlo_lines": hlo.count("\n"),
    }
    if save_hlo:
        os.makedirs(ART_DIR, exist_ok=True)
        with open(os.path.join(
                ART_DIR, f"{arch}__{shape_name}__"
                f"{'multi' if multi_pod else 'single'}.hlo"), "w") as f:
            f.write(hlo)
    return rec


def save(rec: dict, suffix: str = ""):
    os.makedirs(ART_DIR, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{suffix}.json"
    with open(os.path.join(ART_DIR, name), "w") as f:
        json.dump(rec, f, indent=1)
    return name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--schedule", default=None,
                    help="force a Parm schedule (baseline/s1/s2/s1_seqpar/"
                         "s2h or a pipelined *_pipe variant)")
    ap.add_argument("--dump-plan", action="store_true",
                    help="print the chosen schedule's plan-IR stage graph "
                         "and record it (stages, deps, wire dtypes, chunk "
                         "count) in the artifact JSON")
    ap.add_argument("--audit", action="store_true",
                    help="run the predicted-vs-measured schedule audit "
                         "(s1/s2/s1g stage timings vs the perf model) on "
                         "a 4x2 subset mesh and record the reports in "
                         "the artifact JSON (pair with --reduced)")
    ap.add_argument("--pipeline-chunks", type=int, default=None,
                    help="micro-chunk count for the pipelined bodies")
    ap.add_argument("--wire-dtype", default=None,
                    choices=["f32", "bf16", "fp8_e4m3", "auto"],
                    help="wire format for the MoE collectives (auto = "
                         "joint autosched decision per layer shape)")
    ap.add_argument("--run-step", action="store_true",
                    help="after compiling a train combo, init real params "
                         "and execute one optimizer step (use with "
                         "--reduced/--seq/--batch on CPU)")
    ap.add_argument("--guards", action="store_true",
                    help="lower the GUARDED train step (non-finite "
                         "skip-step + LR backoff) and record the guard "
                         "outcome in the artifact")
    ap.add_argument("--reduced", action="store_true",
                    help="lower the smoke-scale config variant")
    ap.add_argument("--seq", type=int, default=None,
                    help="override the input shape's sequence length")
    ap.add_argument("--batch", type=int, default=None,
                    help="override the input shape's global batch")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--skip-existing", action="store_true",
                    help="skip combos whose artifact JSON already exists")
    ap.add_argument("--seq-parallel", action="store_true",
                    help="Megatron-SP residual stream (§Perf B2)")
    ap.add_argument("--cache-seq-shard", action="store_true",
                    help="shard attention KV caches along the length dim "
                         "over MP (context-parallel decode; §Perf lever)")
    ap.add_argument("--saa-chunks", type=int, default=None,
                    help="override SAA pipeline depth (1 = AAS, no overlap)")
    ap.add_argument("--tag", default="",
                    help="artifact filename suffix for perf iterations")
    args = ap.parse_args()

    archs = list(ASSIGNED) if args.all or not args.arch else [args.arch]
    shapes = list(INPUT_SHAPES) if args.all or not args.shape else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch} x {shape} x {'multi' if mp else 'single'}"
                if args.skip_existing:
                    sfx = f"__{args.schedule}" if args.schedule else ""
                    fname = os.path.join(
                        ART_DIR, f"{arch}__{shape}__"
                        f"{'multi' if mp else 'single'}{sfx}.json")
                    if os.path.exists(fname):
                        print(f"[have] {tag}", flush=True)
                        continue
                try:
                    rec = lower_one(arch, shape, mp, args.schedule,
                                    args.dtype, args.save_hlo,
                                    cache_seq_shard=args.cache_seq_shard,
                                    saa_chunks=args.saa_chunks,
                                    seq_parallel=args.seq_parallel,
                                    pipeline_chunks=args.pipeline_chunks,
                                    run_step=args.run_step,
                                    reduced=args.reduced, seq=args.seq,
                                    batch_size=args.batch,
                                    wire_dtype=args.wire_dtype,
                                    dump_plan=args.dump_plan,
                                    guards=args.guards,
                                    audit=args.audit)
                    sfx = f"__{args.schedule}" if args.schedule else ""
                    if args.tag:
                        sfx += f"__{args.tag}"
                    save(rec, sfx)
                    if rec.get("skipped"):
                        print(f"[skip] {tag}: {rec['skipped']}", flush=True)
                        continue
                    rl = rec["roofline"]
                    print(f"[ok]   {tag} sched={rec['schedule']} "
                          f"compile={rec['compile_s']:.1f}s "
                          f"flops={rec['cost_flops']:.3g} "
                          f"coll={rec['collectives']['total_bytes']:.3g}B "
                          f"bound={rl['bottleneck']}", flush=True)
                except Exception as e:  # noqa: BLE001
                    failures.append((tag, repr(e)))
                    print(f"[FAIL] {tag}: {e!r}", flush=True)
    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures: "
                         + "; ".join(t for t, _ in failures))
    print("dry-run complete: all combinations lowered and compiled.")


if __name__ == "__main__":
    main()
