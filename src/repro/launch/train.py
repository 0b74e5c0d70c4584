"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch gpt2-moe --reduced \
      --steps 200 --seq 256 --batch 8 --schedule auto

Full-size configs target the production mesh (real TPU pods); --reduced
runs the smoke-scale variant on whatever devices are present.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from dataclasses import replace

import jax

from repro import obs
from repro.configs import get_config
from repro.data import DataConfig, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import dims_for, local_mesh, make_production_mesh
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.train import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--schedule", default=None,
                    help="Parm schedule override (baseline/s1/s2/s1_seqpar/"
                         "s2h, their *_pipe pipelined variants, or auto; "
                         "any schedule registered in repro.core.plan works)")
    ap.add_argument("--pipeline-chunks", type=int, default=None,
                    help="micro-chunk count for the pipelined bodies "
                         "(1 = unchunked)")
    ap.add_argument("--autosched", default=None,
                    choices=["analytic", "measured"],
                    help="schedule=auto decision mode: score the perf model "
                         "or calibrate each candidate on the live mesh")
    ap.add_argument("--wire-dtype", default=None,
                    choices=["f32", "bf16", "fp8_e4m3", "auto"],
                    help="wire format for the MoE collectives: ship "
                         "AlltoAll/AllGather payloads at this width "
                         "(auto = let the autoscheduler pick f32 vs bf16 "
                         "per layer shape; decisions print after step 0)")
    ap.add_argument("--placement", default="uniform",
                    choices=["uniform", "auto"],
                    help="expert placement: uniform (one expert per slot, "
                         "the default) or auto (load-adaptive replication "
                         "of hot experts, rebalanced from the live load "
                         "EMA every --rebalance-every steps)")
    ap.add_argument("--rebalance-every", type=int, default=50,
                    help="steps between placement rebalance checks "
                         "(--placement auto; 0 disables rebalancing)")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--d-model", type=int, default=None)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint period in steps (default: steps/2 "
                         "when --ckpt is set)")
    ap.add_argument("--retain", type=int, default=3,
                    help="retained checkpoints under --guards (last k)")
    ap.add_argument("--guards", action="store_true",
                    help="fault-tolerant loop: non-finite skip-step + LR "
                         "backoff, loss-spike detection, checkpoint "
                         "rollback (needs --ckpt), fp8 overflow fallback")
    ap.add_argument("--max-skips", type=int, default=3,
                    help="consecutive skipped steps before rollback")
    ap.add_argument("--faults", default=None,
                    help="fault-injection spec, e.g. 'nan_grad@step=5-8;"
                         "fp8_sat@factor=64;ckpt_bitflip@save=2' "
                         "(see repro.runtime.faults; implies --guards)")
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--log-json", default=None)
    ap.add_argument("--metrics-dir", default=None,
                    help="stream run telemetry (train_step / guard / "
                         "autosched / fp8 events) as JSONL into this "
                         "directory; emitted file paths are mirrored "
                         "into --log-json")
    ap.add_argument("--trace", action="store_true",
                    help="after training, time the resolved MoE "
                         "schedule's plan stages and save a Chrome "
                         "trace JSON into --metrics-dir")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    if args.trace and not args.metrics_dir:
        ap.error("--trace requires --metrics-dir")
    enable_compile_cache()

    cfg = get_config(args.arch)
    if cfg.moe is not None and (args.pipeline_chunks is not None
                                or args.autosched or args.wire_dtype
                                or args.placement == "auto"):
        moe_kw = {}
        if args.pipeline_chunks is not None:
            moe_kw["pipeline_chunks"] = args.pipeline_chunks
        if args.autosched:
            moe_kw["autosched"] = args.autosched
        if args.wire_dtype:
            from repro.core.collectives import CommConfig
            moe_kw["comm"] = replace(cfg.moe.comm,
                                     wire_dtype=args.wire_dtype) \
                if cfg.moe.comm else CommConfig(wire_dtype=args.wire_dtype)
        if args.placement == "auto":
            # MoE layers read the live placement from the autosched
            # registry at trace time; the Trainer drives the rebalances
            moe_kw["placement"] = "auto"
        cfg = replace(cfg, moe=replace(cfg.moe, **moe_kw))
    if args.reduced:
        cfg = cfg.reduced(n_layers=args.layers or 2,
                          d_model=args.d_model or 256)
    elif args.layers or args.d_model:
        cfg = replace(cfg, n_layers=args.layers or cfg.n_layers,
                      d_model=args.d_model or cfg.d_model)

    n_dev = jax.device_count()
    if n_dev >= 256:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        dims = dims_for(cfg, args.multi_pod)
    else:
        mesh, dims = local_mesh(cfg)

    if args.metrics_dir:
        obs.configure(args.metrics_dir, meta={
            "kind": "train", "arch": args.arch, "steps": args.steps,
            "seq_len": args.seq, "batch": args.batch,
            "schedule": args.schedule, "n_devices": n_dev,
            "argv": sys.argv[1:]})

    model = build_model(cfg)
    opt = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                      total_steps=args.steps)
    guards = faults = None
    if args.faults:
        from repro.runtime import FaultPlan
        faults = FaultPlan.parse(args.faults, seed=args.fault_seed)
        print(f"fault plan: {faults.summary()}", flush=True)
    if args.guards or faults is not None:
        from repro.runtime import GuardConfig
        guards = GuardConfig(max_skips=args.max_skips)
    placement = args.placement if cfg.moe is not None else "uniform"
    tr = Trainer(model, mesh, dims, opt, schedule=args.schedule,
                 ckpt_path=args.ckpt, guards=guards, faults=faults,
                 ckpt_retain=args.retain,
                 placement="auto" if placement == "auto" else None,
                 rebalance_every=args.rebalance_every)
    params, opt_state = tr.setup(jax.random.PRNGKey(0))
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=args.seq,
                                  global_batch=args.batch))
    ckpt_every = args.ckpt_every or (args.steps // 2 if args.ckpt else 0)
    params, opt_state, hist = tr.run(params, opt_state, data, args.steps,
                                     ckpt_every=ckpt_every if args.ckpt
                                     else 0)

    trace_file = None
    if args.trace:
        if cfg.moe is None:
            print("--trace: dense arch has no MoE plan stages; skipping",
                  flush=True)
        else:
            from repro.obs.audit import trace_schedule
            from repro.obs.trace import save_chrome_trace
            sched = args.schedule
            if sched in (None, "auto") or sched.endswith("_seqpar"):
                sched = "s1"   # concrete, trace-compatible default
            st = trace_schedule(mesh, dims, cfg.moe,
                                args.batch * args.seq, sched,
                                n_chunks=args.pipeline_chunks or 1)
            trace_file = os.path.join(args.metrics_dir,
                                      f"trace_{sched}.json")
            save_chrome_trace(st, trace_file)
            obs.emit("stage_trace", schedule=sched, path=trace_file,
                     total_s=st.total_s, n_stages=st.n_stages)
            print(f"stage trace ({sched}, {st.n_stages} stages, "
                  f"{st.total_s * 1e3:.3f} ms) -> {trace_file}",
                  flush=True)

    metrics_files = None
    if args.metrics_dir:
        metrics_files = list(obs.get_sink().paths)
        obs.close()

    if args.log_json:
        os.makedirs(os.path.dirname(os.path.abspath(args.log_json)),
                    exist_ok=True)
        rec = hist if (guards is None and placement != "auto"
                       and not args.metrics_dir) else {"history": hist}
        if isinstance(rec, dict) and args.metrics_dir:
            rec["obs"] = {"metrics_dir": args.metrics_dir,
                          "metrics_files": metrics_files,
                          "trace_file": trace_file}
        if isinstance(rec, dict) and guards is not None:
            rec.update({"guards": dict(tr.guard_state.counters),
                        "guard_events": tr.guard_state.events,
                        "lr_scale": tr.guard_state.lr_scale})
        if isinstance(rec, dict) and placement == "auto":
            from repro.core import autosched
            pl = autosched.current_placement()
            rec["placement"] = {
                "mode": "auto",
                "rebalance_every": args.rebalance_every,
                "epoch": autosched.placement_epoch(),
                "current": pl.summary() if pl is not None else None,
                "load_ema": [round(float(v), 3)
                             for v in tr.load_ema.value()]}
        with open(args.log_json, "w") as f:
            json.dump(rec, f, indent=1)
    import math
    if guards is not None:
        gs = tr.guard_state
        # the chaos contract: an injected-fault run must still END finite
        assert math.isfinite(hist[-1]["loss"]), \
            f"guarded run ended non-finite: {hist[-1]['loss']}"
        if faults is not None and any(
                s.kind == "nan_grad" for s in faults.specs):
            assert gs.counters["skipped"] > 0, \
                "nan_grad fault injected but no step was skipped"
        print(f"CHAOS TRAIN OK  final loss {hist[-1]['loss']:.4f}  "
              f"({gs.counters['skipped']} skipped, "
              f"{gs.counters['rollbacks']} rollbacks)", flush=True)
    print(f"final loss {hist[-1]['loss']:.4f} "
          f"(start {hist[0]['loss']:.4f})")


if __name__ == "__main__":
    main()
