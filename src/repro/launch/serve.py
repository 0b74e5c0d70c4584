"""Serving launcher: continuous-batching engine over synthetic traffic.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
      --requests 16 --arrival-rate 8 --max-batch 8 --gen 32 --schedule auto

Thin CLI over ``repro.serve.Engine``: synthesizes ``--requests`` random
prompts (lengths uniform in [4, --prompt-len]), optionally spreads their
arrivals at ``--arrival-rate`` req/s, serves them with continuous
batching + decode-dedicated MoE schedules, and prints throughput and
latency percentiles.  ``--smoke`` caps everything for CI and exits 0 on
a clean run.
"""

from __future__ import annotations

import argparse
import sys

import jax
import numpy as np

from repro import obs
from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import local_mesh
from repro.models import build_model
from repro.serve import Engine, SamplerConfig, latency_stats


def build_engine(args, cfg, model):
    mesh, dims = local_mesh(cfg)
    schedule = None if args.schedule in (None, "auto") else args.schedule
    max_batch = args.max_batch
    if max_batch <= 0:               # perf-model bucket sizing (t_decode)
        from repro.serve import suggest_max_batch
        sizes = dims.sizes(mesh)
        # mean live context per row: half the prompt spread + the budget
        mean_len = min((4 + args.prompt_len) / 2 + args.gen, args.max_len)
        max_batch = suggest_max_batch(
            cfg, n_ep=sizes["ep"], n_esp=sizes["esp"], n_mp=sizes["mp"],
            candidates=(1, 2, 4, 8, 16, 32),
            n_blocks=args.n_blocks or None, block_size=args.block_size,
            mean_len=mean_len)
        print(f"auto max-batch (t_decode, block budget): {max_batch}")
    faults = None
    if getattr(args, "faults", None):
        from repro.runtime import FaultPlan
        faults = FaultPlan.parse(args.faults, seed=args.fault_seed)
        print(f"fault plan: {faults.summary()}", flush=True)
    placement = getattr(args, "placement", "uniform")
    if placement == "auto" and cfg.moe is None:
        placement = "uniform"
    return Engine(model, mesh, dims, max_batch=max_batch,
                  max_len=args.max_len, schedule=schedule,
                  prefill_batch=args.prefill_batch,
                  block_size=args.block_size,
                  n_blocks=args.n_blocks or None,
                  prefix_cache=args.prefix_cache,
                  prefill_chunk=args.prefill_chunk,
                  queue_slo=getattr(args, "queue_slo", 0.0),
                  watchdog_rounds=getattr(args, "watchdog_rounds", 0),
                  faults=faults,
                  placement="auto" if placement == "auto" else None,
                  rebalance_every=getattr(args, "rebalance_every", 0)), \
        mesh, dims


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="requests/s (0 = all arrive at t=0)")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="decode batch / KV slots (0 = auto via t_decode)")
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="max synthetic prompt length")
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--prefill-batch", type=int, default=1)
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV page size in tokens (must divide --max-len)")
    ap.add_argument("--n-blocks", type=int, default=0,
                    help="KV arena pages (0 = slab-equivalent "
                         "max_batch * max_len / block_size)")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="shared-prefix reuse (--no-prefix-cache disables)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill size in tokens (0 = one-shot); "
                         "chunks alternate with decode rounds")
    ap.add_argument("--schedule", default=None,
                    help="force one MoE schedule (default: auto decisions)")
    ap.add_argument("--placement", default="uniform",
                    choices=["uniform", "auto"],
                    help="expert placement: uniform (default) or auto "
                         "(load-adaptive replication from the decode load "
                         "EMA, rebalanced every --rebalance-every rounds)")
    ap.add_argument("--rebalance-every", type=int, default=64,
                    help="decode rounds between placement rebalance "
                         "checks (--placement auto; 0 disables)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="per-request wall-clock deadline in seconds "
                         "(0 = none); blown deadlines cancel mid-flight "
                         "and free their KV pages")
    ap.add_argument("--queue-slo", type=float, default=0.0,
                    help="max seconds a request may wait in queue for "
                         "blocks before being shed (0 = backpressure only)")
    ap.add_argument("--watchdog-rounds", type=int, default=0,
                    help="evict a decode row after this many rounds "
                         "without progress (0 = off)")
    ap.add_argument("--faults", default=None,
                    help="fault-injection spec, e.g. 'req_timeout@rid=1,"
                         "ticks=4;req_delay@rid=2,rounds=99;alloc_starve@"
                         "tick=1,hold=999,rounds=8' (repro.runtime.faults)")
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--log-json", default=None,
                    help="write latency + robustness stats to this file")
    ap.add_argument("--metrics-dir", default=None,
                    help="stream request-lifecycle telemetry (queued/"
                         "admitted/prefilled/finished, decode rounds, "
                         "rollups) as JSONL into this directory; file "
                         "paths are mirrored into --log-json")
    ap.add_argument("--trace", action="store_true",
                    help="after serving, time the decode MoE schedule's "
                         "plan stages and save a Chrome trace JSON into "
                         "--metrics-dir")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: tiny run, assert clean completion")
    args = ap.parse_args()
    if args.requests < 1:
        ap.error("--requests must be >= 1")
    if args.trace and not args.metrics_dir:
        ap.error("--trace requires --metrics-dir")
    enable_compile_cache()
    if args.smoke:
        args.requests = min(args.requests, 8)
        args.gen = min(args.gen, 8)
        args.max_len = min(args.max_len, 64)
        args.prompt_len = min(args.prompt_len, 12)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.placement == "auto" and cfg.moe is not None:
        from dataclasses import replace as _replace
        # MoE layers read the live placement from the autosched registry
        # at trace time; the engine drives the rebalances
        cfg = _replace(cfg, moe=_replace(cfg.moe, placement="auto"))
    if args.metrics_dir:
        obs.configure(args.metrics_dir, meta={
            "kind": "serve", "arch": args.arch,
            "requests": args.requests, "max_batch": args.max_batch,
            "gen": args.gen, "schedule": args.schedule,
            "n_devices": jax.device_count(), "argv": sys.argv[1:]})
    model = build_model(cfg)
    engine, mesh, dims = build_engine(args, cfg, model)
    params = model.init(jax.random.PRNGKey(0))

    rng = np.random.RandomState(args.seed)
    sampler = SamplerConfig(temperature=args.temperature,
                            top_k=args.top_k, seed=args.seed)
    for i in range(args.requests):
        plen = int(rng.randint(4, max(args.prompt_len, 5)))
        engine.submit(rng.randint(0, cfg.vocab_size, plen), args.gen,
                      sampler=sampler,
                      arrival=(i / args.arrival_rate
                               if args.arrival_rate > 0 else 0.0),
                      deadline=args.deadline)
    done = engine.run(params, progress=not args.smoke)

    stats = latency_stats(done)
    s = engine.stats
    print(f"served {stats['n_requests']} requests / "
          f"{stats['n_tokens']} tokens: {stats['tok_per_s']:.1f} tok/s  "
          f"p50 {stats['p50_ms']:.0f}ms  p95 {stats['p95_ms']:.0f}ms  "
          f"p99 {stats['p99_ms']:.0f}ms  "
          f"ttft_p50 {stats['ttft_p50_ms']:.0f}ms")
    print(f"engine: {s['prefill_calls']} prefill calls "
          f"({s['prefill_tokens']} tokens), {s['decode_calls']} decode "
          f"rounds ({s['decode_tokens']} tokens), max_active "
          f"{s['max_active']}/{engine.max_batch}")
    print(f"paged kv: {s['prefix_hits']} prefix hits "
          f"({s['prefix_tokens']} tokens reused), peak pages "
          f"{s['peak_blocks']}/{engine.pool.n_blocks} "
          f"(block size {engine.block_size})")
    if s["shed"] or s["expired"] or s["evicted"] or args.faults \
            or args.deadline or args.queue_slo or args.watchdog_rounds:
        print(f"robustness: {s['shed']} shed "
              f"({s['shed_blocks']} blocks, {s['shed_queue']} queue SLO), "
              f"{s['expired']} expired, {s['evicted']} evicted")
    from repro.core import autosched
    summary = autosched.cache_summary()
    if summary:
        print(summary)

    trace_file = None
    if args.trace:
        if cfg.moe is None:
            print("--trace: dense arch has no MoE plan stages; skipping",
                  flush=True)
        else:
            import os as _os
            from repro.obs.audit import trace_schedule
            from repro.obs.trace import save_chrome_trace
            sched = args.schedule
            if sched in (None, "auto") or sched.endswith("_seqpar"):
                sched = "s1d"   # the decode-dedicated plan
            try:
                st = trace_schedule(mesh, dims, cfg.moe,
                                    engine.max_batch, sched, infer=True)
            except Exception as e:
                # tiny CPU decode pools can be untraceable; on the chip a
                # failed trace is a failed run
                if jax.default_backend() == "tpu":
                    raise
                print(f"--trace: {type(e).__name__}: {e}; skipping",
                      flush=True)
            else:
                trace_file = _os.path.join(args.metrics_dir,
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
        import json as _json
        import os as _os
        _os.makedirs(_os.path.dirname(_os.path.abspath(args.log_json)),
                     exist_ok=True)
        rec = {"latency": stats, "engine": s,
               "statuses": {c.rid: c.status for c in done}}
        if args.metrics_dir:
            rec["obs"] = {"metrics_dir": args.metrics_dir,
                          "metrics_files": metrics_files,
                          "trace_file": trace_file}
        if args.placement == "auto":
            pl = autosched.current_placement()
            rec["placement"] = {
                "mode": "auto",
                "rebalance_every": args.rebalance_every,
                "epoch": autosched.placement_epoch(),
                "current": pl.summary() if pl is not None else None,
                "per_expert_load": s.get("per_expert_load")}
        with open(args.log_json, "w") as f:
            _json.dump(rec, f, indent=1)
    ok = [c for c in done if c.status == "ok"]
    if ok:
        print("sample:", ok[0].tokens[:16])
    if args.smoke:
        # every submitted request must come back — finished, shed,
        # expired, or evicted; nothing may hang or vanish
        assert len(done) == args.requests, "smoke: not all requests done"
        assert all(len(c.tokens) > 0 for c in ok)
        if args.faults:
            assert ok, "chaos smoke: every request was cancelled"
            print("SERVE CHAOS OK")
        print("SERVE SMOKE OK")


if __name__ == "__main__":
    main()
