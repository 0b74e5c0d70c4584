#!/usr/bin/env python3
"""Bring-up run of gpt2-moe on a TPU at its full published width.

    python chip_smoke.py              # one chip: train, then serve
    python chip_smoke.py --chips 4    # four chips: the expert-parallel
                                      # schedules, and nothing else

One chip.  The normal trainer (``Trainer``, MoE schedule ``auto``,
kernel backend ``auto``, which is the Pallas kernels on a TPU) takes
``--steps`` optimizer steps of gpt2-moe (12 layers, d_model 768, 8
experts top-2, vocab 50257; weights random from ``--seed``) at
``--seq`` x ``--batch``.  It prints the autosched picks, the backend
each kernel op resolved to and whether the compiled step holds that
kernel, compile time apart from steady step time, the loss of every
step and the device's peak memory.  It fails on a non-finite loss and
on a step-0 loss that differs from the same loss through the ``ref``
(XLA) ops, same weights and batch, by more than ``LOSS_TOL``.  Then the
serving ``Engine`` answers four greedy requests (prompts of 64 to 512
tokens, 32 new tokens each) with the trained weights.

Four chips.  The same trainer on the 2x2 (data, model) mesh: EP=2 over
``data``, ESP=MP=2 over ``model``.  Each of s1, s2, s2h, s1g and auto
takes a few steps from the same seed.  The step-0 losses must agree
across schedules within ``SCHEDULE_RTOL``, every device must hold E/2
experts of every expert weight, and every loss must be finite.  The
schedules are the same math only while no token is dropped (s1 gates
each MP rank's slice of the pool, s2 the whole pool, so their drop sets
differ), so this phase runs at capacity factor E/top_k: every expert's
capacity then covers every token of the pool it gates, and no token
can drop.

Every phase runs in this one process: a chip belongs to one process.
The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; without a
TPU, or when any check fails, the script exits non-zero before it.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# |step-0 loss(pallas) - step-0 loss(ref)|: both runs share every XLA op
# (embeddings, projections, layernorm, logits); they differ only inside
# the kernels (attention, expert FFN), whose f32 matmuls Mosaic and XLA
# round differently (XLA's default precision takes f32 operands through
# bf16 passes).  On a TPU v5e at full width the gap is 6.3e-5 on a loss
# of 11.13; the bound leaves 30x of room and is still 2e-4 of the loss.
LOSS_TOL = 2e-3
# relative spread of the step-0 loss across the Parm schedules: the same
# math with different collective and reduction orders
SCHEDULE_RTOL = 1e-4
FOUR_CHIP_SCHEDULES = ("s1", "s2", "s2h", "s1g", "auto")
# device kinds (lower case) whose peak rates autosched's analytic model
# (``perfmodel.tpu_v5e_model``) holds: it picks the schedules here
PERF_MODEL_KINDS = ("v5 lite", "v5e")
SERVE_PROMPTS = (64, 200, 384, 512)


class SmokeError(AssertionError):
    pass


def check(ok, msg):
    if not ok:
        raise SmokeError(msg)


def kernels_in_hlo(hlo: str) -> set:
    """Registry op names of the Pallas kernels in a compiled HLO text
    (each ``pallas_call`` is named after its op)."""
    return {m.group(1) for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in re.finditer(r"/(\w+)/pallas_call", line)}


def expert_shards_ok(params, n_experts, n_ep, devices):
    """Every expert weight (..., E, a, b) is split E/n_ep per device,
    and its shards sit on every device of the mesh."""
    import jax
    want = {d.id for d in devices}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        key = jax.tree_util.keystr(path)
        if "['moe']" not in key or not key.endswith(("['w1']", "['w2']",
                                                       "['w3']")):
            continue
        shards = leaf.addressable_shards
        check({s.device.id for s in shards} == want,
              f"{key}: shards on devices "
              f"{sorted(s.device.id for s in shards)}, want {sorted(want)}")
        for s in shards:
            check(s.data.shape[-3] == n_experts // n_ep,
                  f"{key}: device {s.device.id} holds "
                  f"{s.data.shape[-3]} experts, want {n_experts // n_ep}")


def train_phase(cfg, mesh, dims, *, seq, batch, steps, seed,
                schedule=None, ref_check=True, log=print):
    """Train ``steps`` steps through ``Trainer``; returns (model, final
    params, per-step losses).  Raises SmokeError on a failed check."""
    import jax
    from dataclasses import replace

    from repro.data import DataConfig, SyntheticLM
    from repro.kernels import registry
    from repro.models import build_model
    from repro.optim import AdamWConfig
    from repro.train import Trainer

    model = build_model(cfg)
    opt = AdamWConfig(lr=1e-3, warmup_steps=max(steps // 20, 5),
                      total_steps=steps)
    tr = Trainer(model, mesh, dims, opt, schedule=schedule)
    params, opt_state = tr.setup(jax.random.PRNGKey(seed))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"model {cfg.name}: {n_params / 1e6:.1f} M params, "
        f"seq {seq} x batch {batch}, schedule {schedule or 'config'}")
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                  global_batch=batch, seed=seed))
    batch0 = data.sharded_batch(0, mesh, tuple(dims.batch_axes))

    t0 = time.perf_counter()
    with registry.record_resolved() as ops:
        compiled = tr.compile(params, opt_state, batch0)
    compile_s = time.perf_counter() - t0
    ops = dict(sorted(ops.items()))
    # off the TPU (the tests' CPU rehearsal) kernels are interpreted and
    # leave no custom call to find
    on_tpu = jax.default_backend() == "tpu"
    in_hlo = kernels_in_hlo(compiled.as_text())
    log("kernel ops (op: backend, tpu_custom_call in compiled step):")
    for op, backend in ops.items():
        mark = "-" if backend != "pallas" else (
            ("yes" if op in in_hlo else "NO") if on_tpu else "interpret")
        log(f"  {op:20s} {backend:6s} {mark}")
    missing = [op for op, b in ops.items()
               if b == "pallas" and op not in in_hlo]
    check(not (on_tpu and missing),
          f"pallas ops missing from the compiled step: {missing}")
    log(f"compile: {compile_s:.3f} s")

    ref_loss = None
    if ref_check:
        ref_cfg = replace(cfg, kernel=replace(cfg.kernel, backend="ref"))
        ref_model = build_model(ref_cfg)
        ref_loss = float(jax.jit(lambda p, b: ref_model.loss(
            p, b, mesh=mesh, dims=dims, schedule=schedule)[0])(
                params, batch0))

    t0 = time.perf_counter()
    params, opt_state, hist = tr.run(params, opt_state, data, steps,
                                     log_every=1)
    jax.block_until_ready((params, opt_state))
    run_s = time.perf_counter() - t0
    losses = [h["loss"] for h in hist]
    ends = [h["wall_s"] for h in hist]
    step_s = [b - a for a, b in zip(ends, ends[1:])]
    steady = sorted(step_s)[len(step_s) // 2] if step_s else float("nan")
    log(f"first step: {ends[0]:.4f} s; steady step (median of steps "
        f"1..{steps - 1}): {steady:.4f} s; {steps} steps: {run_s:.3f} s")
    log("losses: " + " ".join(f"{v:.6f}" for v in losses))
    check(all(math.isfinite(v) for v in losses),
          f"non-finite loss: {losses}")
    if ref_check:
        diff = abs(losses[0] - ref_loss)
        log(f"step-0 loss: pallas {losses[0]:.6f}  ref {ref_loss:.6f}  "
            f"|diff| {diff:.3e} (tol {LOSS_TOL:.0e})")
        check(diff <= LOSS_TOL, f"step-0 loss pallas {losses[0]} vs ref "
              f"{ref_loss}: |diff| {diff} > {LOSS_TOL}")
    return model, params, losses


def serve_phase(model, params, mesh, dims, *, prompts=SERVE_PROMPTS,
                gen=32, max_len=576, seed=0, log=print):
    """Greedy requests through the serving Engine; every one must finish
    ``ok`` with ``gen`` tokens."""
    import numpy as np

    from repro.serve import Engine, SamplerConfig, latency_stats

    check(max(prompts) + gen <= max_len <= 1024,
          f"prompts {prompts} + {gen} do not fit max_len {max_len}")
    engine = Engine(model, mesh, dims, max_batch=len(prompts),
                    max_len=max_len)
    rng = np.random.RandomState(seed)
    for n in prompts:
        engine.submit(rng.randint(0, model.cfg.vocab_size, n), gen,
                      sampler=SamplerConfig())
    t0 = time.perf_counter()
    done = engine.run(params)
    wall = time.perf_counter() - t0
    st = latency_stats(done)
    log(f"serve: {len(done)} requests, prompts {list(prompts)}, {gen} new "
        f"tokens each, {wall:.3f} s wall (compiles included); "
        f"ttft p50 {st['ttft_p50_ms']:.1f} ms")
    for c in sorted(done, key=lambda c: c.rid):
        log(f"  request {c.rid}: {c.status} {len(c.tokens)} tokens "
            f"{list(c.tokens[:8])}...")
    check(len(done) == len(prompts), f"{len(done)} of {len(prompts)} "
          f"requests came back")
    check(all(c.status == "ok" and len(c.tokens) == gen for c in done),
          "a request did not finish ok with all its tokens: "
          + str([(c.rid, c.status, len(c.tokens)) for c in done]))


def four_chip_phase(cfg, devices, *, seq, batch, steps, seed,
                    schedules=FOUR_CHIP_SCHEDULES, log=print):
    """Each schedule trains from the same seed on the (data, model) mesh
    of ``devices``; step-0 losses agree, expert shards are E/n_ep per
    device, every loss is finite."""
    from dataclasses import replace

    from repro.launch.mesh import local_mesh

    moe = cfg.moe
    cfg = replace(cfg, moe=replace(
        moe, capacity_factor=moe.n_experts / moe.top_k))
    mesh, dims = local_mesh(cfg, devices)
    n_ep = dims.sizes(mesh)["ep"]
    log(f"mesh {dict(mesh.shape)}: EP={n_ep} over data, "
        f"ESP=MP={dims.sizes(mesh)['mp']} over model")
    step0 = {}
    for sched in schedules:
        _, params, losses = train_phase(
            cfg, mesh, dims, seq=seq, batch=batch, steps=steps, seed=seed,
            schedule=sched, ref_check=False, log=log)
        expert_shards_ok(params, cfg.moe.n_experts, n_ep, mesh.devices.flat)
        log(f"{sched}: expert weights hold {cfg.moe.n_experts // n_ep} "
            f"experts on each of {mesh.devices.size} devices")
        step0[sched] = losses[0]
    base = step0[schedules[0]]
    spread = max(abs(v - base) for v in step0.values()) / abs(base)
    log("step-0 loss per schedule: " + "  ".join(
        f"{s} {v:.6f}" for s, v in step0.items())
        + f"  (max rel spread {spread:.2e}, tol {SCHEDULE_RTOL:.0e})")
    check(spread <= SCHEDULE_RTOL, f"step-0 losses disagree across "
          f"schedules: {step0}")
    return step0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--steps", type=int, default=None,
                    help="optimizer steps (default: 5 on one chip, "
                         "3 per schedule on four)")
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); this run needs the chip",
              file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    if not any(k in kind.lower() for k in PERF_MODEL_KINDS):
        print(f"chip_smoke: device {kind!r}: autosched's analytic model "
              f"holds the peak rates of {PERF_MODEL_KINDS} only",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 2
    devices = devices[:args.chips]

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import local_mesh

    log = print
    log(f"device: {devices[0].device_kind} x {len(devices)}; "
        f"compile cache {enable_compile_cache()}")
    cfg = get_config("gpt2-moe")
    try:
        if args.chips == 4:
            four_chip_phase(cfg, devices, seq=args.seq, batch=args.batch,
                            steps=args.steps or 3, seed=args.seed)
        else:
            mesh, dims = local_mesh(cfg, devices)
            model, params, losses = train_phase(
                cfg, mesh, dims, seq=args.seq, batch=args.batch,
                steps=args.steps or 5, seed=args.seed)
            check(len(losses) >= 5, "fewer than 5 training steps")
            log(f"peak_bytes_in_use after training: "
                f"{devices[0].memory_stats()['peak_bytes_in_use']}")
            serve_phase(model, params, mesh, dims, seed=args.seed)
            log(f"peak_bytes_in_use after serving: "
                f"{devices[0].memory_stats()['peak_bytes_in_use']}")
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
