"""One run of a training cell: set-up, the measured window, the traced
window (``--trace 1``), and the check against the reference."""

from __future__ import annotations

import gc
import math
import sys
import time
from dataclasses import replace

import jax

from bench.harness import compare, program
from bench.harness import trace as tracelib
from bench.harness.spec import Cell, SpecError, family_part, generator

CHECK_STEPS = 3     # steps the reference follows
TIMING_STEPS = 2    # steps after them that size the window
TRACE_STEPS = 4     # steps of the traced window


def log(*a):
    print(*a, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts compilations (and compile-cache reads) while ``on``."""

    def __init__(self):
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **kw):
        if self.on and ("compile" in name or "compilation_cache" in name):
            self.n += 1


def gate_pools(cell: Cell, moe, mesh_shape: dict, dims, schedules) -> tuple:
    """(n_pools, cap): the token pools the program gates one step's
    batch in, and the per-expert capacity of each pool.

    A device's pool is its tokens at the MoE boundary: distinct over the
    mesh's batch axes (``ParallelDims.batch_axes``: with ESP = MP over
    ``model``, over ``data`` alone), and split over the MP axes too
    where the schedule's plan takes its input sequence-parallel
    (``program.gate_layout``).  Its capacity is GShard's k * f * S_pool
    / E rounded up to a multiple of max(8, n_mp)
    (``core/moe.shard_pool_capacity``), with k, f and E from the
    program's MoE config ``moe``.  A schedule whose gate sees its MP
    rank's slice of the pool (S1 pauses MP before the gate) gates N_MP
    pools of a 1/N_MP capacity each.  Every pool is a block of
    contiguous rows.  A step whose MoE layers gate in
    different layouts, or in one this does not state, is refused."""
    def size(axes):
        return math.prod(mesh_shape[a] for a in axes)
    n_mp = size(tuple(dims.mp))
    layouts = set()
    for s in schedules or ("",):
        kind, over_mp = program.gate_layout(s) if s else ("pool", False)
        shard = size(tuple(dims.batch_axes)
                     + (tuple(dims.mp) if over_mp else ()))
        if kind not in ("pool", "mp_shard"):
            raise SpecError(f"{cell.name}: schedule {s!r} gates a pool of "
                            f"kind {kind!r}, which the reference does not "
                            f"state")
        layouts.add((shard, n_mp if kind == "mp_shard" else 1))
    if len(layouts) != 1:
        raise SpecError(f"{cell.name}: the step's MoE layers gate in pools "
                        f"of different layouts ({sorted(schedules)} on "
                        f"{mesh_shape}); the reference states one")
    shard, split = layouts.pop()
    mix = cell.traffic
    if mix["global_batch"] % (shard * split):
        raise SpecError(f"{cell.name}: batch {mix['global_batch']} does not "
                        f"split into {shard * split} gate pools of whole "
                        f"rows")
    S = mix["global_batch"] * mix["seq_len"] // shard
    align = max(8, n_mp)
    c = math.ceil(moe.top_k * moe.capacity_factor * S / moe.n_experts)
    return shard * split, max(align, -(-c // align) * align) // split


def summarize(t: dict, hlo: str, kernels: dict, schedules: set):
    """Window, busy time and breakdown of a traced window, in place."""
    t["index"] = tracelib.hlo_index(hlo)
    t["collectives"] = tracelib.collective_ops(hlo)
    t["window"] = lo, hi = tracelib.window(t["host"], "bench.traced_window")
    busy = [tracelib.length(tracelib.busy(ops, lo, hi))
            for ops in t["devices"].values()]
    t["busy_s"] = sum(busy) / len(busy) / 1e9 if busy else 0.0
    t["window_s"] = (hi - lo) / 1e9
    first = sorted(t["devices"])[0] if t["devices"] else None
    t["breakdown"] = tracelib.breakdown(
        t["devices"].get(first, []), t["index"], set(kernels), schedules,
        lo, hi, t["host"])


class Program:
    """The program's trainer for one cell and seed, set up and compiled,
    with the traffic feed it pulls batches from."""

    def __init__(self, cell: Cell, seed: int, devices, compiled=None,
                 dtype: str = "float32"):
        from repro.launch.mesh import local_mesh
        from repro.core import autosched
        from repro.models import build_model
        from repro.train import Trainer

        self.cell = cell
        conf, mix = cell.config, cell.traffic
        cfg = self.cfg = program.program_config(conf, dtype)
        if dtype != "float32":
            # the control runs the program's XLA kernels: its Pallas
            # kernels do not compile in bfloat16 for the TPU (the grouped
            # expert kernel's one-row copies miss the bf16 tiling)
            xla = replace(cfg.kernel, backend="ref")
            cfg = self.cfg = replace(cfg, kernel=xla, moe=replace(
                cfg.moe, kernel=replace(cfg.moe.kernel, backend="ref")))
        self.mesh, self.dims = local_mesh(cfg, devices)
        self.model = build_model(cfg)
        if dtype != "float32":
            # the control: the float32 path's weights, rounded to the
            # types the program's own lower-precision path keeps them in
            init32 = build_model(program.program_config(conf)).init
            like = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
            self.model.init = lambda k: jax.tree.map(
                lambda a, t: a.astype(t.dtype), init32(k), like)
        self.opt_cfg = program.adamw_config(conf)
        self.tr = Trainer(self.model, self.mesh, self.dims, self.opt_cfg)
        self.key = program.seed_key(seed)
        self.params, self.opt_state = self.tr.setup(self.key)
        self.gen = generator(mix).make(mix, cfg.vocab_size, seed)
        self.feed = program.Feed(self.gen)
        if compiled is None:
            # autosched's decisions are the process's: keep only this
            # step's, which ``schedules`` reads
            autosched.clear_cache()
            batch0 = self.feed.sharded_batch(0, self.mesh,
                                             tuple(self.dims.batch_axes))
            compiled = self.tr.compile(self.params, self.opt_state, batch0)
        self.tr._step = self.compiled = compiled

    def steps(self, n: int, log_every: int = None):
        """``n`` steps through ``Trainer.run``, the timed entry, on the
        feed's next batches."""
        self.params, self.opt_state, hist = self.tr.run(
            self.params, self.opt_state, self.feed, n,
            log_every=log_every or n)
        self.feed.offset += n
        return hist

    def check_steps(self) -> dict:
        """The first CHECK_STEPS steps, with the readings the reference
        is compared on: each loss, the first clipped gradient as AdamW's
        first moment holds it after one step, and the parameters' change
        after the last."""
        h = self.steps(1, log_every=1)
        grad_norms = {k: v / (1 - self.opt_cfg.beta1) for k, v in
                      program.leaf_norms(self.opt_state["mu"]).items()}
        h += self.steps(CHECK_STEPS - 1, log_every=1)
        return {"loss": [r["loss"] for r in h], "grad_norms": grad_norms,
                "change_norms": program.change_norms(
                    self.params, self.model.init, self.key)}

    def schedules(self) -> set:
        """The schedules the step runs: the configured one, or those
        autosched picked for it."""
        from repro.core import autosched
        if self.cfg.moe.schedule != "auto":
            return {self.cfg.moe.schedule}
        return {d.schedule for d in autosched.cache_info().values()}

    def pools(self) -> tuple:
        """(n_pools, cap) the program gates each step's batch in."""
        return gate_pools(self.cell, self.cfg.moe, dict(self.mesh.shape),
                          self.dims, self.schedules())

    def free(self):
        self.params = self.opt_state = self.compiled = None
        self.tr._step = self.tr._step_fn = None
        gc.collect()


def reference(cell: Cell, key, gen, pools, device, fault="") -> dict:
    """The reference's CHECK_STEPS steps on the same seed and batches,
    gated in the program's ``pools``, by the configuration's family's
    ``bench/reference/<family>.py``."""
    ref = family_part("reference", cell.config)
    batches = [gen.batch(i) for i in range(CHECK_STEPS)]
    return ref.train_steps(key, cell.config["model"],
                           cell.config["optimizer"], batches, pools=pools,
                           fault=fault, device=device)


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float, out_dir: str) -> dict:
    prog = Program(cell, seed, devices)
    hlo = prog.compiled.as_text()
    kernels = program.kernels_in_hlo(hlo)
    prog_check = prog.check_steps()
    schedules = prog.schedules()
    pools = prog.pools()

    # size the window from steps of its own
    t0 = time.perf_counter()
    prog.steps(TIMING_STEPS)
    jax.block_until_ready((prog.params, prog.opt_state))
    step_s = (time.perf_counter() - t0) / TIMING_STEPS
    n_steps = max(2, round(seconds / step_s))
    setup_s = time.perf_counter() - t_start
    log(f"bench: autosched picked {sorted(schedules)}, gating {pools[0]} "
        f"pools of capacity {pools[1]}; kernels in the compiled step: "
        f"{sorted(kernels)}; window of {n_steps} steps (set-up step "
        f"{step_s:.4f} s)")

    counter = CompileCounter()
    counter.on = True
    t0 = time.perf_counter()
    prog.steps(n_steps)
    jax.block_until_ready((prog.params, prog.opt_state))
    window_s = time.perf_counter() - t0
    counter.on = False
    mix = cell.traffic
    tokens_per_s = n_steps * mix["global_batch"] * mix["seq_len"] / window_s
    log(f"bench: window {n_steps} steps in {window_s:.4f} s, compiles in "
        f"the window: {counter.n}")

    traced = None
    if trace:
        traced = tracelib.traced_window(prog, TRACE_STEPS, out_dir)
        summarize(traced, hlo, kernels, schedules)
    del hlo
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    prog.free()     # the program's state goes before the reference runs
    t0 = time.perf_counter()
    ref = reference(cell, prog.key, prog.gen, pools, devices[0])
    ref_s = time.perf_counter() - t0
    numbers = compare.gaps(prog_check, ref)
    correct, lines = compare.judge(numbers, cell.limits)
    correct &= counter.n == 0
    log(f"bench: reference {CHECK_STEPS} steps in {ref_s:.3f} s; program "
        f"losses {prog_check['loss']} reference {ref['loss']}; read "
        + " ".join(f"{k}={v!r}@{w}" for k, (v, w) in numbers.items()))
    return {"correct": correct, "compare": lines, "setup_s": setup_s,
            "tokens_per_s": tokens_per_s, "n_steps": n_steps,
            "window_s": window_s, "peak": peak, "kernels": kernels,
            "schedules": sorted(schedules), "pools": pools,
            "traced": traced,
            "compiles_in_window": counter.n}
