#!/usr/bin/env python3
"""Readings that set a cell's limits (``bench/limits/<cell>.json``).

    python bench/calibrate.py --workload <cell> --seeds 1 2 3 ... --out <file>

In one process on the chips the cell asks for, for each seed: the
program's first steps through ``Trainer.run`` against the plain
reference (the lower readings).  On the first three seeds also the
controls and a fault (the upper readings): the program's own bfloat16
path (``ModelConfig.dtype``), the reference in bfloat16 put in the
program's place, and the reference with half of the batch left out.  A
step that returns its state unchanged reads 1 on ``update_gap`` by
construction and needs no run.  The benchmark's own runs never run
this.  Prints one JSON line per seed and reading, and writes them all
to ``--out``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))
CONTROL_SEEDS = 3   # the first seeds also read the controls and the fault


def program_readings(cell, seeds, devices, dtype):
    """{seed: check-step readings} of the program, one compile for all."""
    from bench.harness import train
    out, compiled = {}, None
    for seed in seeds:
        t0 = time.perf_counter()
        prog = train.Program(cell, seed, devices, compiled=compiled,
                             dtype=dtype)
        compiled = prog.compiled
        out[seed] = prog.check_steps()
        out[seed]["schedules"] = sorted(prog.schedules())
        out[seed]["mesh"] = dict(prog.mesh.shape)
        prog.tr._step = None          # keep ``compiled`` for the next seed
        prog.params = prog.opt_state = None
        print(f"program {dtype} seed {seed}: losses {out[seed]['loss']} "
              f"({time.perf_counter() - t0:.1f} s)", file=sys.stderr,
              flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    from bench.harness import compare, program, spec, train
    from bench.harness.device import chips
    cell = spec.load_cell(args.workload)
    devices, _ = chips(cell.chips)
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))
    seeds, few = args.seeds, args.seeds[:CONTROL_SEEDS]
    readings = {s: {"program": r} for s, r in program_readings(
        cell, seeds, devices, "float32").items()}
    try:
        for s, r in program_readings(cell, few, devices,
                                     "bfloat16").items():
            readings[s]["control_program_bf16"] = r
    except Exception:       # a control that crashes has failed: no number
        print("control_program_bf16 failed:\n" + traceback.format_exc(),
              file=sys.stderr, flush=True)
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        got = readings[seed]
        key = program.seed_key(seed)
        gen = spec.generator(cell.traffic).make(
            cell.traffic, cell.config["model"]["vocab_size"], seed)
        shape = got["program"]["mesh"]
        ref = train.reference(cell, key, gen, shape, devices[0])
        if seed in few:
            got["control_ref_bf16"] = train.reference(
                cell, key, gen, shape, devices[0], dtype="bfloat16")
            got["fault_half_batch"] = train.reference(
                cell, key, gen, shape, devices[0], fault="half_batch")
        for name, v in got.items():
            row = {"seed": seed, "reading": name,
                   "schedules": got["program"]["schedules"],
                   "loss": v["loss"], "ref_loss": ref["loss"]}
            row.update({k: {"value": val, "where": where} for k, (val, where)
                        in compare.gaps(v, ref).items()})
            rows.append(row)
            print(json.dumps(row), flush=True)
        print(f"reference seed {seed}: {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
