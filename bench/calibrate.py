#!/usr/bin/env python3
"""Readings that set a cell's limits (``bench/limits/<cell>.json``).

    python bench/calibrate.py --workload <cell> --seeds 1 2 3 ... --out <file>

In one process on the chips the cell asks for, for each seed: the
program's first steps through ``Trainer.run`` against the plain
reference (the lower readings).  On the first three seeds also the
control and the faults (the upper readings): the program's own bfloat16
path (``ModelConfig.dtype``), the reference with half of the batch left
out put in the program's place, and, on a cell of more than one chip,
the program with each exchange between chips left out: the MoE layer's
all-to-alls and the dense FFN's MP all-reduce (``bench/faults.py``).  A step that returns its state unchanged reads 1
on ``update_gap`` by construction and needs no run.  The benchmark's
own runs never run this.  Prints one JSON line per seed and reading,
and writes them all to ``--out``.

The two sides can run apart, so that a cell of several chips holds
them only for the program's side (the reference runs on one chip):

    python bench/calibrate.py --workload <cell> --seeds ... --part program \
        --out <readings.json>
    python bench/calibrate.py --workload <cell> --part reference \
        --readings <readings.json> --out <file>
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


def program_readings(cell, seeds, devices, dtype, fault=""):
    """{seed: check-step readings} of the program, one compile for all."""
    from bench import faults
    from bench.harness import train
    out, compiled = {}, None
    for seed in seeds:
        t0 = time.perf_counter()
        with faults.planted(fault):
            prog = train.Program(cell, seed, devices, compiled=compiled,
                                 dtype=dtype)
        compiled = prog.compiled
        out[seed] = prog.check_steps()
        out[seed]["schedules"] = sorted(prog.schedules())
        out[seed]["pools"] = prog.pools()
        prog.tr._step = None          # keep ``compiled`` for the next seed
        prog.params = prog.opt_state = None
        print(f"program {dtype} {fault or 'sound'} seed {seed}: losses "
              f"{out[seed]['loss']} ({time.perf_counter() - t0:.1f} s)",
              file=sys.stderr, flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+")
    ap.add_argument("--out", required=True)
    ap.add_argument("--part", choices=("both", "program", "reference"),
                    default="both")
    ap.add_argument("--readings", help="the program side's --out, for "
                    "--part reference")
    args = ap.parse_args(argv)

    import jax
    from bench.harness import compare, program, spec, train
    from bench.harness.device import chips
    cell = spec.load_cell(args.workload)
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if args.part == "reference":
        devices, _ = chips(1)
        with open(args.readings) as f:
            readings = {int(s): r for s, r in json.load(f).items()}
        seeds = list(readings)
    else:
        devices, _ = chips(cell.chips)
        seeds = args.seeds
        readings = {s: {"program": r} for s, r in program_readings(
            cell, seeds, devices, "float32").items()}
        uppers = [("control_program_bf16", "bfloat16", "")]
        if cell.chips > 1:
            uppers += [("fault_no_exchange", "float32", "no_exchange"),
                       ("fault_no_mp_exchange", "float32", "no_mp_exchange")]
        for name, dtype, fault in uppers:
            try:
                for s, r in program_readings(cell, seeds[:CONTROL_SEEDS],
                                             devices, dtype, fault).items():
                    readings[s][name] = r
            except Exception:   # a control that crashes has failed
                print(f"{name} failed:\n" + traceback.format_exc(),
                      file=sys.stderr, flush=True)
        if args.part == "program":
            _write(args.out, [readings])
            return 0
    few = seeds[:CONTROL_SEEDS]
    vocab = program.program_config(cell.config).vocab_size
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        got = readings[seed]
        key = program.seed_key(seed)
        gen = spec.generator(cell.traffic).make(cell.traffic, vocab, seed)
        pools = got["program"]["pools"]
        ref = train.reference(cell, key, gen, pools, devices[0])
        if seed in few:
            got["fault_half_batch"] = train.reference(
                cell, key, gen, pools, devices[0], fault="half_batch")
        for name, v in got.items():
            row = {"seed": seed, "reading": name,
                   "schedules": got["program"]["schedules"],
                   "pools": list(pools),
                   "loss": v["loss"], "ref_loss": ref["loss"]}
            row.update({k: {"value": val, "where": where} for k, (val, where)
                        in compare.gaps(v, ref).items()})
            rows.append(row)
            print(json.dumps(row), flush=True)
        print(f"reference seed {seed}: {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)
    _write(args.out, rows)
    return 0


def _write(path, rows):
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    sys.exit(main())
