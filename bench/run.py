#!/usr/bin/env python3
"""The chip benchmark: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``).  The run builds the program's trainer
on the chips the cell asks for, makes the weights from ``--seed`` on the
device, compiles the step (JAX's persistent cache at ``.jax_cache`` in
this checkout), takes the first steps through the timed entry
(``Trainer.run``) for the correctness check, measures ``--seconds`` of
steps, and then compares those first steps with the plain reference
(``bench/reference``).  With ``--trace 1`` it also traces a few steps
and reports the per-layer metrics (``bench/metrics/<metric>.py``).

The last line of stdout is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and with
``--trace 1`` ``breakdown``).  Without a TPU, with a device kind that
``bench/peaks.json`` does not hold, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
OUT_DIR = os.path.join(ROOT, ".bench_out")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import spec
    try:
        cell = spec.load_cell(args.workload)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    import jax
    from bench.harness.device import DeviceError, chips
    try:
        devices, peaks = chips(cell.chips)
    except DeviceError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from bench.harness import report, train
    res = train.run(cell, args.seed, args.seconds, bool(args.trace),
                    devices, T_START, OUT_DIR)
    line = report.result_line(cell, res, peaks, devices, bool(args.trace))
    report.print_compare(res["compare"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
