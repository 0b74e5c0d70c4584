#!/usr/bin/env python3
"""Record a small trace of the program's train step for the tests of the
trace reduction (``bench/traces``): a cell's configuration cut to 2
layers, at seq 256 x batch ``--batch`` (1; a four-chip cell's batch has to
split into its gate pools: 4), on the chips the cell asks for;
``--steps`` steps of ``Trainer.run`` traced after two untraced ones.

    python bench/record_trace.py --out bench/traces/<name>.json.gz \
        [--workload <cell>] [--batch 1]

Writes what ``bench.harness.trace.traced_window`` reads (each TPU's
``XLA Ops`` line, and the host's spans that meet the traced window),
with the scope path of each instruction on the device (``index``), which
of them are collectives (``collectives``), the step's Pallas calls
(``kernels``), the schedules autosched picked and the number of traced
steps.  Off a TPU the file holds no device ops.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import sys
from dataclasses import replace

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))
N_LAYERS, SEQ = 2, 256


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--workload", default="gpt2-moe.train-s1024")
    ap.add_argument("--batch", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    from bench.harness import program, spec, train
    from bench.harness import trace as T

    cell = spec.load_cell(args.workload)
    cell.config["model"]["n_layers"] = N_LAYERS
    cell = replace(cell, traffic=dict(cell.traffic, seq_len=SEQ,
                                      global_batch=args.batch))
    devices = jax.devices()[:cell.chips]
    prog = train.Program(cell, args.seed, devices)
    hlo = prog.compiled.as_text()
    prog.steps(2)
    jax.block_until_ready((prog.params, prog.opt_state))
    ev = T.traced_window(prog, args.steps, os.path.join(ROOT, ".bench_out"))
    lo, hi = T.window(ev["host"], "bench.traced_window")
    index = T.hlo_index(hlo)
    on_device = {o[0] for ops in ev["devices"].values() for o in ops}
    d0 = devices[0]
    rec = {"about": f"{args.steps} traced train steps of "
                    f"{cell.config['name']} cut to {N_LAYERS} layers "
                    f"(seq {SEQ} x batch {args.batch}) on "
                    f"{len(devices)} {d0.device_kind}, reduced by "
                    f"bench.harness.trace.traced_window; index: "
                    f"instruction -> scope path from the compiled HLO",
           "devices": ev["devices"],
           "host": [h for h in ev["host"] if h[1] < hi and h[1] + h[2] > lo],
           "index": {n: index[n] for n in sorted(on_device) if n in index},
           "collectives": sorted(T.collective_ops(hlo) & on_device),
           "kernels": program.kernels_in_hlo(hlo),
           "schedules": sorted(prog.schedules()),
           "n_steps": args.steps}
    with gzip.open(args.out, "wt") as f:
        json.dump(rec, f, separators=(",", ":"))
    print(f"record_trace: {sum(len(o) for o in ev['devices'].values())} "
          f"device ops, {len(rec['host'])} host spans -> {args.out}",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
