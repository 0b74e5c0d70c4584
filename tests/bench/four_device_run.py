"""The benchmark's training path on four devices (the 2x2 mesh of a
four-chip cell) at a small size, past the look for a chip: one
``train.run`` with its check against the reference, gated in the pools
that ``train.gate_pools`` states.

    python tests/bench/four_device_run.py <case> [<cell>]

with four devices (``XLA_FLAGS=--xla_force_host_platform_device_count=4``).
Cases: ``auto``, ``s2h`` and ``s1_seqpar`` (sound runs: the schedule
autosched picks, which gates each MP rank's slice of the pool, and two
forced, which gate the whole pool and the MP-split pool; judged by
tight limits), ``control`` (the
program's bfloat16 path) and the faults of ``bench/faults.py`` (judged
by ``<cell>``'s limits).  Prints one JSON line: ``correct``, the
numbers compared, the schedules and the pools.
"""

import functools
import json
import os
import sys
from dataclasses import replace

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_helpers import tiny_cell  # noqa: E402  (root and src on the path)

import jax  # noqa: E402

from bench import faults  # noqa: E402
from bench.harness import program, spec, train  # noqa: E402

SOUND = ("auto", "s2h", "s1_seqpar")


def main(case: str, cell_name: str) -> int:
    devices = jax.devices()
    if len(devices) != 4:
        print(f"want 4 devices, have {len(devices)}", file=sys.stderr)
        return 2
    limits = None if case in SOUND else spec.load_cell(cell_name).limits
    cell = tiny_cell("gpt2-moe", chips=4, batch=8, seq=32, limits=limits)
    if case in SOUND[1:]:
        real = program.program_config

        def forced(conf, dtype="float32"):
            cfg = real(conf, dtype)
            return replace(cfg, moe=replace(cfg.moe, schedule=case))
        program.program_config = forced
    if case == "control":
        train.Program = functools.partial(train.Program, dtype="bfloat16")
    fault = case if case in faults.FAULTS else ""
    prog_cls = train.Program

    def planted(*a, **k):
        with faults.planted(fault):
            return prog_cls(*a, **k)
    train.Program = planted
    res = train.run(cell, seed=2 ** 33 + 29, seconds=0.3, trace=False,
                    devices=devices, t_start=0.0, out_dir=None)
    print(json.dumps({
        "case": case, "correct": bool(res["correct"]),
        "compared": {n: v for n, v, *_ in res["compare"]},
        "schedules": res["schedules"], "pools": list(res["pools"])}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2] if len(sys.argv) > 2
                  else "gpt2-moe.train-s1024-x4"))
