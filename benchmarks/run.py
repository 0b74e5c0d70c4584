"""Benchmark runner: one bench per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Analytic benches run
in-process; measured multi-device benches run in subprocesses with 8 fake
CPU devices (the main process must keep seeing 1 device).

A CPU tool by design: it pins ``JAX_PLATFORMS=cpu`` for itself and every
child it starts, so on a TPU host it neither takes the chip nor contends
with the process that holds it.  Its timings are CPU timings, never
device numbers; ``chip_smoke.py`` is the run on the chip.

Every row is also collected into the canonical ``BENCH_pr10.json`` at the
repo root — the machine-readable perf trajectory successive PRs diff
against (schema: ``{"rows": [{"name", "us_per_call", "derived"}, ...]}``).
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import subprocess
import sys

os.environ["JAX_PLATFORMS"] = "cpu"      # before any bench imports jax

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):   # python benchmarks/run.py
    if _p not in sys.path:
        sys.path.insert(0, _p)

IN_PROCESS = [
    "benchmarks.bench_fig1_comm_ratio",
    "benchmarks.bench_table4_speedups",
    "benchmarks.bench_fig7_stats",
    "benchmarks.bench_roofline",
    "benchmarks.bench_kernels",
]
SUBPROCESS = [
    "benchmarks.bench_fig6_perfmodel",
    "benchmarks.bench_table4_measured",
    "benchmarks.bench_table5_realworld",
    "benchmarks.bench_comm_precision",
    "benchmarks.bench_plan_overhead",
    "benchmarks.bench_serve",
    "benchmarks.bench_guards",
    "benchmarks.bench_loadbalance",
    "benchmarks.bench_obs_overhead",
]

BENCH_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_pr10.json")


def _collect(rows: list, line: str) -> None:
    """Parse one ``name,us_per_call,derived`` CSV row into ``rows``."""
    parts = line.split(",", 2)
    if len(parts) != 3 or parts[0] in ("", "name"):
        return
    try:
        us = float(parts[1])
    except ValueError:
        return
    rows.append({"name": parts[0], "us_per_call": us,
                 "derived": parts[2]})


def main() -> None:
    from importlib import import_module
    rows: list = []
    print("name,us_per_call,derived")
    for mod in IN_PROCESS:
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            import_module(mod).main()
        for line in buf.getvalue().splitlines():
            print(line)
            _collect(rows, line)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root, env.get("PYTHONPATH", "")])
    for mod in SUBPROCESS:
        r = subprocess.run([sys.executable, "-m", mod], env=env, cwd=root,
                           capture_output=True, text=True, timeout=3600)
        if r.returncode != 0:
            print(f"{mod},0,FAILED: {r.stderr[-300:]!r}")
            raise SystemExit(1)
        for line in r.stdout.splitlines():
            if "," in line:
                print(line)
                _collect(rows, line)
    with open(BENCH_JSON, "w") as f:
        json.dump({"rows": rows}, f, indent=1)
    print(f"# wrote {len(rows)} rows to {os.path.basename(BENCH_JSON)}")


if __name__ == '__main__':
    main()
