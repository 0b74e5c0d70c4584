"""Find a cell's pieces by name: ``BENCHMARK.json`` names the cell, the
cell names its configuration and traffic mix, and each of those, each
per-layer metric and each kernel's operation count is a file of its own
under ``bench/``.  Adding one is adding a file; nothing here changes.

A configuration belongs to a model family (its key ``family``; without
it, the family ``model``).  A family is three files
named after it: ``bench/programs/<family>.py`` (the program's entry for
the configuration: ``program_config(conf, dtype)``,
``adamw_config(conf)``), ``bench/reference/<family>.py`` (the plain
reference: ``train_steps``) and ``bench/flops/<family>.py`` (model
FLOPs: ``train_flops_per_token(m, seq_len)``)."""

from __future__ import annotations

import functools
import importlib.util
import json
import os
from dataclasses import dataclass

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
DEFAULT_FAMILY = "model"     # the family of configuration files without one
FAMILY_PARTS = ("programs", "reference", "flops")


class SpecError(Exception):
    pass


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}") from e


def load_module(path: str, name: str):
    """Import one file of the benchmark by its path."""
    if not os.path.exists(path):
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict           # bench/configs/<config>.json
    traffic: dict          # bench/traffic/<mix>.json
    limits: dict           # bench/limits/<cell>.json ({} if none yet)
    end_to_end: list       # BENCHMARK.json metrics this cell reports
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_file: str = None, bench_dir: str = BENCH
              ) -> Cell:
    spec = _load_json(bench_file or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[name]
    limits_path = os.path.join(bench_dir, "limits", f"{name}.json")
    return Cell(
        name=name, chips=w["chips"],
        config=_load_json(os.path.join(bench_dir, "configs",
                                       f"{w['config']}.json")),
        traffic=_load_json(os.path.join(bench_dir, "traffic",
                                        f"{w['traffic']}.json")),
        limits=(_load_json(limits_path) if os.path.exists(limits_path)
                else {}),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def generator(mix: dict, bench_dir: str = BENCH):
    """The traffic generator a mix names (``bench/traffic/<gen>.py``)."""
    return load_module(os.path.join(bench_dir, "traffic",
                                    f"{mix['generator']}.py"),
                       f"bench_traffic_{mix['generator']}")


def metric_reader(name: str, bench_dir: str = BENCH):
    """``read(run)`` of a per-layer metric (``bench/metrics/<name>.py``)."""
    return load_module(os.path.join(bench_dir, "metrics", f"{name}.py"),
                       "bench_metric_" + name.replace(".", "_")).read


def kernel_counter(kernel: str, bench_dir: str = BENCH):
    """``ops_bytes(call)`` of a Pallas kernel
    (``bench/flops/kernels/<kernel>.py``), or None if there is none."""
    path = os.path.join(bench_dir, "flops", "kernels", f"{kernel}.py")
    if not os.path.exists(path):
        return None
    return load_module(path, f"bench_kernel_{kernel}").ops_bytes


def family(conf: dict) -> str:
    return conf.get("family", DEFAULT_FAMILY)


def family_part(part: str, conf: dict, bench_dir: str = BENCH):
    """The module ``bench/<part>/<family>.py`` of a configuration's
    family, ``part`` one of FAMILY_PARTS; loaded once per process."""
    if part not in FAMILY_PARTS:
        raise SpecError(f"no family part {part!r} (have {FAMILY_PARTS})")
    return _family_module(os.path.join(bench_dir, part,
                                       f"{family(conf)}.py"))


@functools.lru_cache(maxsize=None)
def _family_module(path: str):
    rel = os.path.relpath(path, BENCH).replace(os.sep, "_")
    return load_module(path, "bench_family_" + rel[:-3].replace(".", "_"))


def peaks(kind: str, bench_dir: str = BENCH) -> dict:
    table = _load_json(os.path.join(bench_dir, "peaks.json"))
    if kind not in table:
        raise SpecError(f"device kind {kind!r} is not in bench/peaks.json "
                        f"(have {sorted(table)})")
    return table[kind]
