"""The run's result line and the numbers compared, as the benchmark prints
them."""

from __future__ import annotations

import sys

from bench.harness.spec import metric_reader


def result_line(cell, res: dict, peaks: dict, devices, trace: bool) -> dict:
    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            v = {"train_tokens_per_s": res["tokens_per_s"],
                 "setup_s": res["setup_s"]}.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        run = dict(res, cell=cell, peaks=peaks, chips=len(devices))
        for m in cell.per_layer:
            v = metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(res["peak"])}
    line = {"correct": bool(res["correct"]),
            "attempted": len(res["compare"]),
            "failed": max(sum(1 for *_, good in res["compare"] if not good),
                          0 if res["correct"] else 1),
            "metrics": metrics, "device": device}
    if trace and res["traced"]:
        t = res["traced"]
        device["busy_s"] = t["busy_s"]
        device["window_s"] = t["window_s"]
        line["breakdown"] = t["breakdown"]
    line["compared"] = {name: {"value": v, "limit": lim}
                        for name, v, lim, _, _ in res["compare"]}
    return line


def print_compare(lines):
    for name, v, lim, where, good in lines:
        print(f"compared {name} {v!r} limit {lim!r} "
              f"({'ok' if good else 'FAIL'}; worst at {where})",
              file=sys.stderr, flush=True)
