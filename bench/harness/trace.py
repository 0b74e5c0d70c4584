"""From a profiler trace to per-layer numbers.

Two steps, kept apart so that the second can be checked on a recorded
trace without a chip:

1. :func:`load` reads the ``.xplane.pb`` the JAX profiler writes into
   plain lists: per device the ops of its ``XLA Ops`` line, as
   (instruction, opcode, start ns, duration ns), and the host's spans (TraceAnnotation names and,
   with the Python tracer, Python frames) as (name, start, duration).
2. The functions below reduce those lists, given the compiled step's
   HLO (instruction -> op_name, the ``jax.named_scope`` path, and which
   instructions are collectives), to busy and idle time, time under the
   MoE plan scopes, per-kernel time, exposed collective time and the
   breakdown.

Container ops (``while``, ``conditional``, ``call``) span the ops of
their bodies and are left out of every sum and union.
"""

from __future__ import annotations

import glob
import os
import re
import shutil

CONTAINERS = {"while", "conditional", "call"}
_OPCODE = re.compile(r"=.*?\s([a-z][\w\-]*)\(")


def opcode(text: str) -> str:
    """The HLO opcode of an instruction's text ``%name = <type> op(...)``."""
    m = _OPCODE.search(text)
    return m.group(1) if m else ""


def hlo_index(hlo_text: str) -> dict:
    """instruction name -> op_name (its scope path) in a compiled HLO."""
    out = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        if not line.startswith(("%", "ROOT %")):
            continue
        name = line.split(" = ", 1)[0].replace("ROOT ", "").lstrip("%")
        m = re.search(r'op_name="([^"]*)"', line)
        out[name] = m.group(1) if m else ""
    return out


COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute", "collective-broadcast")
MATMULS = ("convolution", "dot")
KERNEL = 'custom_call_target="tpu_custom_call"'    # a Pallas kernel
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")


def collective_ops(hlo_text: str) -> set:
    """Names of the instructions of a compiled HLO that are collectives:
    a collective op (``all-to-all``, ``all-reduce-start``, ...), or a
    fusion or async op whose computation holds one and no matmul or
    Pallas kernel.  (A fusion that carries a matmul beside its
    collective overlaps the two by construction: its time counts as
    compute.)"""
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)", line)
            cur = comps.setdefault(m.group(1) if m else line, [])
            continue
        line = line.strip()
        if cur is not None and line.startswith(("%", "ROOT %")):
            name = line.split(" = ", 1)[0].replace("ROOT ", "").lstrip("%")
            op = opcode(line)
            mm = op.startswith(MATMULS) or KERNEL in line
            cur.append((name, op, mm, _CALLS.findall(line)))

    def is_coll(op):
        return op.startswith(COLLECTIVES)

    def holds(comp, seen=()):      # (holds a collective, holds a matmul)
        coll = mm = False
        for _, op, m, calls in comps.get(comp, ()):
            coll |= is_coll(op)
            mm |= m
            for c in calls:
                if c not in seen:
                    a, b = holds(c, seen + (comp,))
                    coll, mm = coll | a, mm | b
        return coll, mm

    out = set()
    for instrs in comps.values():
        for name, op, _, calls in instrs:
            if is_coll(op):
                out.add(name)
            elif calls and (op == "fusion" or op.startswith("async-")):
                got = [holds(c) for c in calls]
                if any(c for c, _ in got) and not any(m for _, m in got):
                    out.add(name)
    return out


def load(path: str) -> dict:
    """The device and host events of one ``.xplane.pb``."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                devices[plane.name] = [
                    (e.name.split(" = ", 1)[0].lstrip("%"), opcode(e.name),
                     int(e.start_ns), int(e.duration_ns))
                    for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events)
    return {"devices": devices, "host": host}


# --- interval arithmetic -----------------------------------------------------


def union(intervals) -> list:
    """Merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(merged, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in merged
            if min(e, hi) > max(s, lo)]


def length(merged) -> int:
    return sum(e - s for s, e in merged)


def subtract(a, b) -> list:
    """a minus b, both merged."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


# --- reductions ----------------------------------------------------------------


def leaf_ops(ops):
    return [o for o in ops if o[1] not in CONTAINERS]


def busy(ops, lo, hi) -> list:
    """Merged intervals in [lo, hi) in which some op runs."""
    return clip(union((s, s + d) for _, _, s, d in leaf_ops(ops)), lo, hi)


def idle_gaps(ops, lo, hi) -> list:
    return subtract([[lo, hi]], busy(ops, lo, hi))


def scope_time(ops, index: dict, pattern: str, lo, hi) -> int:
    """Device ns in [lo, hi) of ops whose scope path matches ``pattern``."""
    rx = re.compile(pattern)
    return length(clip(union(
        (s, s + d) for name, _, s, d in leaf_ops(ops)
        if rx.search(index.get(name, ""))), lo, hi))


def kernel_calls(ops, kernel: str, lo, hi) -> tuple:
    """(number of calls, device ns) of a Pallas kernel in [lo, hi): its
    instructions are named after it (``%flash_attention.48``)."""
    n, t = 0, 0
    for name, _, s, d in ops:
        if name.rsplit(".", 1)[0] == kernel and lo <= s < hi:
            n, t = n + 1, t + d
    return n, t


def exposed_collective(ops, collectives: set, lo, hi) -> int:
    """Device ns in [lo, hi) in which a collective runs and no other op
    does.  A collective runs for its own op's time and, for an async
    pair (``<kind>-start`` ... ``<kind>-done``), from the start's
    beginning to the done's end; pairs of one kind are matched in order,
    which gives the same union as any matching in which each done
    follows its start."""
    coll, other, open_ = [], [], {}
    for name, _, s, d in sorted(leaf_ops(ops), key=lambda o: o[2]):
        if name not in collectives:
            other.append((s, s + d))
            continue
        coll.append((s, s + d))
        base = name.rsplit(".", 1)[0]
        if base.endswith("-start"):
            open_.setdefault(base[:-6], []).append(s)
        elif base.endswith("-done") and open_.get(base[:-5]):
            coll.append((open_[base[:-5]].pop(0), s + d))
    return length(subtract(clip(union(coll), lo, hi),
                           clip(union(other), lo, hi)))


def op_group(name: str, index: dict, kernels, schedules) -> str:
    """What a device op is part of: a kernel, a ``<plan>.<stage>`` scope,
    or its jax primitive, forward or backward."""
    base = name.rsplit(".", 1)[0]
    if base in kernels:
        return base
    path = index.get(name, "")
    for part in path.split("/"):
        if part.split(".", 1)[0] in schedules and "." in part:
            return part
    prim = path.rsplit("/", 1)[-1] or base
    return ("bwd " if "transpose(" in path else "fwd ") + prim


def breakdown(ops, index, kernels, schedules, lo, hi, host, n_top=10):
    """The device ops that took most time, and the longest idle gaps by
    the innermost host span they fall in."""
    tot = {}
    for name, _, s, d in leaf_ops(ops):
        if lo <= s < hi:
            g = op_group(name, index, kernels, schedules)
            tot[g] = tot.get(g, 0) + d
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n_top]
    gaps = sorted(idle_gaps(ops, lo, hi), key=lambda g: g[0] - g[1])[:n_top]
    named = []
    for s, e in gaps:
        mid = (s + e) / 2
        inner = [(d, n) for n, hs, d in host
                 if hs <= mid < hs + d and n != "bench.traced_window"]
        named.append([min(inner)[1] if inner else "no host span",
                      (e - s) / 1e9])
    return {"device_ops": [[g, t / 1e9] for g, t in top],
            "idle_gaps": named}


def window(host, name: str) -> tuple:
    """[start, end) of the host span ``name``."""
    for n, s, d in host:
        if n == name:
            return s, s + d
    raise ValueError(f"no host span {name!r} in the trace")


def traced_window(prog, n_steps, out_dir):
    """Trace ``n_steps`` steps of the program's ``Trainer.run``; returns
    the trace's events, with each step's routed-row counter."""
    import jax
    tdir = os.path.join(out_dir, "trace")
    shutil.rmtree(tdir, ignore_errors=True)
    loads = []
    step = prog.tr._step

    def counted(p, o, b):          # keep each step's routed-row counts
        out = step(p, o, b)
        loads.append(out[2].get("expert_load"))
        return out

    prog.tr._step = counted
    jax.profiler.start_trace(tdir)
    try:
        with jax.profiler.TraceAnnotation("bench.traced_window"):
            prog.steps(n_steps)
            jax.block_until_ready((prog.params, prog.opt_state))
    finally:
        jax.profiler.stop_trace()
        prog.tr._step = step
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    ev = load(path)
    shutil.rmtree(tdir, ignore_errors=True)
    ev["loads"] = [None if x is None else jax.device_get(x).tolist()
                   for x in loads]
    ev["n_steps"] = n_steps
    return ev
