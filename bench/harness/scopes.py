"""Shared arithmetic of the readers of the program's layer scopes, step
spans and set-up phases (``bench/metrics/<layer>.*``).

Layer scopes.  The program names its layers with ``jax.named_scope``:
``attn``, ``ffn``, ``norm``, ``head`` and ``adamw`` (the MoE layer keeps
its ``<plan>.<stage>`` scopes, ``readers.moe_pattern``).  A scope shows
in an instruction's scope path as a part of its own
(``.../closed_call/attn/dot_general``) or as the argument of an AD
transform (``jvp(head)/...``, ``transpose(jvp(head))/...``).  An
instruction is backward where its path holds ``transpose(`` outside the
remat recompute (``rematted_computation``), which counts as forward.

Step spans.  ``Trainer.run`` marks its host work with profiler
annotations (``train.input``, ``train.expert_load_read``, ...), on the
clock of the device's ops, so each idle gap of the device falls in the
span that held it back.

Set-up phases.  ``repro.obs.phase`` keeps each phase's host-clock start
and end in the process (``repro.obs.phases()``).

Each reader returns None where the program has no such scope, span or
phase."""

from __future__ import annotations

import re

from bench.harness import trace as T
from bench.harness.readers import moe_pattern, per_device

LAYERS = ("attn", "ffn", "norm", "head", "adamw")


def scope_pattern(scope: str) -> str:
    """The scope as a part of a path or a transform's argument, and not
    as the name of a jitted function (``jit(norm)``)."""
    return r"(^|/|(?<!jit)\()%s([/)]|$)" % re.escape(scope)


def is_backward(path: str) -> bool:
    return "transpose(" in path and "rematted_computation" not in path


def device_ms(run, keep):
    """Device ms per step, averaged over chips, of the leaf ops whose
    scope path ``keep`` accepts."""
    t = run.get("traced")
    if not t:
        return None
    index = t["index"]

    def one(ops, lo, hi):
        return T.length(T.clip(T.union(
            (s, s + d) for name, _, s, d in T.leaf_ops(ops)
            if keep(index.get(name, ""))), lo, hi))
    ns = per_device(run, one)
    return None if ns is None else ns / 1e6 / t["n_steps"]


def _has_scope(run, rx) -> bool:
    t = run.get("traced")
    return bool(t) and any(rx.search(p) for p in t["index"].values())


def layer_ms(run, scope: str, part: str = "all"):
    """Device ms per step under ``scope``: ``part`` "all", "fwd" (the
    forward and the remat recompute) or "bwd"."""
    rx = re.compile(scope_pattern(scope))
    if not _has_scope(run, rx):
        return None
    if part == "all":
        return device_ms(run, rx.search)
    bwd = part == "bwd"
    return device_ms(run, lambda p: bool(rx.search(p))
                     and is_backward(p) == bwd)


def unnamed_ms(run):
    """Device ms per step of the leaf ops under none of the layer scopes
    and no plan scope (embedding, loss glue, empty paths)."""
    pats = [scope_pattern(s) for s in LAYERS]
    if not _has_scope(run, re.compile("|".join(pats))):
        return None
    if run.get("schedules"):
        pats.append(moe_pattern(run["schedules"]))
    rx = re.compile("|".join(f"(?:{p})" for p in pats))
    return device_ms(run, lambda p: not rx.search(p))


def idle_in_span_ms(run, span: str):
    """Device idle ms per step, averaged over chips, inside the host
    spans named ``span``: idle gaps ∩ union(spans)."""
    t = run.get("traced")
    if not t:
        return None
    spans = T.union((s, s + d) for n, s, d in t["host"] if n == span)
    if not spans:
        return None

    def one(ops, lo, hi):
        gaps = T.idle_gaps(ops, lo, hi)
        return T.length(T.subtract(gaps, T.subtract(gaps, spans)))
    ns = per_device(run, one)
    return None if ns is None else ns / 1e6 / t["n_steps"]


def first_phase_s(name: str):
    """Seconds of the process's first set-up phase ``name``."""
    from repro import obs
    if not hasattr(obs, "phases"):
        return None
    for ph in obs.phases():
        if ph[0] == name:
            return (ph[2] - ph[1]) / 1e9
    return None
