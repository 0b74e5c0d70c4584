"""Top-k gating with expert capacity (GShard-style), plus the scatter
dispatch / gather combine that move tokens in and out of the per-expert
capacity buffer.

``dispatch``/``combine`` compute the flat slot indices here (pure jnp) and
route the actual scatter/gather through the kernel-backend registry
(``repro.kernels.registry``): backend ``"ref"`` is the pure-jnp oracle the
schedule bodies historically inlined, ``"pallas"`` the TPU kernel.
Capacity semantics follow the paper: T = k * f * tokens / E, and each
schedule applies it to the token set it gates (S1 gates each MP shard
independently, so its per-shard capacity is T / N_MP — see DESIGN.md
fidelity notes).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.registry import KernelConfig, get_op


@dataclass(frozen=True)
class GateConfig:
    n_experts: int
    top_k: int = 1
    capacity_factor: float = 1.25
    normalize_topk: bool = False   # qwen3 norm_topk_prob
    aux_loss_weight: float = 1e-2
    z_loss_weight: float = 1e-3
    gate_dtype: jnp.dtype = jnp.float32
    # slot assignment implementation: "sort" is O(S*k log S*k) and avoids
    # materializing the (S*k, E) one-hot cumsum (which dominated the memory
    # roofline term for fine-grained MoE — see EXPERIMENTS.md §Perf A1);
    # "cumsum" is the GShard-style reference.  Identical outputs.
    impl: str = "sort"


def capacity(tokens: int, cfg: GateConfig, align: int = 8) -> int:
    """Per-expert capacity T for a pool of ``tokens`` tokens."""
    c = int(-(-cfg.top_k * cfg.capacity_factor * tokens // cfg.n_experts))
    return max(align, -(-c // align) * align)


class GateResult:
    """One token pool's routing decision, unpackable as the classic
    ``(expert_idx, slot_idx, weights, aux)`` 4-tuple.

    Also memoizes :func:`flat_slots` per ``(cap, n_experts)`` so the
    dispatch scatter and combine gather of the same layer share a single
    flat-index computation instead of each re-deriving it (they always
    ask for the same key, so this halves the index math per MoE layer).
    """

    __slots__ = ("expert_idx", "slot_idx", "weights", "aux", "_flat")

    def __init__(self, expert_idx, slot_idx, weights, aux):
        self.expert_idx = expert_idx
        self.slot_idx = slot_idx
        self.weights = weights
        self.aux = aux
        self._flat = {}

    def __iter__(self):
        return iter((self.expert_idx, self.slot_idx, self.weights,
                     self.aux))

    def __getitem__(self, i):
        return (self.expert_idx, self.slot_idx, self.weights, self.aux)[i]

    def __len__(self):
        return 4

    def flat(self, cap: int, n_experts: int, n_held: int = None):
        """Cached :func:`flat_slots` for this routing decision."""
        key = (cap, n_experts, n_held)
        if key not in self._flat:
            self._flat[key] = flat_slots(self.expert_idx, self.slot_idx,
                                         cap, n_experts, n_held)
        return self._flat[key]


def topk_gate(x, wg, cfg: GateConfig, cap) -> "GateResult":
    """Route tokens to experts.

    Args:
      x: (S, M) tokens.
      wg: (M, E) gate weights.
      cap: per-expert capacity for this token pool — a python int, or an
           (E,) int array of per-expert *effective* capacities (an
           expert replicated r times under an ``ExpertPlacement`` keeps
           ``r * placed_cap`` slots; the scalar path is bitwise
           unchanged).

    Returns a :class:`GateResult` (unpacks as a 4-tuple):
      expert_idx: (S, k) int32 — chosen expert per (token, choice).
      slot_idx:   (S, k) int32 — position in the expert's capacity buffer;
                  >= cap means the token was dropped for that choice.
      weights:    (S, k) f32   — combine weights (0 for dropped).
      aux:        dict with load-balance loss, z-loss and per-expert load.
    """
    S, _ = x.shape
    E, k = cfg.n_experts, cfg.top_k
    logits = jnp.asarray(x, cfg.gate_dtype) @ jnp.asarray(wg, cfg.gate_dtype)
    probs = jax.nn.softmax(logits, axis=-1)                      # (S, E)
    gate_w, expert_idx = lax.top_k(probs, k)                     # (S, k)
    expert_idx = expert_idx.astype(jnp.int32)
    if cfg.normalize_topk:
        gate_w = gate_w / jnp.sum(gate_w, axis=-1, keepdims=True)

    # Capacity assignment with choice-major priority (all 1st choices win
    # slots before any 2nd choice), GShard semantics.
    flat_e = expert_idx.T.reshape(-1)                            # (k*S,) choice-major
    if cfg.impl == "sort":
        # sort-based: stable argsort groups entries by expert while
        # preserving the choice-major priority; the slot is the rank
        # within the expert's run.  O(S*k log S*k) memory/compute — no
        # (S*k, E) one-hot materialization.
        order = jnp.argsort(flat_e, stable=True)                 # (k*S,)
        sorted_e = flat_e[order]
        first = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
        slot_sorted = jnp.arange(k * S, dtype=jnp.int32) - first[sorted_e]
        slot_flat = jnp.zeros((k * S,), jnp.int32).at[order].set(
            slot_sorted.astype(jnp.int32))
        load = jnp.zeros((E,), jnp.float32).at[flat_e].add(
            1.0, mode="drop")
    else:
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)      # (k*S, E)
        pos = jnp.cumsum(onehot, axis=0) - 1                     # slot per entry
        slot_flat = jnp.take_along_axis(pos, flat_e[:, None],
                                        axis=1)[:, 0]
        load = jnp.sum(onehot, axis=0).astype(jnp.float32)
    slot_idx = slot_flat.reshape(k, S).T.astype(jnp.int32)       # (S, k)
    if isinstance(cap, int):
        kept = slot_idx < cap
        cap_f = float(cap)
    else:                       # (E,) per-expert effective capacities
        cap_e = jnp.asarray(cap, jnp.int32)
        kept = slot_idx < cap_e[expert_idx]
        cap_f = cap_e.astype(jnp.float32)
    weights = jnp.where(kept, gate_w, 0.0).astype(jnp.float32)

    # Aux losses (Switch/GShard load balancing + router z-loss).
    me = jnp.mean(probs, axis=0)                                 # mean prob/expert
    first_choice = jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32)
    ce = jnp.mean(first_choice, axis=0)                          # frac tokens/expert
    aux_loss = cfg.aux_loss_weight * E * jnp.sum(me * ce)
    z_loss = cfg.z_loss_weight * jnp.mean(
        jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux = {"aux_loss": aux_loss, "z_loss": z_loss, "load": load,
           # per-expert rows that actually won a slot (= the ragged
           # grouped kernel's group sizes; load is the unclamped demand)
           "routed": jnp.minimum(load, cap_f),
           "drop_frac": 1.0 - jnp.mean(kept.astype(jnp.float32))}
    return GateResult(expert_idx, slot_idx, weights, aux)


def flat_slots(expert_idx, slot_idx, cap: int, n_experts: int,
               n_held: int = None):
    """Flat capacity-buffer index per (token, choice) into the buffer of
    the held experts ``[0, n_held)`` (default all ``n_experts``);
    ``n_held * cap`` marks a dropped choice (the registry ops' drop
    sentinel), and a route to an expert not held here is one."""
    kept = slot_idx < cap
    n = n_experts if n_held is None else n_held
    if n < n_experts:
        kept &= expert_idx < n
    return jnp.where(kept, expert_idx * cap + slot_idx,
                     n * cap).astype(jnp.int32)


def dispatch(x, expert_idx, slot_idx, cap: int, n_experts: int,
             kernel: Optional[KernelConfig] = None, *, flat=None):
    """Scatter tokens into the (E, cap, M) capacity buffer.

    Dropped tokens (slot >= cap) are discarded.  The scatter itself is a
    registry op (``moe_dispatch``), so the backend follows ``kernel``.
    ``flat`` short-circuits the index computation with a precomputed
    :func:`flat_slots` (see :meth:`GateResult.flat`).
    """
    M = x.shape[-1]
    if flat is None:
        flat = flat_slots(expert_idx, slot_idx, cap, n_experts)  # (S, k)
    op = get_op("moe_dispatch", cfg=kernel, n_slots=n_experts * cap)
    return op(x, flat).reshape(n_experts, cap, M)


def combine(buf, expert_idx, slot_idx, weights, cap: int,
            kernel: Optional[KernelConfig] = None, *, flat=None):
    """Gather expert outputs back to token order and mix with gate weights
    (registry op ``moe_combine``; dropped choices contribute zero).
    ``flat`` reuses a precomputed :func:`flat_slots` like :func:`dispatch`."""
    E = buf.shape[0]
    M = buf.shape[-1]
    if flat is None:
        flat = flat_slots(expert_idx, slot_idx, cap, E)          # (S, k)
    op = get_op("moe_combine", cfg=kernel)
    return op(buf.reshape(E * cap, M), flat, weights)
