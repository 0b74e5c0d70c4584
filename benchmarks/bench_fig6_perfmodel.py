"""Fig. 6 reproduction: measure collective latencies over message sizes on
a real (8 fake CPU device) mesh, least-squares fit alpha/beta per collective
(paper §V-A / §VI-B), and report the fit quality (R^2).

Run via a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
(benchmarks/run.py does this automatically).
"""

from __future__ import annotations

import os


def _ensure_devices():
    if "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=8 "
            + os.environ.get("XLA_FLAGS", ""))


_ensure_devices()

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
from jax.sharding import PartitionSpec as P             # noqa: E402

from benchmarks.common import emit, time_fn             # noqa: E402
from repro.core import collectives as coll              # noqa: E402
from repro.core.perfmodel import fit_alpha_beta         # noqa: E402
from repro.parallel.mesh import make_mesh               # noqa: E402

SIZES = [2 ** i for i in range(12, 21)]   # elements


def measure(mesh, make_fn, sizes=SIZES):
    times = []
    for n in sizes:
        x = jnp.zeros((64, max(n // 64, 1)), jnp.float32)
        f = jax.jit(make_fn)
        f(x).block_until_ready()
        times.append(time_fn(lambda: f(x).block_until_ready(), iters=7))
    return times


def r_squared(sizes, times, fit):
    mean = sum(times) / len(times)
    ss_tot = sum((t - mean) ** 2 for t in times)
    ss_res = sum((t - fit(x)) ** 2 for x, t in zip(sizes, times))
    return 1 - ss_res / ss_tot if ss_tot else 1.0


def main():
    mesh = make_mesh((4, 2), ("data", "model"))

    def ag_mp(x):
        return jax.shard_map(
            lambda v: coll.mp_all_gather(v, ("model",), 2, axis=0),
            mesh=mesh, in_specs=P(("data", "model"), None),
            out_specs=P(("data",), None), check_vma=False)(x)

    def a2a_ep_esp(x):
        return jax.shard_map(
            lambda v: coll.ep_esp_all_to_all(v, ("data",), ("model",)),
            mesh=mesh, in_specs=P(("data", "model"), None),
            out_specs=P(("data", "model"), None), check_vma=False)(x)

    def a2a_ep(x):
        return jax.shard_map(
            lambda v: coll.ep_all_to_all(v, ("data",)),
            mesh=mesh, in_specs=P(("data",), None),
            out_specs=P(("data",), None), check_vma=False)(x)

    for name, fn in [("ag_mp", ag_mp), ("a2a_ep_esp", a2a_ep_esp),
                     ("a2a_ep", a2a_ep)]:
        times = measure(mesh, fn)
        fit = fit_alpha_beta(SIZES, times)
        r2 = r_squared(SIZES, times, fit)
        emit(f"fig6/{name}_alpha_us", fit.alpha * 1e6, f"r2={r2:.4f}")
        emit(f"fig6/{name}_beta_ns_per_el", fit.beta * 1e9,
             f"n_sizes={len(SIZES)}")
        # the paper's claim: the linear model fits collectives well
        assert r2 > 0.8, (name, r2, times)


if __name__ == "__main__":
    main()
