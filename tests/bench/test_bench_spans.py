"""The trainer's host spans and set-up phases as the benchmark reads
them: two ``Trainer.run`` steps of a tiny MoE model traced by the JAX
profiler on the CPU, reduced by ``bench.harness.trace.load``."""

import bench_helpers  # noqa: F401  (the repo root and src on the path)

import glob
import os

from bench.harness import trace as T

STEP_SPANS = ("train.input", "train.dispatch", "train.expert_load_read",
              "train.log")


def _tiny_trainer(guards=None, ckpt_path=None):
    import jax
    from bench.harness import program
    from bench.harness.spec import generator
    from repro.launch.mesh import local_mesh
    from repro.models import build_model
    from repro.train import Trainer

    cell = bench_helpers.tiny_cell(n_layers=2)
    cfg = program.program_config(cell.config)
    mesh, dims = local_mesh(cfg, jax.devices()[:1])
    tr = Trainer(build_model(cfg), mesh, dims,
                 program.adamw_config(cell.config), guards=guards,
                 ckpt_path=ckpt_path)
    params, opt_state = tr.setup(jax.random.PRNGKey(0))
    feed = program.Feed(generator(cell.traffic).make(
        cell.traffic, cell.config["model"]["vocab_size"], 5))
    return tr, params, opt_state, feed


def _traced(tmp_path, fn):
    """The host spans (name, start, duration) of ``fn()`` traced."""
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    return T.load(path)["host"]


def _starts(host, name):
    return sorted(s for n, s, _ in host if n == name)


def test_run_marks_each_step_on_the_host_plane(tmp_path):
    tr, params, opt_state, feed = _tiny_trainer()
    params, opt_state, _ = tr.run(params, opt_state, feed, 1)  # compiles
    host = _traced(tmp_path, lambda: tr.run(params, opt_state, feed, 2,
                                            log_every=1))
    for name in STEP_SPANS:
        assert len(_starts(host, name)) >= 2, name
    steps = sorted((s, s + d) for n, s, d in host if n == "train")
    assert len(steps) == 2
    # inside each step: the batch, then the dispatch, then the read
    for lo, hi in steps:
        i, d, r = (min(s for s in _starts(host, n) if lo <= s < hi)
                   for n in STEP_SPANS[:3])
        assert i < d < r


def test_guarded_run_marks_the_guard_read_and_checkpoint(tmp_path):
    from repro import obs
    from repro.runtime.guards import GuardConfig
    tr, params, opt_state, feed = _tiny_trainer(
        guards=GuardConfig(), ckpt_path=str(tmp_path / "ckpt"))
    n_before = len(obs.phases())
    host = _traced(tmp_path / "trace", lambda: tr.run(
        params, opt_state, feed, 2, log_every=1, ckpt_every=1))
    for name in ("train.input", "train.dispatch", "train.guard_read",
                 "train.log", "train.checkpoint"):
        assert _starts(host, name), name
    saved = [p for p in obs.phases()[n_before:]
             if p.name == "train.checkpoint"]
    assert len(saved) == 2          # the anchor and step 1's snapshot


def test_setup_and_compile_are_phases():
    import jax
    from repro import obs
    tr, params, opt_state, feed = _tiny_trainer()
    got = obs.phases()[-1]
    assert got.name == "setup.init" and got.parent is None
    batch = feed.sharded_batch(0, tr.mesh, tuple(tr.dims.batch_axes))
    tr.compile(params, opt_state, batch)
    lower, comp = obs.phases()[-2:]
    assert (lower.name, comp.name) == ("setup.lower", "setup.compile")
    assert lower.end_ns <= comp.start_ns
    assert all(p.end_ns > p.start_ns for p in (got, lower, comp))
    jax.block_until_ready(params)

