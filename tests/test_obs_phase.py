"""``obs.phase``: the in-memory record of each set-up phase (host clock,
parent, bound), the ``phase`` sink event, and the profiler annotation."""

import pytest

from repro import obs
from repro.obs.sink import read_events


@pytest.fixture(autouse=True)
def fresh(monkeypatch):
    """Each test sees an empty, small phase record and no sink."""
    import collections
    monkeypatch.setattr(obs, "_PHASES", collections.deque(maxlen=4))
    obs.close()
    yield
    obs.close()


def test_record_times_the_block():
    import time
    with obs.phase("setup.init"):
        time.sleep(0.01)
    (ph,) = obs.phases()
    assert ph.name == "setup.init" and ph.parent is None
    assert ph.end_ns - ph.start_ns >= 10_000_000


def test_parent_is_the_enclosing_phase():
    with obs.phase("setup.init"):
        with obs.phase("setup.lower"):
            pass
        with obs.phase("setup.compile"):
            pass
    with obs.phase("train.checkpoint"):
        pass
    got = [(p.name, p.parent) for p in obs.phases()]
    assert got == [("setup.lower", "setup.init"),
                   ("setup.compile", "setup.init"),
                   ("setup.init", None), ("train.checkpoint", None)]
    inner, _, outer, _ = obs.phases()
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns


def test_record_is_bounded_newest_last():
    for i in range(6):
        with obs.phase(f"p{i}"):
            pass
    assert [p.name for p in obs.phases()] == ["p2", "p3", "p4", "p5"]


def test_a_raising_block_is_recorded_and_reraises():
    with pytest.raises(RuntimeError):
        with obs.phase("setup.compile"):
            raise RuntimeError("compile failed")
    assert [p.name for p in obs.phases()] == ["setup.compile"]
    with obs.phase("after"):
        pass
    assert obs.phases()[-1].parent is None


def test_sink_gets_a_phase_event(tmp_path):
    sink = obs.configure(str(tmp_path), meta={"kind": "t"})
    obs.set_context(step=3)
    with obs.phase("train.checkpoint", step=7):
        pass
    obs.flush()
    ev = [e for e in read_events(sink.paths) if e["event"] == "phase"]
    assert len(ev) == 1
    e = ev[0]
    assert e["name"] == "train.checkpoint" and e["step"] == 7
    assert e["parent"] is None and e["seconds"] >= 0.0


def test_no_sink_no_event():
    with obs.phase("setup.init"):
        pass
    assert obs.get_sink() is None and len(obs.phases()) == 1


def test_phase_is_a_profiler_annotation(tmp_path):
    import glob
    import os
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.phase("setup.lower"):
            jax.numpy.zeros(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in pd.planes for line in plane.lines
             for e in line.events}
    assert "setup.lower" in names
