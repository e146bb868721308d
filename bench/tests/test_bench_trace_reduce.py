"""The trace reduction on small profiler traces recorded on one TPU v5e
(one sliced call of das2-500pm.trace1k, from a program without and with
stage scopes and host spans), and its interval arithmetic."""
import pathlib

import pytest

from bench import harness, trace_reduce

TRACE = pathlib.Path(__file__).parent / "data" / "small.xplane.pb.gz"
SCOPED = pathlib.Path(__file__).parent / "data" / "small_scoped.xplane.pb.gz"
STAGE_METRICS = ("advance_us", "observe_us", "vm_lifecycle_us",
                 "pm_sched_us", "vm_sched_us")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(trace_reduce.load(TRACE))


def test_busy_within_window(reduced):
    assert reduced["n_devices"] == 1
    assert 0 < reduced["busy_s"] <= reduced["window_s"]


def test_breakdown_is_named_and_short(reduced):
    ops = reduced["breakdown"]["device_ops"]
    gaps = reduced["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert all(" " not in name and t > 0 for name, t in ops)
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    assert all(name.startswith("bench.") or name == "outside bench spans"
               for name, _ in gaps)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(t for _, t in gaps) <= idle * (1 + 1e-9)


def test_program_runs_found(reduced):
    red = trace_reduce.reduce(trace_reduce.load(TRACE), program="simulate")
    assert red["program_runs"] and all(b > a for a, b in
                                       red["program_runs"])


def test_self_time_and_union():
    t = trace_reduce._self_times([(0, 10, "while"), (1, 3, "a"),
                                  (4, 6, "b"), (4.5, 5, "c")])
    assert t == {"while": 6, "a": 2, "b": 1.5, "c": 0.5}
    assert trace_reduce._union([(3, 4), (0, 2), (1, 3)]) == [[0, 4]]
    assert trace_reduce.op_name("%fusion.3 = f32[8]{0} fusion(x)") == \
        "fusion.3"


def _read(name, traced):
    reader = harness.load_module(harness.BENCH / "metrics" / f"{name}.py")
    return reader.read({"traced": traced, "calls": []})


def test_capture_merges_the_stage_times():
    """What ``capture`` reduces a traced call to carries the program's
    stage times per iteration, which add up to ``iter_us``, and its
    labelled idle time."""
    red = trace_reduce.reduce_raw(trace_reduce.raw_bytes(SCOPED),
                                  iterations=12)
    iter_us = _read("iter_us", red)
    assert sum(red["stages"].values()) * 1e6 == pytest.approx(iter_us,
                                                              rel=0.05)
    for name in STAGE_METRICS:
        assert 0 < _read(name, red) < iter_us
    assert red["entry"]["name"] == "repro.simulate"
    assert any(n.startswith("repro.") for n, _ in
               red["breakdown"]["idle_gaps"])
    idle = red["window_s"] - red["busy_s"]
    assert sum(red["idle_by_label"].values()) == pytest.approx(idle,
                                                               rel=1e-6)
    assert 0 < _read("entry_idle_ms", red) < idle * 1e3


def test_unscoped_trace_gives_no_stage_metrics():
    red = trace_reduce.reduce_raw(trace_reduce.raw_bytes(TRACE),
                                  iterations=12)
    assert red["entry"] is None
    assert red["breakdown"]["idle_gaps"] == trace_reduce.reduce(
        trace_reduce.load(TRACE))["breakdown"]["idle_gaps"]
    for name in STAGE_METRICS + ("entry_idle_ms",):
        assert _read(name, red) is None
