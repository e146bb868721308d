"""The trace reduction on a small profiler trace recorded on one TPU v5e
(one sliced call of das2-500pm.trace1k), and its interval arithmetic."""
import pathlib

import pytest

from bench import trace_reduce

TRACE = pathlib.Path(__file__).parent / "data" / "small.xplane.pb.gz"


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
