"""The comparison that decides ``correct`` fails what it must: the
bfloat16 control in the program's place, and whole benchmark runs (the
look for a chip skipped) with the timed path broken underneath.  A sound
run of the same size comes out correct."""
import jax
import pytest

from bench import control, harness
from bench.tests.tiny import tiny_cell


@pytest.mark.parametrize("workload", ["das2-500pm.trace1k",
                                      "das2-grid-100pm.sweep24"])
def test_bfloat16_control_is_caught(workload):
    out = control.control_numbers(tiny_cell(workload, n_tasks=64), seed=4)
    assert not out["correct"], out["numbers"]


def _run(cell, monkeypatch=None, name=None, broken=None):
    from repro.core import engine
    from repro.sched import registry
    registry.names("vm")  # register the built-in policies before patching
    if broken is not None:
        orig = getattr(engine, name)
        monkeypatch.setattr(engine, name,
                            lambda *a, **k: broken(orig, *a, **k))
    return harness.run(cell, seed=2**31 + 3, seconds=0.0, trace=False,
                       require_platform=None, log=lambda *a: None)


def _altered(orig, *a, **k):
    """One task's completion time moved by 1 % where it is produced."""
    res = orig(*a, **k)
    c = res.completion
    return res._replace(completion=c.at[..., 5].multiply(1.01))


def _unchanged(orig, spec, trace, params, *a, **k):
    """The event loop returns the state it was given: nothing runs."""
    k["t_stop"] = 0.0
    return orig(spec, trace, params, **k)


def _half_lanes(orig, *a, **k):
    """Half of the sweep's lanes left out: lane 0's answers stand in."""
    res = orig(*a, **k)
    half = res.completion.shape[0] // 2
    return jax.tree.map(lambda x: x.at[half:].set(x[:1].repeat(
        x.shape[0] - half, axis=0)), res)


def _other_chips_missing(orig, *a, **k):
    """The exchange between chips left out: only the first quarter of the
    lanes (the first chip's shard) comes back; lane 0 stands in for the
    rest."""
    res = orig(*a, **k)
    keep = max(res.completion.shape[0] // 4, 1)
    return jax.tree.map(lambda x: x.at[keep:].set(x[:1].repeat(
        x.shape[0] - keep, axis=0)), res)


CASES = [
    ("das2-500pm.trace1k", "simulate", _altered),
    ("das2-500pm.trace1k", "simulate", _unchanged),
    ("das2-grid-100pm.sweep24", "simulate_batch", _altered),
    ("das2-grid-100pm.sweep24", "simulate_batch", _unchanged),
    ("das2-grid-100pm.sweep24", "simulate_batch", _half_lanes),
    ("das2-500pm.stream", "simulate_stream", _altered),
    ("das2-grid-100pm.sweep24", "simulate_batch_sharded",
     _other_chips_missing),
    ("das2-grid-100pm.sweep24", "simulate_batch_sharded", _altered),
]


@pytest.mark.parametrize("workload,entry,fault", CASES,
                         ids=[f"{w}-{f.__name__}" for w, _, f in CASES])
def test_broken_timed_path_is_not_correct(monkeypatch, workload, entry,
                                          fault):
    cell = tiny_cell(workload)
    if entry == "simulate_batch_sharded":
        # the sweep's grid through the sharded driver: the four-chip cell
        # itself is not in BENCHMARK.json yet
        cell.traffic["driver"] = entry
    line = _run(cell, monkeypatch, entry, fault)
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("workload", ["das2-500pm.trace1k",
                                      "das2-grid-100pm.sweep24",
                                      "das2-500pm.stream"])
def test_sound_run_is_correct(workload):
    line = _run(tiny_cell(workload))
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"tasks_per_s", "setup_s"}
    assert list(line)[-1] == "checks"
