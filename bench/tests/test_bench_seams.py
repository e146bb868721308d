"""The seams through which a new deployment reaches the harness without an
edit to it: the reference module a traffic file names, the program's
cloud and trace built from what the files hold, the program's counters
in each lane's answer, and the per-layer readers of counters and stage
times."""
import dataclasses
import json
import sys
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.drivers import common
from bench.tests.tiny import tiny_cell

CELLS = [w["name"] for w in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]


# ---- the reference named by the cell

STUB = '''
from bench.reference import cloud as base

SEEN = []


def cloud(config, lane):
    return base.cloud(config, lane)


def replay(c, trace, **kw):
    SEEN.append(sorted(trace))
    out = base.replay(c, trace, **kw)
    return {**out, "completion": out["completion"] * SCALE}
'''


def test_default_reference_is_cloud():
    cell = harness.load_cell("das2-500pm.trace1k")
    assert "reference" not in cell.traffic and cell.reference == "cloud"
    ref = harness.load_reference(cell)
    assert ref.__file__ == str(harness.BENCH / "reference" / "cloud.py")


@pytest.mark.parametrize("scale,correct", [(1.0, True), (1.01, False)])
def test_reference_named_by_traffic_is_the_one_checked(tmp_path, monkeypatch,
                                                      scale, correct):
    """A stub ``bench/reference/stub.py`` named by the traffic file gets the
    whole trace dict, and its answers are what ``correct`` is decided
    against: the reference's own answers pass, answers 1 % late fail."""
    for sub in ("drivers", "generators", "metrics", "checks", "traffic"):
        (tmp_path / sub).symlink_to(harness.BENCH / sub)
    (tmp_path / "reference").mkdir()
    (tmp_path / "reference" / "stub.py").write_text(
        STUB + f"\n\nSCALE = {scale!r}\n")
    monkeypatch.setattr(harness, "BENCH", tmp_path)
    cell = tiny_cell("das2-500pm.trace1k")
    cell.traffic["reference"] = "stub"
    line = harness.run(cell, seed=2**31 + 11, seconds=0.0, trace=False,
                       require_platform=None, log=lambda *a: None)
    stub = sys.modules["bench_reference_stub"]
    assert stub.SEEN and all(k == ["arrival", "cores", "work"]
                             for k in stub.SEEN)
    assert line["correct"] is correct, line["checks"]


# ---- the program built from what the files hold

def _explicit_cloud(config, lanes):
    """The construction ``common.engine_cloud`` replaced: a fixed list of
    ``cluster`` keys."""
    from repro.core import engine
    from repro.core.energy import MeterTopology, hvac_spec
    c = config["cluster"]
    spec, base = engine.make_cloud(
        n_pm=c["n_pm"], n_vm=c["n_vm"], pm_cores=float(c["pm_cores"]),
        perf_core=float(c["perf_core"]), net_bw=float(c["net_bw"]),
        repo_bw=float(c["repo_bw"]), image_mb=float(c["image_mb"]),
        boot_work=float(c["boot_work"]), latency_s=float(c["latency_s"]),
        max_events=int(config["max_events"]),
        meters=MeterTopology(indirect=(
            hvac_spec(config["meters"]["hvac_pue_minus_one"]),)))
    points = [dataclasses.replace(base, vm_sched=ln.vm_sched,
                                  pm_sched=ln.pm_sched,
                                  power=common.power_table(config,
                                                           ln.idle_scale))
              for ln in lanes]
    if len(points) == 1:
        return spec, points[0]
    return spec, engine.stack_params(points)


def _explicit_trace(h):
    """The construction ``common.trace_of`` replaced: ``gid`` only where
    the generator made windows."""
    from repro.core import engine
    gid = {"gid": jnp.asarray(h["gid"])} if "gid" in h else {}
    return engine.Trace(arrival=jnp.asarray(h["arrival"]),
                        cores=jnp.asarray(h["cores"]),
                        work=jnp.asarray(h["work"]), **gid)


def _same_leaves(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        assert type(x) is type(y)
        assert np.asarray(x).dtype == np.asarray(y).dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("workload", CELLS)
def test_cells_build_the_same_program_inputs(workload):
    cell = harness.load_cell(workload)
    driver = harness.load_module(harness.BENCH / "drivers"
                                 / f"{cell.driver}.py")
    wl = driver.Workload(cell, 2**31 + 7, devices=None)
    spec, params = common.engine_cloud(cell.config, wl.lane_list)
    spec0, params0 = _explicit_cloud(cell.config, wl.lane_list)
    assert spec == spec0
    _same_leaves(params, params0)
    host = wl.traces()
    inputs = [w for ws in wl.windows_host for w in ws] \
        if hasattr(wl, "windows_host") else host
    for h in inputs:
        _same_leaves(common.trace_of(h), _explicit_trace(h))


def test_every_cluster_key_reaches_the_program():
    config = harness.load_cell("das2-500pm.trace1k").config
    config = {**config, "cluster": {**config["cluster"], "vm_mem_mb": 2048,
                                    "n_vm": 512}}
    spec, params = common.engine_cloud(config, [common.Lane("firstfit",
                                                            "alwayson")])
    assert params.vm_mem_mb == 2048.0 and type(params.vm_mem_mb) is float
    assert spec.n_vm == 512 and type(spec.n_vm) is int
    with pytest.raises(TypeError, match="n_pmm"):
        common.engine_cloud({**config, "cluster": {**config["cluster"],
                                                   "n_pmm": 3}},
                            [common.Lane("firstfit", "alwayson")])


class _TraceWithMem(NamedTuple):
    """``engine.Trace`` with one more field, as a later deployment's."""
    arrival: object
    cores: object
    work: object
    gid: object = None
    mem: object = None


@pytest.mark.parametrize("workload", ["das2-500pm.trace1k",
                                      "das2-500pm.stream"])
def test_generator_array_named_like_a_trace_field_reaches_the_program(
        monkeypatch, workload):
    """A generator that makes one more array: the drivers put it into the
    trace where ``engine.Trace`` has that field, and leave out an array
    that names none."""
    from repro.core import engine
    monkeypatch.setattr(engine, "Trace", _TraceWithMem)
    cell = tiny_cell(workload)
    driver = harness.load_module(harness.BENCH / "drivers"
                                 / f"{cell.driver}.py")
    wl = driver.Workload(cell, 5, jax.devices()[:1])
    traces = wl.traces

    def with_mem():
        host = traces()
        for h in host + [w for ws in getattr(wl, "windows_host", [])
                         for w in ws]:
            h["mem"] = np.full_like(h["cores"], 4096.0)
            h["not_a_field"] = h["cores"]
        return host
    monkeypatch.setattr(wl, "traces", with_mem)
    wl.setup()
    pool = [t for item in wl.pool
            for t in (item if isinstance(item, list) else [item])]
    assert pool and all(isinstance(t, _TraceWithMem) for t in pool)
    for t in pool:
        np.testing.assert_array_equal(np.asarray(t.mem),
                                      np.full(t.cores.shape, 4096.0))
    assert (pool[0].gid is None) == (workload == "das2-500pm.trace1k")


# ---- the program's counters in each lane's answer

@pytest.fixture(scope="module")
def tiny_results():
    from repro.core import engine
    from repro.core.trace import gwa_like_trace
    spec, base = engine.make_cloud(n_pm=4, n_vm=32, pm_cores=64.0)
    trace = gwa_like_trace("das2", 60, seed=3)
    params = engine.stack_params([dataclasses.replace(base, vm_sched=v)
                                  for v in ("firstfit", "smallestfirst")])
    return {False: engine.simulate(spec, trace, base),
            True: engine.simulate_batch(spec, trace, params)}


@pytest.mark.parametrize("batched", [False, True])
def test_split_lanes_carries_every_counter(tiny_results, batched):
    from repro.core.loop.state import LoopCounters
    res = tiny_results[batched]
    n = 2 if batched else 1
    answers = common.split_lanes(jax.device_get(common.pick(res)), n,
                                 batched)
    want = jax.device_get(res.counters)
    for b, ans in enumerate(answers):
        assert set(ans["counters"]) == set(LoopCounters._fields)
        for name in LoopCounters._fields:
            x = np.asarray(getattr(want, name))
            np.testing.assert_array_equal(ans["counters"][name],
                                          x[b] if batched else x)


def test_split_lanes_takes_a_counter_it_does_not_know():
    host = {"completion": np.zeros((2, 3)), "rejected": np.zeros((2, 3)),
            "pm_energy": np.ones((2, 4)), "iaas_total": np.ones(2),
            "indirect": np.ones((2, 1)), "t_end": np.ones(2),
            "n_events": np.array([7, 9]), "overflow": np.zeros(2, bool),
            "counters": {"fill_rounds": np.array([3, 4]),
                         "new_rounds": np.array([[1, 2], [5, 6]])}}
    answers = common.split_lanes(host, 2, True)
    assert [a["counters"] for a in answers] == [
        {"fill_rounds": 3, "new_rounds": [1, 2]},
        {"fill_rounds": 4, "new_rounds": [5, 6]}]


# ---- the per-layer readers on a hand-built ctx

def _answer(n_events, **counters):
    return {"n_events": n_events, "counters": counters}


def _ctx():
    calls = [
        harness.Call(item=0, start=0.0, end=1.0, tasks=20, lanes=2,
                     events=[10, 30], dense_replays=0, failed=0, error=None,
                     answers=[_answer(10, fill_rounds=40, label_rounds=20,
                                      serve_rounds=5, small_bucket_iters=10),
                              _answer(30, fill_rounds=80, label_rounds=60,
                                      serve_rounds=15,
                                      small_bucket_iters=20)]),
        # a call that raised has no answers and is not counted
        harness.Call(item=1, start=1.0, end=2.0, tasks=20, lanes=2,
                     events=[0], dense_replays=0, failed=2,
                     error="RuntimeError: x", answers=[]),
    ]
    traced = {"stages": {"advance": 80e-6, "observe": 150e-6,
                         "vm_lifecycle": 75e-6, "pm_sched": 1e-6,
                         "pm_sched/ondemand": 9e-6,
                         "vm_sched/firstfit": 12e-6,
                         "vm_sched/smallestfirst": 8e-6},
              "entry": {"name": "repro.simulate"},
              "idle_by_label": {"repro.launch": 2.5e-3,
                                "repro.compact_check": 1.5e-3,
                                "bench.readback": 9e-3}}
    return {"calls": calls, "traced": traced}


READINGS = [("advance_us", 80.0), ("observe_us", 150.0),
            ("vm_lifecycle_us", 75.0), ("pm_sched_us", 10.0),
            ("vm_sched_us", 20.0), ("fill_rounds", 3.0),
            ("label_rounds", 2.0), ("serve_rounds", 0.5),
            ("small_bucket_share", 75.0), ("entry_idle_ms", 4.0)]


@pytest.mark.parametrize("name,value", READINGS)
def test_reader_on_a_hand_built_ctx(name, value):
    reader = harness.load_module(harness.BENCH / "metrics" / f"{name}.py")
    assert reader.read(_ctx()) == pytest.approx(value, rel=1e-12)


@pytest.mark.parametrize("name", [n for n, _ in READINGS])
def test_reader_with_nothing_to_read_returns_none(name):
    reader = harness.load_module(harness.BENCH / "metrics" / f"{name}.py")
    ctx = _ctx()
    ctx["traced"] = {"stages": {}, "entry": None, "idle_by_label": {}}
    for a in ctx["calls"][0].answers:
        del a["counters"]
    assert reader.read(ctx) is None
