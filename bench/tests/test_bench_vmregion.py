"""The VM region deployment (azure-vm-256pm.region): its generator, its
plain reference (``bench/reference/vmregion.py``), and the program's
memory dimension and utilisation caps against that reference on the CPU,
at 8 PMs x 8 cores x 32 GB with 32 VM slots, where memory binds."""
import copy
import dataclasses

import jax
import numpy as np
import pytest

from bench import harness
from bench.drivers import common
from bench.generators import azure, gwa
from bench.reference import cloud as cloud_ref
from bench.reference import compare
from bench.reference import vmregion

LIMITS = {"fate_mismatch": {"limit": 0}, "completion_rel": {"limit": 1e-4},
          "pm_energy_rel": {"limit": 1e-4},
          "energy_total_rel": {"limit": 1e-4}, "clock_rel": {"limit": 1e-4}}
SMALL = dict(n_pm=8, n_vm=32, pm_cores=8.0, pm_mem=32.0)
LANES = [common.Lane(v, p) for v in ("firstfit", "smallestfirst")
         for p in ("alwayson", "ondemand")]


def small_trace(seed: int, T: int = 120) -> dict:
    """VM requests in bursts every 300 s, 4 GB per core on average on
    hosts of 4 GB per core: memory binds the placement often."""
    rng = np.random.default_rng(seed)
    cores = rng.choice([1.0, 2.0, 4.0], T, p=[0.5, 0.3, 0.2])
    util = np.clip(rng.beta(0.6, 2.4, T), 0.01, 1.0)
    life = rng.lognormal(np.log(600.0), 1.0, T)
    f32 = np.float32
    return {"arrival": (np.sort(rng.uniform(0, 3000, T)) // 300 * 300)
            .astype(f32),
            "cores": cores.astype(f32),
            "work": (life * util * cores).astype(f32),
            "mem": (cores * rng.choice([2.0, 4.0, 8.0], T,
                                       p=[0.25, 0.5, 0.25])).astype(f32),
            "util": util.astype(f32)}


def _region(lane) -> vmregion.Region:
    return vmregion.Region(**SMALL, vm_sched=lane.vm_sched,
                           pm_sched=lane.pm_sched)


def _check(answer, lane, trace):
    refs = compare.References(vmregion, {0: (_region(lane), trace)})
    got = compare.lane_numbers(answer, 0, refs, LIMITS)
    assert compare.passes(got, LIMITS), (lane, got)


# ---- the generator

@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_generator_is_seeded_and_shaped(seed):
    fam = azure.FAMILIES["azure-256pm"]
    a = azure.trace("azure-256pm", 4096, seed=seed, max_cores=64)
    b = azure.trace("azure-256pm", 4096, seed=seed, max_cores=64)
    c = azure.trace("azure-256pm", 4096, seed=seed + 1, max_cores=64)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["work"], c["work"])
    assert set(a) == {"arrival", "cores", "work", "mem", "util"}
    assert all(v.dtype == np.float32 and v.shape == (4096,)
               for v in a.values())
    assert (a["arrival"][:fam.live] == 0).all()
    assert (a["arrival"][fam.live:] > 0).all()
    assert (np.diff(a["arrival"]) >= 0).all()
    assert set(np.unique(a["cores"])) <= {1, 2, 4, 8, 16}
    assert set(np.unique(a["mem"] / a["cores"])) <= {2, 4, 8}
    assert (a["util"] >= np.float32(0.01)).all() and (a["util"] <= 1).all()
    life = a["work"] / (a["util"] * a["cores"])
    assert life.max() <= fam.lifetime_cap_s * (1 + 1e-5)
    # long-running VMs dominate the live population, short ones arrive
    assert np.median(life[:fam.live]) > 3600
    assert np.median(life[fam.live:]) < np.median(life[:fam.live])


def test_generator_rate_keeps_the_population():
    fam = azure.FAMILIES["azure-256pm"]
    a = azure.trace("azure-256pm", 4096, seed=3, max_cores=64)
    span = float(a["arrival"][-1])
    rate = fam.live / azure.mean_lifetime(fam)
    # from the daily trough the mean rate over the first hours is lower
    assert 0.5 * rate < 1024 / span < rate


# ---- the reference

def test_water_fill_is_progressive_filling():
    rng = np.random.default_rng(0)
    for _ in range(100):
        n, hosts = rng.integers(1, 20), rng.integers(1, 4)
        host = rng.integers(0, hosts, n)
        cap = rng.uniform(0, 5, n) * (rng.random(n) < 0.9)
        capacity = rng.uniform(0, 20, hosts)
        flows = [(("cpu", int(h)), ("vm", i), 1e30)
                 for i, h in enumerate(host)]
        caps = {("cpu", h): capacity[h] for h in range(hosts)}
        caps.update({("vm", i): cap[i] for i in range(n)})
        np.testing.assert_allclose(
            vmregion.water_fill(host, cap, capacity),
            cloud_ref.maxmin(flows, caps), rtol=1e-9, atol=1e-12)


@pytest.mark.parametrize("vm", common.VM_POLICIES)
@pytest.mark.parametrize("pm", common.PM_POLICIES)
def test_reference_is_cloud_without_memory_and_caps(vm, pm):
    """With memory that never binds and every VM at its full cores, the
    region is the cloud of ``cloud.py``, step for step."""
    tr = gwa.trace("das2", 150, seed=1, max_cores=64)
    tr["arrival"] = (tr["arrival"] // 400 * 400).astype(np.float32)
    base = cloud_ref.Cloud(n_pm=4, n_vm=32, vm_sched=vm, pm_sched=pm,
                           idle_scale=0.8)
    want = cloud_ref.simulate(base, **tr)
    got = vmregion.simulate(
        vmregion.Region(**dataclasses.asdict(base), pm_mem=1e9),
        tr["arrival"], tr["cores"], tr["work"], np.zeros(150), np.ones(150))
    assert got["steps"] == want["steps"]
    for k in ("completion", "pm_energy"):
        np.testing.assert_allclose(got[k], want[k], rtol=1e-12)
    np.testing.assert_array_equal(got["rejected"], want["rejected"])


def test_reference_rejects_what_no_pm_can_hold():
    tr = {k: v[:4] for k, v in small_trace(1).items()}
    tr["mem"][1] = 33.0
    tr["cores"][2] = 9.0
    out = vmregion.replay(_region(LANES[0]), tr)
    np.testing.assert_array_equal(out["rejected"], [False, True, True,
                                                    False])


# ---- the program against the reference

@pytest.fixture(scope="module")
def program():
    from repro.core import engine
    spec, base = engine.make_cloud(**SMALL)
    params = engine.stack_params([
        dataclasses.replace(base, vm_sched=ln.vm_sched, pm_sched=ln.pm_sched)
        for ln in LANES])
    return engine, spec, base, params


@pytest.mark.parametrize("seed", [1, 2])
def test_simulate_matches_reference(program, seed):
    engine, spec, base, _ = program
    tr = small_trace(seed)
    res = engine.simulate(spec, engine.Trace(**tr), base)
    [ans] = common.split_lanes(jax.device_get(common.pick(res)), 1, False)
    _check(ans, LANES[0], tr)
    assert ans["counters"]["mem_bound"] > 0
    assert ans["counters"]["fill_truncated"] == 0


@pytest.mark.parametrize("seed", [1, 3])
def test_simulate_batch_matches_reference(program, seed):
    engine, spec, _, params = program
    tr = small_trace(seed)
    res = engine.simulate_batch(spec, engine.Trace(**tr), params)
    answers = common.split_lanes(jax.device_get(common.pick(res)),
                                 len(LANES), True)
    for lane, ans in zip(LANES, answers):
        _check(ans, lane, tr)
        assert ans["counters"]["fill_truncated"] == 0
    assert all(a["counters"]["mem_bound"] > 0 for a in answers)


def test_stream_equals_simulate_with_memory(program):
    from repro.core.trace import chunk_trace
    engine, spec, base, _ = program
    tr = engine.Trace(**small_trace(2))
    want = engine.simulate(spec, tr, base)
    # memory-bound queues hold many tasks: a slot pool of the whole trace
    got = engine.simulate_stream(spec, chunk_trace(tr, 40), base,
                                 n_slots=120)
    assert not bool(got.overflow)
    for name in ("completion", "rejected", "t_end", "n_events"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(want, name)), name)
    for a, b in zip(jax.tree.leaves(got.meters), jax.tree.leaves(want.meters)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---- the cell through the harness, cut to a CPU's size

def test_region_cell_at_a_tiny_size(monkeypatch):
    fam = azure.FAMILIES["azure-256pm"]
    monkeypatch.setitem(azure.FAMILIES, "azure-256pm",
                        dataclasses.replace(fam, live=48))
    cell = harness.load_cell("azure-vm-256pm.region")
    assert cell.reference == "vmregion"
    cell.config = copy.deepcopy(cell.config)
    cell.config["cluster"].update(n_pm=8, n_vm=128)
    cell.traffic = {**cell.traffic, "n_tasks": 80, "trace_tasks": 60}
    driver = harness.load_module(harness.BENCH / "drivers" / "simulate.py")
    wl = driver.Workload(cell, 2**31 + 3, jax.devices()[:1])
    # the driver loads the generator file afresh: hand it the cut family
    monkeypatch.setattr(wl, "traces", lambda: [
        azure.trace("azure-256pm", 80, seed=wl.seed + j, max_cores=64)
        for j in range(2)])
    wl.setup()
    wl.warm()
    calls = [wl.call(0), wl.call(1)]
    assert all(c.failed == 0 and c.dense_replays == 0 for c in calls)
    checks = compare.check(cell.checks, wl.reference,
                           wl.reference_jobs(calls), calls)
    assert checks["correct"], checks["numbers"]
    ctx = {"calls": calls}
    for name in ("dense_pass_share", "live_flows", "mem_bound_share"):
        reader = harness.load_module(harness.BENCH / "metrics"
                                     / f"{name}.py")
        assert reader.read(ctx) is not None, name


# ---- the new readers on a hand-built ctx

def _ctx(counters=True):
    def answer(n, **c):
        return {"n_events": n, "counters": c if counters else {}}
    calls = [harness.Call(
        item=0, start=0.0, end=1.0, tasks=40, lanes=2, events=[10, 30],
        dense_replays=0, failed=0, error=None,
        answers=[answer(10, dense_iters=5, live_flows=1000, mem_bound=2),
                 answer(30, dense_iters=15, live_flows=3000, mem_bound=6)])]
    return {"calls": calls}


@pytest.mark.parametrize("name,value", [("dense_pass_share", 50.0),
                                        ("live_flows", 100.0),
                                        ("mem_bound_share", 20.0)])
def test_region_readers(name, value):
    reader = harness.load_module(harness.BENCH / "metrics" / f"{name}.py")
    assert reader.read(_ctx()) == pytest.approx(value, rel=1e-12)
    assert reader.read(_ctx(counters=False)) is None
