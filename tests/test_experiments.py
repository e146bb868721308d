"""The sweep-experiment layer (repro.experiments): device sharding,
Pareto frontiers, trace ensembles, scheduler tournaments.

Run with ``XLA_FLAGS=--xla_force_host_platform_device_count=2`` to exercise
the real ``shard_map`` path in-process; without it the same tests cover the
single-device fallback, and a subprocess test still forces the 2-device
topology either way (the parent pytest process must keep its default device
count: a forced host topology is fixed at jax start-up).
"""
from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.energy import PowerStateTable
from repro.core.trace import synthetic_trace
from repro.experiments import ensemble, pareto, shard, tournament

SRC = str(Path(__file__).resolve().parent.parent / "src")


def _cloud(**kw):
    base = dict(n_pm=2, n_vm=16, pm_cores=4.0, net_bw=100.0, repo_bw=200.0,
                image_mb=100.0, boot_work=4.0, latency_s=0.0)
    base.update(kw)
    return engine.make_cloud(**base)


def _sweep_inputs(n_points=4):
    spec, base = _cloud()
    trace = synthetic_trace(20, parallel=5, seed=0)
    points = [dataclasses.replace(base,
                                  net_bw=jnp.float32(50.0 + 25.0 * i),
                                  boot_work=jnp.float32(2.0 + i))
              for i in range(n_points)]
    return spec, trace, points


def _assert_results_equal(a: engine.CloudResult, b: engine.CloudResult):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------- sharding

def test_sharded_matches_unsharded_bitwise():
    """shard_map over the batch axis must be bit-identical to the plain
    vmap — vmap lanes are independent, sharding only moves them.  (With one
    device this exercises the documented fallback; the subprocess test
    below always exercises the 2-device mesh.)"""
    spec, trace, points = _sweep_inputs(4)
    params = engine.stack_params(points)
    ref = engine.simulate_batch(spec, trace, params)
    got = shard.simulate_batch_sharded(spec, trace, params)
    _assert_results_equal(ref, got)
    # the engine-side entry point is the same path
    _assert_results_equal(ref, engine.simulate_batch_sharded(
        spec, trace, params))


def test_sharded_two_devices_subprocess():
    """Forced 2-device CPU topology: the real shard_map program, bitwise
    equal to the unsharded result, using both devices.  The provider-only
    solves are taken per shard, by fewer lanes together than in the
    unsharded batch: a lane counts at least as many."""
    code = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.core import engine
from repro.core.trace import synthetic_trace
from repro.experiments import shard

def same(ref, got):
    ref_own = np.asarray(ref.counters.provider_only_solves)
    got_own = np.asarray(got.counters.provider_only_solves)
    assert (got_own >= ref_own).all(), (got_own, ref_own)
    drop = lambda r: r._replace(counters=r.counters._replace(
        provider_only_solves=None))
    for a, b in zip(jax.tree.leaves(drop(ref)), jax.tree.leaves(drop(got))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

assert jax.device_count() == 2, jax.devices()
spec, base = engine.make_cloud(n_pm=2, n_vm=16, pm_cores=4.0, net_bw=100.0,
                               repo_bw=200.0, image_mb=100.0, boot_work=4.0,
                               latency_s=0.0)
trace = synthetic_trace(20, parallel=5, seed=0)
def points(n):
    return [dataclasses.replace(base, net_bw=jnp.float32(50.0 + 25.0 * i),
                                boot_work=jnp.float32(2.0 + i))
            for i in range(n)]

params = engine.stack_params(points(4))
assert shard.shard_count(4) == 2
ref = engine.simulate_batch(spec, trace, params)
got = shard.simulate_batch_sharded(spec, trace, params)
same(ref, got)
# the result really lives on the 2-device mesh
assert len(got.t_end.sharding.device_set) == 2, got.t_end.sharding

# prime batch: pad-and-mask keeps both devices busy, valid rows bitwise
params5 = engine.stack_params(points(5))
assert shard.shard_count(5) == 2 and shard.pad_rows(5, 2) == 1
ref5 = engine.simulate_batch(spec, trace, params5)
got5 = shard.simulate_batch_sharded(spec, trace, params5)
assert got5.t_end.shape == (5,)
same(ref5, got5)
print("SHARDED_BITWISE_OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=560)
    assert "SHARDED_BITWISE_OK" in r.stdout, r.stdout + r.stderr[-2000:]


def test_shard_count_and_pad_rows():
    assert shard.shard_count(8, 4) == 4
    assert shard.shard_count(6, 4) == 4   # pad-and-mask: full mesh
    assert shard.shard_count(7, 4) == 4   # prime batch -> padded, not 1
    assert shard.shard_count(2, 8) == 2   # never more shards than points
    assert shard.shard_count(1, 8) == 1   # single point -> vmap fallback
    assert shard.pad_rows(8, 4) == 0
    assert shard.pad_rows(7, 4) == 1
    assert shard.pad_rows(6, 4) == 2
    assert shard.pad_rows(3, 2) == 1


def test_prime_batch_sharded_matches_unsharded_bitwise():
    """Pad-and-mask path: a prime batch size still matches the plain vmap
    on its valid rows (with one in-process device this exercises the
    fallback; the subprocess test exercises the padded 2-device mesh)."""
    spec, trace, points = _sweep_inputs(5)
    params = engine.stack_params(points)
    ref = engine.simulate_batch(spec, trace, params)
    got = shard.simulate_batch_sharded(spec, trace, params)
    _assert_results_equal(ref, got)


def test_batch_size_validates():
    spec, trace, points = _sweep_inputs(3)
    params = engine.stack_params(points)
    assert shard.batch_size(spec, trace, params) == 3
    with pytest.raises(ValueError, match="stack_params"):
        shard.batch_size(spec, trace, points[0])


# ------------------------------------------------------------------ pareto

def test_pareto_front_dominance_invariant():
    """Frontier points are mutually non-dominated; every off-frontier point
    is strictly dominated by some frontier point."""
    rng = np.random.RandomState(3)
    costs = rng.uniform(0.0, 1.0, size=(64, 2))
    mask = pareto.pareto_front(costs)
    assert mask.any()
    front = costs[mask]
    for i in range(costs.shape[0]):
        dominated = ((front <= costs[i]).all(axis=1)
                     & (front < costs[i]).any(axis=1))
        if mask[i]:
            assert not dominated.any(), f"frontier point {i} is dominated"
        else:
            assert dominated.any(), (
                f"non-frontier point {i} not dominated by the frontier")


def test_pareto_front_duplicates_and_single():
    # identical points dominate nothing: both stay on the frontier
    mask = pareto.pareto_front([[1.0, 2.0], [1.0, 2.0], [2.0, 3.0]])
    assert mask.tolist() == [True, True, False]
    assert pareto.pareto_front([[5.0, 5.0]]).tolist() == [True]


def test_pareto_sweep_end_to_end():
    spec, _, _ = _sweep_inputs()
    # sparse long-gap trace: always-on burns idle power between arrivals,
    # on-demand pays a boot delay instead — a genuine energy/makespan
    # trade-off, so both cells must survive on the frontier
    trace = engine.Trace(
        arrival=jnp.asarray([0.0, 4000.0, 8000.0], jnp.float32),
        cores=jnp.asarray([4.0, 4.0, 4.0], jnp.float32),
        work=jnp.asarray([800.0, 800.0, 800.0], jnp.float32))
    base = engine.CloudParams.for_spec(spec, pm_cores=4.0, boot_work=4.0)
    points = pareto.param_grid(base, pm_sched=["alwayson", "ondemand"])
    labels = pareto.grid_labels(pm_sched=["alwayson", "ondemand"])
    res = pareto.sweep(spec, trace, points, labels=labels)
    assert len(res.rows) == 2
    by = {r["pm_sched"]: r for r in res.rows}
    assert by["alwayson"]["energy_kwh"] > by["ondemand"]["energy_kwh"]
    assert by["alwayson"]["makespan_s"] < by["ondemand"]["makespan_s"]
    assert all(r["on_frontier"] for r in res.rows)
    assert sorted(res.frontier.tolist()) == [0, 1]
    # frontier rows always contain the minimal-energy point
    emin = min(res.rows, key=lambda r: r["energy_kwh"])
    assert emin["on_frontier"]


def test_param_grid_shapes_and_validation():
    spec, base = _cloud()
    pts = pareto.param_grid(base, net_bw=[1.0, 2.0], boot_work=[3.0, 4.0, 5.0])
    assert len(pts) == 6
    assert float(pts[0].net_bw) == 1.0 and float(pts[5].boot_work) == 5.0
    labels = pareto.grid_labels(net_bw=[1.0, 2.0], boot_work=[3.0, 4.0, 5.0])
    assert labels[5] == {"net_bw": 2.0, "boot_work": 5.0}
    with pytest.raises(TypeError, match="unknown CloudParams"):
        pareto.param_grid(base, nonsense=[1])


# ---------------------------------------------------------------- ensemble

def test_ensemble_reproducible_and_sane():
    """Fixed seeds => bit-identical stats across runs; CI half-widths are
    non-negative and the mean lies inside the replicate range."""
    spec, base = _cloud(n_pm=2, n_vm=64, pm_cores=64.0)
    traces = ensemble.gwa_ensemble("das2", 24, 4, pm_cores=64.0, seed0=5)
    points = [base, dataclasses.replace(base, pm_sched="ondemand")]
    labels = [{"pm_sched": "alwayson"}, {"pm_sched": "ondemand"}]
    r1 = ensemble.run_ensemble(spec, traces, points, labels=labels)
    r2 = ensemble.run_ensemble(spec, traces, points, labels=labels)
    assert r1.rows == r2.rows
    assert len(r1.rows) == 2
    for row in r1.rows:
        assert row["replicates"] == 4
        for m in ("energy_kwh", "job_kwh", "idle_kwh", "makespan_s"):
            assert row[f"{m}_std"] >= 0.0
            assert row[f"{m}_ci"] >= 0.0
    # per-policy means must match the per-replicate engine results: policy
    # p's replicates occupy batch rows [p*R, (p+1)*R)
    energies = np.asarray(
        r1.result.readings(spec)["iaas_total"], np.float64) / 3.6e6
    for p, row in enumerate(r1.rows):
        v = energies[p * 4:(p + 1) * 4]
        np.testing.assert_allclose(row["energy_kwh_mean"], v.mean(),
                                   rtol=1e-12)
        assert v.min() <= row["energy_kwh_mean"] <= v.max()


def test_ensemble_validates_inputs():
    spec, base = _cloud()
    traces = ensemble.gwa_ensemble("das2", 10, 2, pm_cores=4.0)
    with pytest.raises(ValueError, match="confidence"):
        ensemble.run_ensemble(spec, traces, [base], confidence=0.5)
    with pytest.raises(ValueError, match="replicates"):
        ensemble.run_ensemble(spec, traces[:1], [base])


# -------------------------------------------------------------- tournament

def test_tournament_matches_sequential_cells():
    """The generalised grid gives the same per-cell numbers as sequential
    single-scenario simulate calls."""
    spec, trace, _ = _sweep_inputs()
    base = engine.CloudParams.for_spec(spec, pm_cores=4.0, boot_work=4.0)
    res = tournament.run(spec, trace, base)
    # full registry grid by default: 3 VM x 5 PM policies
    assert len(res.rows) == 15
    for row in res.rows:
        single = engine.simulate(spec, trace, params=dataclasses.replace(
            base, vm_sched=row["vm_sched"], pm_sched=row["pm_sched"]))
        np.testing.assert_allclose(
            row["energy_kwh"],
            float(single.meters.total.energy) / 3.6e6, rtol=1e-6)
        np.testing.assert_allclose(row["makespan_s"], float(single.t_end),
                                   rtol=1e-6)
        assert row["jobs_rejected"] == int(single.rejected.sum())


def test_tournament_custom_grid_and_codes():
    spec, trace, _ = _sweep_inputs()
    base = engine.CloudParams.for_spec(spec, pm_cores=4.0)
    grid = tournament.scheduler_grid(("firstfit",), (0, 1))
    res = tournament.run(spec, trace, base, schedulers=grid)
    assert [(r["vm_sched"], r["pm_sched"]) for r in res.rows] == [
        ("firstfit", "alwayson"), ("firstfit", "ondemand")]


def test_evaluate_schedulers_energy_ordering():
    """On-demand PM scheduling must not use more energy than always-on for
    a sparse *long-running* job trace (the paper's central energy
    argument; for very short traces boot-cycle energy legitimately wins —
    that regime is covered by the benchmark, not asserted here)."""
    # two 256-core jobs of about 4000 s each on pods of 256 cores: power
    # is linear from 75 W idle to 200 W peak per core, off at 5 % of idle
    trace = engine.Trace(
        arrival=jnp.asarray([2.7440674, 3.5759468], jnp.float32),
        cores=jnp.asarray([256.0, 256.0], jnp.float32),
        work=jnp.asarray([1_024_000.0, 409_600.0], jnp.float32))
    power = PowerStateTable.simple(
        off_w=0.05 * 75.0 * 256, on_w=75.0 * 256, min_w=75.0 * 256,
        max_w=200.0 * 256, off_w2=75.0 * 256, boot_s=120.0, shutdown_s=30.0)
    params = engine.CloudParams(
        pm_cores=256.0, perf_core=1.0, image_mb=10_000.0, net_bw=2_000.0,
        repo_bw=8_000.0, boot_work=60.0 * 256, power=power)
    spec = engine.CloudSpec(n_pm=4, n_vm=8)
    table = tournament.run(spec, trace, params).rows
    by = {(r["vm_sched"], r["pm_sched"]): r for r in table}
    # full registry matrix (3 VM x 5 PM), batched through one compile
    assert len(by) == 15
    for row in table:
        assert row["energy_kwh"] > 0
        if row["vm_sched"] == "nonqueuing" and row["pm_sched"] != "alwayson":
            # pods boot on demand, so a non-queuing cloud rejects arrivals
            # that land before any pod is accepting — a legitimate policy
            # outcome, not a bug
            continue
        assert row["jobs_done"] == 2, row
    assert (by[("firstfit", "ondemand")]["energy_kwh"]
            <= by[("firstfit", "alwayson")]["energy_kwh"] * 1.001)
    # the migration policies inherit on-demand's wake/sleep rules: never worse
    for pm in ("consolidate", "defrag", "evacuate"):
        assert (by[("firstfit", pm)]["energy_kwh"]
                <= by[("firstfit", "ondemand")]["energy_kwh"] * 1.001)
