"""The extended plain reference against the program's engine.simulate on
the CPU, at a tiny size, for every VM x PM scheduler pair the cells run."""
import dataclasses

import jax
import numpy as np
import pytest

from bench.drivers import common
from bench.generators import gwa
from bench.reference import cloud as reference
from bench.reference import compare
from bench.reference.cloud import Cloud, maxmin, simulate

LIMITS = {"fate_mismatch": {"limit": 0}, "completion_rel": {"limit": 1e-4},
          "pm_energy_rel": {"limit": 1e-4},
          "energy_total_rel": {"limit": 1e-4}, "clock_rel": {"limit": 1e-4}}


@pytest.fixture(scope="module")
def engine_runs():
    from repro.core import engine
    from repro.experiments.pareto import power_scale_grid
    spec, base = engine.make_cloud(n_pm=4, n_vm=32, pm_cores=64.0)
    lanes = [common.Lane(v, p, s) for v in common.VM_POLICIES
             for p in common.PM_POLICIES for s in (0.8,)]
    params = engine.stack_params([dataclasses.replace(
        base, vm_sched=ln.vm_sched, pm_sched=ln.pm_sched,
        power=power_scale_grid([ln.idle_scale])[0]) for ln in lanes])
    out = {}
    for seed in (1, 2):
        tr = gwa.trace("das2", 150, seed=seed, max_cores=64)
        # a burst every few tasks, so that queues form and policies differ
        tr["arrival"] = (tr["arrival"] // 400 * 400).astype(np.float32)
        res = engine.simulate_batch(spec, engine.Trace(
            **{k: np.asarray(v) for k, v in tr.items()}), params)
        host = jax.device_get(common.pick(res))
        out[seed] = (tr, lanes, common.split_lanes(host, len(lanes), True))
    return out


@pytest.mark.parametrize("vm", common.VM_POLICIES)
@pytest.mark.parametrize("pm", common.PM_POLICIES)
@pytest.mark.parametrize("seed", (1, 2))
def test_reference_matches_engine(engine_runs, vm, pm, seed):
    tr, lanes, answers = engine_runs[seed]
    b = lanes.index(common.Lane(vm, pm, 0.8))
    cloud = Cloud(n_pm=4, n_vm=32, vm_sched=vm, pm_sched=pm, idle_scale=0.8)
    refs = compare.References(reference, {0: (cloud, tr)})
    got = compare.lane_numbers(answers[b], 0, refs, LIMITS)
    assert compare.passes(got, LIMITS), got


def test_policies_differ_on_bursts(engine_runs):
    tr, lanes, _ = engine_runs[1]
    fates = {}
    for vm in common.VM_POLICIES:
        r = simulate(Cloud(n_pm=4, n_vm=32, vm_sched=vm,
                           pm_sched="ondemand"), **tr)
        fates[vm] = (r["rejected"].sum(), np.nansum(
            np.where(np.isfinite(r["completion"]), r["completion"], 0)))
    assert fates["nonqueuing"][0] > 0
    assert fates["firstfit"][1] != fates["smallestfirst"][1]


def test_maxmin_progressive_filling():
    # two flows share a 10-unit link; one is capped at 2 -> 2 and 8
    r = maxmin([("a", "x", 2.0), ("a", "y", 1e30)],
               {"a": 10.0, "x": 100.0, "y": 100.0})
    np.testing.assert_allclose(r, [2.0, 8.0])


def test_vm_slot_limit_reports_overflow():
    tr = {"arrival": np.zeros(5, np.float32), "cores": np.ones(5, np.float32),
          "work": np.full(5, 100.0, np.float32)}
    r = simulate(Cloud(n_pm=1, n_vm=3), **tr)
    assert r["overflow"] and np.isfinite(r["completion"]).sum() == 5
