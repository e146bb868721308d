"""The registry's full VM x PM policy grid on two small traces.

Policy codes are traced data, so each trace runs the whole grid as one
``simulate_batch`` call under one compile.  Every lane is checked three
ways: it equals a single ``simulate`` with its codes bit for bit, its
meter readings obey what the meter stack guarantees (DESIGN.md §3), and
every task is accounted for.

The two traces: a dense DAS-2 trace whose widest tasks do not fit a PM,
and a sparse trace of long tasks in bursts far apart, on which on-demand
switches PMs off between bursts and the migration policies move VMs.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.energy import PM_OFF, PM_RUNNING
from repro.core.trace import gwa_like_trace
from repro.sched import registry

GRID = [(vm, pm) for vm in registry.names("vm")
        for pm in registry.names("pm")]
QUEUING = ("firstfit", "smallestfirst")  # hold a request until it fits
MIGRATING = ("consolidate", "defrag", "evacuate")
PM_CORES = 8.0
BURST_GAP_S = 20_000.0
TRACES = ("das2_dense", "sparse_long")
CASES = [(t, vm, pm) for t in TRACES for vm, pm in GRID]
over_grid = pytest.mark.parametrize(
    "trace_name,vm,pm", CASES, ids=["{}-{}x{}".format(*c) for c in CASES])


def _bits(x) -> np.ndarray:
    x = np.asarray(x)
    if np.issubdtype(x.dtype, np.floating):
        return x.view({2: np.uint16, 4: np.uint32, 8: np.uint64}[x.itemsize])
    return x


@functools.cache
def _cloud():
    return engine.make_cloud(n_pm=4, n_vm=16, pm_cores=PM_CORES,
                             metering_period=300.0)


def _params(vm, pm):
    return dataclasses.replace(_cloud()[1], vm_sched=vm, pm_sched=pm)


def _sparse_long_trace() -> engine.Trace:
    """Three bursts ``BURST_GAP_S`` apart, each the consolidation pattern
    on 8-core PMs: a 4-core long task and a 3-core short one fill PM0, a
    5-core short one takes PM1, and a 3-core long one arriving later joins
    it.  Once the short ones finish, PM1 idles under one VM that fits
    beside the long task on PM0."""
    rows = []
    for k in range(3):
        t0 = BURST_GAP_S * k
        rows += [(t0, 4.0, 3000.0), (t0 + 0.01, 3.0, 300.0),
                 (t0 + 0.02, 5.0, 300.0), (t0 + 230.0, 3.0, 3000.0)]
    arrival, cores, runtime = (np.asarray(c, np.float32) for c in zip(*rows))
    return engine.Trace(arrival=jnp.asarray(arrival),
                        cores=jnp.asarray(cores),
                        work=jnp.asarray(runtime * cores))


def _trace(name: str) -> engine.Trace:
    if name == "das2_dense":
        # up to 16-core tasks on 8-core PMs: some can never be placed
        return gwa_like_trace("das2", 40, max_cores=16, seed=3)
    return _sparse_long_trace()


@pytest.fixture(scope="module")
def grid():
    """``grid(name)`` -> (trace, batched result, batched readings), one
    ``simulate_batch`` over the whole grid per trace, run once."""
    runs = {}

    def get(name):
        if name not in runs:
            spec, _ = _cloud()
            trace = _trace(name)
            params = engine.stack_params([_params(vm, pm) for vm, pm in GRID])
            res = jax.block_until_ready(
                engine.simulate_batch(spec, trace, params))
            runs[name] = (trace, res, res.readings(spec))
        return runs[name]
    return get


@over_grid
def test_lane_matches_simulate(grid, trace_name, vm, pm):
    spec, _ = _cloud()
    trace, res, readings = grid(trace_name)
    b = GRID.index((vm, pm))
    single = jax.block_until_ready(
        engine.simulate(spec, trace, _params(vm, pm)))
    for field in ("completion", "rejected", "t_end", "n_events"):
        np.testing.assert_array_equal(
            _bits(getattr(single, field)), _bits(getattr(res, field)[b]),
            err_msg=f"{field} bits diverge")
    want = single.readings(spec)
    assert set(want) == set(readings)
    for key, value in want.items():
        np.testing.assert_array_equal(
            _bits(value), _bits(readings[key][b]),
            err_msg=f"meter {key!r} bits diverge")


@over_grid
def test_meter_identities(grid, trace_name, vm, pm):
    spec, base = _cloud()
    _, res, readings = grid(trace_name)
    b = GRID.index((vm, pm))
    r = {k: np.asarray(v[b], np.float64) for k, v in readings.items()}
    total = float(r["iaas_total"])
    for key, value in r.items():
        assert np.isfinite(value).all(), key
        if key != "vm_unattributed":
            assert (value >= 0).all(), key
    # the per-VM meters never attribute more than the IaaS drew
    assert r["vm_unattributed"] >= -1e-6 * total
    # a PM's idle component never exceeds its whole draw
    assert (r["pm_idle"] <= r["pm"] * (1 + 1e-6)).all()
    # the whole-IaaS meter integrates the summed draw, the per-PM meters
    # each PM's draw: two Kahan sums of one power curve
    np.testing.assert_allclose(total, r["pm"].sum(), rtol=1e-6)
    # every PM draws between its lowest and highest state draw all along
    t_end = float(res.t_end[b])
    floor_w = float(jnp.min(base.power.p_min))
    peak_w = float(jnp.max(base.power.p_max))
    assert total >= spec.n_pm * floor_w * t_end * (1 - 1e-6)
    assert total <= spec.n_pm * peak_w * t_end * (1 + 1e-6)


@over_grid
def test_task_accounting(grid, trace_name, vm, pm):
    trace, res, _ = grid(trace_name)
    b = GRID.index((vm, pm))
    arrival = np.asarray(trace.arrival)
    cores = np.asarray(trace.cores)
    completion = np.asarray(res.completion[b])
    rejected = np.asarray(res.rejected[b])
    state = np.asarray(res.state.task_state[b])
    done = np.isfinite(completion)
    # every task ends exactly one way: rejected, or done no earlier than
    # it arrived
    np.testing.assert_array_equal(done, ~rejected)
    np.testing.assert_array_equal(
        state, np.where(rejected, engine.TASK_REJECTED, engine.TASK_DONE))
    assert (completion[done] >= arrival[done]).all()
    assert float(res.t_end[b]) >= completion[done].max(initial=0.0)
    if vm in QUEUING:
        # a queuing policy waits for room, so only a task wider than any
        # PM is turned away
        np.testing.assert_array_equal(rejected, cores > PM_CORES)


def test_sparse_trace_sleeps_pms_and_migrates(grid):
    """The sparse trace keeps doing what it is in the grid for: on-demand
    switches PMs off between bursts, and a migration policy moves VMs."""
    spec, _ = _cloud()
    trace, res, readings = grid("sparse_long")
    lay = spec.layout
    # only a live migration sends bytes out of a PM's NIC
    sent = np.asarray(res.state.processed)[:, lay.netout0:lay.repo_out]
    moved = {pm for (vm, pm), s in zip(GRID, sent.sum(axis=1)) if s > 0}
    assert moved & set(MIGRATING), moved
    assert not moved - set(MIGRATING), moved
    # between the first and second burst
    gap = 0.75 * BURST_GAP_S
    for pm, pstate in (("ondemand", PM_OFF), ("alwayson", PM_RUNNING)):
        mid = engine.simulate(spec, trace, _params("firstfit", pm),
                              t_stop=gap)
        assert (np.asarray(mid.state.pstate) == pstate).all(), pm
    energy = dict(zip(GRID, np.asarray(readings["iaas_total"])))
    assert (energy[("firstfit", "ondemand")]
            < 0.5 * energy[("firstfit", "alwayson")])
