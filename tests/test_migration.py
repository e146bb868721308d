"""Live migration + the in-loop consolidation PM scheduler (PR 4).

Covers the ISSUE-4 satellite list: work conservation across
suspend-transfer/resume (``vm_saved_pr``), Eq. 6 attribution during the
migration window, the consolidation-vs-ondemand energy ordering on a
sparse trace, and the masked-policy contracts (consolidate == ondemand
bitwise when the trigger can never fire; batched == sequential cells).

The staged-pipeline refactor itself was verified bitwise against the
pre-refactor HEAD offline (every VM x PM scheduler combination on seed
traces, all meter readings — see CHANGES.md PR 4); the tests here pin the
behaviours that must keep holding without access to the old monolith.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as eng
from repro.core import fairshare, loop
from repro.core import machine as mc
from repro.core.arrays import KIND_HIDDEN
from repro.core.energy import PM_OFF, PM_RUNNING, PM_SWITCHING_OFF
from repro.core.loop.state import KIND_MIGRATE, LoopCounters


def _answers(res):
    """The result's leaves without its loop counters: policies that give
    the same answers may still open their event gates differently."""
    return jax.tree.leaves(res._replace(counters=None))


def _cloud(**kw):
    base = dict(n_pm=2, n_vm=16, pm_cores=4.0, net_bw=100.0, repo_bw=200.0,
                image_mb=100.0, boot_work=4.0, latency_s=0.0)
    base.update(kw)
    return eng.make_cloud(**base)


def _trace(arrival, cores, runtime):
    arrival = jnp.asarray(arrival, jnp.float32)
    cores = jnp.asarray(cores, jnp.float32)
    runtime = jnp.asarray(runtime, jnp.float32)
    return eng.Trace(arrival=arrival, cores=cores, work=runtime * cores)


def _consolidation_trace():
    """2 PMs x 100 cores.  A(60c, long) + C(35c, medium) fill PM0;
    B(70c, short) -> PM1; D(25c, long) arrives while PM0 has only 5 free
    cores -> PM1.  After B and C finish, PM1 hosts only D (idle-dominated)
    while PM0 has room: a consolidation opportunity on-demand cannot
    exploit."""
    return eng.Trace(
        arrival=jnp.asarray([0.0, 0.01, 0.02, 230.0], jnp.float32),
        cores=jnp.asarray([60.0, 35.0, 70.0, 25.0], jnp.float32),
        work=jnp.asarray([60 * 2000.0, 35 * 200.0, 70 * 200.0, 25 * 2000.0],
                         jnp.float32))


def _consolidation_cloud(pm_sched):
    return eng.make_cloud(n_pm=2, n_vm=8, pm_cores=100.0, pm_sched=pm_sched)


# ------------------------------------------------------- work conservation

def test_migration_work_conservation_via_saved_pr():
    """Suspend-transfer/resume must lose no task work: the saved remaining
    work equals the flow state at suspension, and completion shifts by
    exactly the memory-transfer pause (1024 MB over the 100 MB/s NIC)."""
    spec, params = _cloud(n_pm=2)
    tr = _trace([0.0], [2.0], [50.0])
    base = eng.simulate(spec, tr, params=params)
    res1 = eng.simulate(spec, tr, params=params, t_stop=10.0)
    st = eng.start_migration(spec, params, res1.state, 0, 1)
    assert float(st.vm_saved_pr[0]) == float(res1.state.f_pr[0])
    res2 = eng.simulate(spec, tr, params=params, state=st)
    assert int(res2.state.task_state[0]) == eng.TASK_DONE
    np.testing.assert_allclose(float(res2.completion[0]),
                               float(base.completion[0]) + 1024.0 / 100.0,
                               rtol=1e-4)
    # delivered CPU work is conserved: boot + task core-seconds, whether
    # they were served by one host or split across the migration
    lay = spec.layout
    cpu = slice(lay.cpu0, lay.cpu0 + spec.n_pm)
    np.testing.assert_allclose(
        float(np.asarray(base.state.processed)[cpu].sum()),
        float(np.asarray(res2.state.processed)[cpu].sum()), rtol=1e-5)
    np.testing.assert_allclose(
        float(np.asarray(res2.state.processed)[cpu].sum()),
        4.0 + 100.0, rtol=1e-4)  # boot_work + work
    # both hosts really served a share
    assert (np.asarray(res2.state.processed)[cpu] > 1.0).all()


# ------------------------------------------- Eq. 6 during the migration

def test_eq6_reconstruction_holds_during_migration_window():
    """Mid-transfer the VM is network-coupled: it draws nothing (its meter
    is frozen) and the dependent-meter identity VM-sum + unattributed ==
    whole-IaaS keeps holding at every probe point."""
    spec, params = _cloud(n_pm=2)
    tr = _trace([0.0], [2.0], [50.0])
    res1 = eng.simulate(spec, tr, params=params, t_stop=10.0)
    st = eng.start_migration(spec, params, res1.state, 0, 1)
    vm_at_suspend = float(res1.meters.vm.energy[0])
    for t_probe in (12.0, 16.0, 20.0):  # transfer spans [10, 20.24]
        # simulate() donates its state argument — each probe gets a copy
        res = eng.simulate(spec, tr, params=params,
                           state=jax.tree.map(jnp.copy, st), t_stop=t_probe)
        rd = res.readings(spec)
        assert np.asarray(res.state.vstage)[0] == mc.VM_MIGRATING
        np.testing.assert_allclose(float(rd["vm"][0]), vm_at_suspend,
                                   rtol=1e-5)
        np.testing.assert_allclose(
            float(jnp.sum(rd["vm"])) + float(rd["vm_unattributed"]),
            float(rd["iaas_total"]), rtol=1e-5)


def test_pm_idle_meter_reads_state_baseline():
    """The new per-PM idle-component meter integrates p_min over time —
    the live signal the consolidation policy watches."""
    spec, params = _cloud(n_pm=1)
    res = eng.simulate(spec, _trace([0.0], [4.0], [10.0]), params=params)
    rd = res.readings(spec)
    np.testing.assert_allclose(float(rd["pm_idle"][0]),
                               368.8 * float(res.t_end), rtol=1e-4)
    # idle + attributed-variable never exceeds the direct meter
    assert float(rd["pm_idle"][0]) <= float(rd["pm"][0]) + 1e-3


# ----------------------------------------------------- consolidation policy

def test_consolidation_beats_ondemand_on_sparse_trace():
    tr = _consolidation_trace()
    res = {}
    for pm in ("alwayson", "ondemand", "consolidate"):
        spec, params = _consolidation_cloud(pm)
        r = eng.simulate(spec, tr, params=params)
        assert (np.asarray(r.state.task_state) == eng.TASK_DONE).all(), pm
        res[pm] = r.readings(spec)
    e = {k: float(v["iaas_total"]) for k, v in res.items()}
    # migrating D off PM1 lets the donor power down for the long tail
    assert e["consolidate"] < e["ondemand"] < 1.05 * e["alwayson"], e
    assert e["consolidate"] < 0.85 * e["ondemand"], e
    # the shed waste shows up in the unattributed-idle reading
    idle = {k: float(v["vm_unattributed"]) for k, v in res.items()}
    assert idle["consolidate"] < idle["alwayson"], idle


def test_consolidation_migrates_and_powers_donor_down():
    tr = _consolidation_trace()
    spec, params = _consolidation_cloud("consolidate")
    mid = eng.simulate(spec, tr, params=params, t_stop=600.0)
    # D's VM resumed on PM0; the donor PM1 is draining or already off
    d_vm = int(np.asarray(mid.state.task_vm)[3])
    assert d_vm >= 0
    assert int(np.asarray(mid.state.vm_host)[d_vm]) == 0
    assert int(np.asarray(mid.state.vstage)[d_vm]) == mc.VM_RUNNING
    assert int(np.asarray(mid.state.pstate)[1]) in (PM_SWITCHING_OFF, PM_OFF)
    # on-demand at the same instant still burns idle on PM1 hosting D
    spec_o, params_o = _consolidation_cloud("ondemand")
    mid_o = eng.simulate(spec_o, tr, params=params_o, t_stop=600.0)
    assert int(np.asarray(mid_o.state.pstate)[1]) == PM_RUNNING
    # run to completion: everything finishes, all machines off
    res = eng.simulate(spec, tr, params=params)
    assert (np.asarray(res.state.task_state) == eng.TASK_DONE).all()
    assert (np.asarray(res.state.pstate) == PM_OFF).all()


def test_consolidate_with_impossible_trigger_equals_ondemand_bitwise():
    """consolidate inherits on-demand's wake/sleep pass; with a trigger
    threshold no meter reading can exceed, the policies must be
    *bit-identical* — the migration machinery is a masked no-op."""
    tr = _consolidation_trace()
    spec, params = _consolidation_cloud("ondemand")
    ref = eng.simulate(spec, tr, params=params)
    spec_c, params_c = _consolidation_cloud("consolidate")
    params_c = dataclasses.replace(params_c,
                                   consolidate_idle_frac=jnp.float32(2.0))
    got = eng.simulate(spec_c, tr, params=params_c)
    for a, b in zip(_answers(ref), _answers(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_consolidate_batched_matches_sequential():
    """The whole PM-policy axis (incl. consolidate) is CloudParams data:
    one simulate_batch compile, per-point results identical to sequential
    simulate calls."""
    tr = _consolidation_trace()
    spec, base = _consolidation_cloud("alwayson")
    pts = [dataclasses.replace(base, pm_sched=p)
           for p in ("alwayson", "ondemand", "consolidate")]
    batched = eng.simulate_batch(spec, tr, eng.stack_params(pts))
    for i, pt in enumerate(pts):
        single = eng.simulate(spec, tr, params=pt)
        np.testing.assert_array_equal(np.asarray(batched.energy[i]),
                                      np.asarray(single.energy))
        np.testing.assert_array_equal(
            np.asarray(batched.meters.vm.energy[i]),
            np.asarray(single.meters.vm.energy))
        np.testing.assert_array_equal(
            np.asarray(batched.meters.pm_idle.energy[i]),
            np.asarray(single.meters.pm_idle.energy))
        np.testing.assert_array_equal(np.asarray(batched.completion[i]),
                                      np.asarray(single.completion))
        assert int(batched.n_events[i]) == int(single.n_events)


def test_consolidation_no_migration_churn():
    """The load-ordering guard (dest at least as loaded as source) must
    prevent ping-pong: two equally idle hosts converge to one move, not an
    endless migration cycle (bounded event count, both tasks complete)."""
    tr = eng.Trace(
        arrival=jnp.asarray([0.0, 0.01], jnp.float32),
        cores=jnp.asarray([60.0, 60.0], jnp.float32),
        work=jnp.asarray([60 * 1500.0, 60 * 1500.0], jnp.float32))
    spec, params = eng.make_cloud(n_pm=2, n_vm=8, pm_cores=100.0,
                                  pm_sched="consolidate",
                                  consolidate_idle_frac=0.3)
    res = eng.simulate(spec, tr, params=params)
    assert (np.asarray(res.state.task_state) == eng.TASK_DONE).all()
    assert int(res.n_events) < 100, int(res.n_events)
    # at most one migration happened: makespan within one transfer pause
    assert float(res.t_end) < 1500.0 + 2 * 1024.0 / 125.0 + 250.0


# ------------------------------------------------------------- billing

def test_tenant_energy_partitions_vm_meters():
    from repro.core.energy import tenant_energy
    spec, params = _cloud(n_pm=2)
    tr = _trace([0.0, 0.0, 0.0], [2.0, 1.0, 1.0], [20.0, 10.0, 10.0])
    res = eng.simulate(spec, tr, params=params)
    rd = res.readings(spec)
    owner = np.full(spec.n_vm, -1, np.int32)
    owner[:3] = [0, 1, 1]  # all 3 tasks dispatched at t=0 -> slots 0..2
    te = np.asarray(tenant_energy(rd, owner, 2))
    assert te.shape == (2,) and (te > 0.0).all()
    vm = np.asarray(rd["vm"])
    np.testing.assert_allclose(te[0], vm[0], rtol=1e-6)
    np.testing.assert_allclose(te[1], vm[1] + vm[2], rtol=1e-6)
    # owned shares partition the attributed total; unowned slots drop
    np.testing.assert_allclose(te.sum(), vm.sum(), rtol=1e-6)


# ------------------------------------------------------- memory dimension

def _mem_trace(d_mem):
    """The consolidation trace with memory: PM0 keeps A's 200 GB after C
    ends, so D (``d_mem`` GB) may move there only if it fits the rest."""
    tr = _consolidation_trace()
    return tr._replace(mem=jnp.asarray([200.0, 20.0, 20.0, d_mem],
                                       jnp.float32))


@pytest.mark.parametrize("pm_sched", ["consolidate", "defrag", "evacuate"])
@pytest.mark.parametrize("d_mem,moves", [(100.0, False), (50.0, True)])
def test_no_migration_overfills_memory(pm_sched, d_mem, moves):
    """Every migration policy moves a VM only where its memory is free:
    at every snapshot each PM's free memory is its size less what its VMs
    hold, and never negative; D moves to PM0 exactly when it fits."""
    spec, params = eng.make_cloud(n_pm=2, n_vm=8, pm_cores=100.0,
                                  pm_mem=256.0, pm_sched=pm_sched)
    tr = _mem_trace(d_mem)
    for t_stop in (100.0, 235.0, 300.0, 450.0, 600.0, 1000.0, 3000.0):
        st = eng.simulate(spec, tr, params=params, t_stop=t_stop).state
        free = np.asarray(st.free_mem)
        held = np.zeros(2)
        on = np.asarray(st.vstage) != mc.VM_FREE
        np.add.at(held, np.asarray(st.vm_host)[on], np.asarray(st.vm_mem)[on])
        assert (free >= 0).all(), (t_stop, free)
        np.testing.assert_allclose(free + held, 256.0)
    mid = eng.simulate(spec, tr, params=params, t_stop=600.0).state
    d_vm = int(np.asarray(mid.task_vm)[3])
    assert (int(np.asarray(mid.vm_host)[d_vm]) == 0) == moves
    res = eng.simulate(spec, tr, params=params)
    assert (np.asarray(res.state.task_state) == eng.TASK_DONE).all()


def test_migrate_one_refuses_a_host_without_the_memory():
    from repro.core.loop.migrate import migrate_one
    spec, params = eng.make_cloud(n_pm=2, n_vm=8, pm_cores=100.0,
                                  pm_mem=256.0)
    tr = _mem_trace(100.0)
    st = eng.simulate(spec, tr, params=params, t_stop=300.0).state
    d_vm = int(np.asarray(st.task_vm)[3])
    assert int(np.asarray(st.vm_host)[d_vm]) == 1
    moved = migrate_one(spec, params, st, d_vm, 0, jnp.bool_(True))
    for a, b in zip(jax.tree.leaves(moved), jax.tree.leaves(st)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------- provider-only fair-share solve

@pytest.mark.parametrize("complex_power", [False, True])
def test_owned_consumers_through_migrations(monkeypatch, complex_power):
    """The provider-only solve (DESIGN.md §7) is exact because a consumer
    at or above ``layout.vm0`` is one flow's own: at every step of a
    consolidating run with memory and utilisation caps, each active flow
    that names such a consumer names its own VM or hidden spreader.  The
    run takes the provider-only solve in some passes, not while a
    migration or an image transfer is live, and gives the answers of
    solves that never take it."""
    tr = _mem_trace(50.0)._replace(util=jnp.full((4,), 0.5, jnp.float32))
    spec, params = eng.make_cloud(n_pm=2, n_vm=8, pm_cores=100.0,
                                  pm_mem=256.0, pm_sched="consolidate",
                                  complex_power=complex_power)
    lay, V, P = spec.layout, spec.n_vm, spec.n_pm
    own = np.concatenate([lay.vm0 + np.arange(V), lay.hidden0 + np.arange(P)])
    body = jax.jit(loop.make_body(spec, params, tr, jnp.float32(jnp.inf)))
    start = jax.jit(functools.partial(loop.management_pass, spec, params, tr))
    carry = (start(eng.init_state(spec, tr, params)), jnp.bool_(True),
             LoopCounters.zero())
    kinds = set()
    while bool(carry[0].running):
        st = carry[0]
        active = np.asarray(st.f_active)
        cons = np.asarray(st.f_cons)
        named = active & (cons >= lay.vm0)
        np.testing.assert_array_equal(cons[named], own[named])
        kinds |= set(np.asarray(st.f_kind)[active].tolist())
        carry = body(carry)
    assert KIND_MIGRATE in kinds
    assert (KIND_HIDDEN in kinds) == complex_power

    res = eng.simulate(spec, tr, params=params)
    for a, b in zip(jax.tree.leaves(carry[2]), jax.tree.leaves(res.counters)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    n_own = int(res.counters.provider_only_solves)
    assert 0 < n_own < int(res.n_events)

    # the same engine with every solve over both sides
    with monkeypatch.context() as m:
        m.setitem(fairshare.SCHEDULERS, "maxmin",
                  lambda *a, owned=None, **kw: fairshare.maxmin_fill(*a, **kw))
        eng.simulate.clear_cache()
        both = eng.simulate(spec, tr, params=params)
    eng.simulate.clear_cache()
    for a, b in zip(_answers(res), _answers(both)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
