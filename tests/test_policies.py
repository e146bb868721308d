"""The defragmentation and multi-VM evacuation PM policies (PR 5).

Both are contributed through the open registry alone
(repro.sched.policies.{defrag,evacuate}) — these tests pin their policy
behaviour: defrag packs toward bin-packing targets with no idle-threshold
trigger and never churns; evacuation drains a multi-VM donor in one
pipeline pass (up to ``CloudSpec.max_migrations`` moves) where
consolidation needs one pass per VM; both stay masked no-ops (bitwise
equal to their base policies) when they cannot fire.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as eng
from repro.core import machine as mc
from repro.core.energy import PM_OFF, PM_RUNNING, PM_SWITCHING_OFF


def _answers(res):
    """The result's leaves without its loop counters: policies that give
    the same answers may still open their event gates differently."""
    return jax.tree.leaves(res._replace(counters=None))


def _trace(arrival, cores, runtime):
    arrival = jnp.asarray(arrival, jnp.float32)
    cores = jnp.asarray(cores, jnp.float32)
    runtime = jnp.asarray(runtime, jnp.float32)
    return eng.Trace(arrival=arrival, cores=cores, work=runtime * cores)


def _evac_trace():
    """2 PMs x 100 cores.  First-fit: A(70c, long) + E(30c, 250s) fill PM0;
    B(60c, 200s) -> PM1, then C(15c, long) + D(10c, long) land next to it.
    The on-demand fleet boots at ~t=200 (boot_s); after B and E drain
    (~t=410/450), PM1 hosts C+D at 25% utilisation (idle-dominated, donor)
    while PM0 runs A at 70% (not idle-dominated, fits both): a two-VM
    evacuation opportunity."""
    return _trace([0.0, 0.005, 0.01, 0.02, 0.03],
                  [70.0, 30.0, 60.0, 15.0, 10.0],
                  [2000.0, 250.0, 200.0, 2000.0, 2000.0])


def _straggler_trace(waves=2):
    """The consolidation-bench workload: per wave, first-fit packs 4
    16-core tasks per PM; one per PM is a long straggler."""
    arrival, cores, work = [], [], []
    for w in range(waves):
        t0 = w * 5000.0
        for i in range(16):
            arrival.append(t0 + 0.01 * i)
            cores.append(16.0)
            runtime = 4000.0 if (i % 4) == 3 else 200.0
            work.append(16.0 * runtime)
    return eng.Trace(arrival=jnp.asarray(arrival, jnp.float32),
                     cores=jnp.asarray(cores, jnp.float32),
                     work=jnp.asarray(work, jnp.float32))


def _cloud(pm_sched, **kw):
    base = dict(n_pm=2, n_vm=8, pm_cores=100.0, pm_sched=pm_sched)
    base.update(kw)
    return eng.make_cloud(**base)


# --------------------------------------------------------- evacuation

def test_evacuation_drains_donor_in_one_pass():
    """On a two-VM donor, one evacuation_step call plans and issues both
    moves (cumulative destination capacity), where consolidation_step
    issues exactly one."""
    from repro.sched.policies.consolidate import consolidation_step
    from repro.sched.policies.evacuate import evacuation_step

    spec, params = _cloud("ondemand")
    tr = _evac_trace()
    res = eng.simulate(spec, tr, params=params, t_stop=460.0)
    st = res.state
    # the probe state really is the two-VM-donor configuration
    hosted1 = (np.asarray(st.vstage) == mc.VM_RUNNING) \
        & (np.asarray(st.vm_host) == 1)
    assert hosted1.sum() == 2
    assert float(st.free_cores[0]) == 30.0

    st_e = evacuation_step(spec, params, st)
    moved = np.asarray(st_e.vstage) == mc.VM_MIGRATING
    assert moved.sum() == 2
    assert (np.asarray(st_e.vm_mig_dst)[moved] == 0).all()
    # cores committed src -> dst for both moves at once
    assert float(st_e.free_cores[0]) == 5.0
    assert float(st_e.free_cores[1]) == 100.0

    st_c = consolidation_step(spec, params, st)
    assert (np.asarray(st_c.vstage) == mc.VM_MIGRATING).sum() == 1


def test_evacuate_completes_and_beats_ondemand():
    tr = _evac_trace()
    res = {}
    for pm in ("ondemand", "evacuate"):
        spec, params = _cloud(pm)
        r = eng.simulate(spec, tr, params=params)
        assert (np.asarray(r.state.task_state) == eng.TASK_DONE).all(), pm
        assert (np.asarray(r.state.pstate) == PM_OFF).all(), pm
        res[pm] = float(r.readings(spec)["iaas_total"])
    # the drained donor powers off for the ~1800 s tail it would have idled
    assert res["evacuate"] < 0.9 * res["ondemand"], res


def test_evacuate_equals_consolidate_bitwise_on_single_vm_donor():
    """With at most one movable VM on any donor, the K-move plan degrades
    to consolidation's single move — bit-identical, masked lanes and all."""
    tr = _trace([0.0, 0.01, 0.02, 230.0], [60.0, 35.0, 70.0, 25.0],
                [2000.0, 200.0, 200.0, 2000.0])
    spec_c, params_c = _cloud("consolidate")
    ref = eng.simulate(spec_c, tr, params=params_c)
    spec_e, params_e = _cloud("evacuate")
    got = eng.simulate(spec_e, tr, params=params_e)
    for a, b in zip(_answers(ref), _answers(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_evacuate_with_impossible_trigger_equals_ondemand_bitwise():
    tr = _evac_trace()
    spec, params = _cloud("ondemand")
    ref = eng.simulate(spec, tr, params=params)
    spec_e, params_e = _cloud("evacuate")
    params_e = dataclasses.replace(params_e,
                                   consolidate_idle_frac=jnp.float32(2.0))
    got = eng.simulate(spec_e, tr, params=params_e)
    for a, b in zip(_answers(ref), _answers(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_max_migrations_caps_the_evacuation_plan():
    """K=1 turns evacuation into consolidation's one-at-a-time drain: the
    direct step call moves exactly one VM off the two-VM donor."""
    from repro.sched.policies.evacuate import evacuation_step

    spec, params = _cloud("ondemand")
    res = eng.simulate(spec, _evac_trace(), params=params, t_stop=460.0)
    spec1 = dataclasses.replace(spec, max_migrations=1)
    st1 = evacuation_step(spec1, params, res.state)
    assert (np.asarray(st1.vstage) == mc.VM_MIGRATING).sum() == 1


# --------------------------------------------------------- defrag

def test_defrag_packs_stragglers_and_beats_ondemand():
    """The consolidation-bench workload: after each wave's short tasks
    drain, every PM hosts one straggler; defrag packs them onto one host
    (no idle threshold involved) and the donors power down."""
    tr = _straggler_trace()
    spec, base = eng.make_cloud(n_pm=4, n_vm=max(int(tr.n), 8),
                                pm_cores=64.0, max_events=4_000_000)
    e = {}
    for pm in ("ondemand", "defrag", "consolidate"):
        r = eng.simulate(spec, tr,
                         params=dataclasses.replace(base, pm_sched=pm))
        assert (np.asarray(r.state.task_state) == eng.TASK_DONE).all(), pm
        e[pm] = float(r.readings(spec)["iaas_total"])
    assert e["defrag"] < 0.7 * e["ondemand"], e
    # same packed end state as the idle-meter policy on this workload
    np.testing.assert_allclose(e["defrag"], e["consolidate"], rtol=0.02)


def test_defrag_holds_when_nothing_can_pack():
    """A fragmented state where no victim fits any more-loaded host is a
    stable no-op: no migration flows, no churn, bounded events.
    First-fit at the ~t=200 boot: A(60)+C(20) -> PM0 (80 used), B(50) ->
    PM1; PM1's only VM (50c) does not fit PM0's 20 free cores and moving
    C the other way would spread (dest less loaded) — forbidden."""
    tr = _trace([0.0, 0.01, 0.02], [60.0, 50.0, 20.0],
                [2000.0, 2000.0, 2000.0])
    spec, params = _cloud("defrag")
    mid = eng.simulate(spec, tr, params=params, t_stop=300.0)
    assert (np.asarray(mid.state.vstage) != mc.VM_MIGRATING).all()
    hosts = np.asarray(mid.state.vm_host)[
        np.asarray(mid.state.vstage) == mc.VM_RUNNING]
    assert sorted(hosts.tolist()) == [0, 0, 1]
    res = eng.simulate(spec, tr, params=params)
    assert (np.asarray(res.state.task_state) == eng.TASK_DONE).all()
    assert int(res.n_events) < 100, int(res.n_events)


def test_defrag_no_churn_between_equal_hosts():
    """Two equally loaded hosts (40 cores each once the 300 s filler
    drains): the load-ordering guard allows exactly one packing move —
    donor empties, powers down, and the reverse move is forbidden, so the
    event count stays bounded."""
    tr = _trace([0.0, 0.01, 0.02], [40.0, 60.0, 40.0],
                [1500.0, 300.0, 1500.0])
    spec, params = _cloud("defrag")
    # first-fit at boot: A(40)+B(60) fill PM0, C(40) -> PM1.  B drains at
    # ~t=505 leaving 40 vs 40; the tie-broken donor is PM0, dest PM1.
    mid = eng.simulate(spec, tr, params=params, t_stop=700.0)
    assert int(np.asarray(mid.state.pstate)[0]) in (PM_SWITCHING_OFF, PM_OFF)
    assert int(np.asarray(mid.state.pstate)[1]) == PM_RUNNING
    hosts = np.asarray(mid.state.vm_host)[
        np.asarray(mid.state.vstage) == mc.VM_RUNNING]
    assert hosts.tolist() == [1, 1]
    res = eng.simulate(spec, tr, params=params)
    assert (np.asarray(res.state.task_state) == eng.TASK_DONE).all()
    assert int(res.n_events) < 120, int(res.n_events)


def test_defrag_on_single_pm_equals_ondemand_bitwise():
    """With one PM there is never a packing target: defrag must be a
    masked bitwise no-op over on-demand."""
    tr = _trace([0.0, 0.01, 300.0], [40.0, 30.0, 20.0],
                [500.0, 200.0, 400.0])
    spec_o, params_o = _cloud("ondemand", n_pm=1)
    ref = eng.simulate(spec_o, tr, params=params_o)
    spec_d, params_d = _cloud("defrag", n_pm=1)
    got = eng.simulate(spec_d, tr, params=params_d)
    for a, b in zip(_answers(ref), _answers(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------- batched == sequential

def test_new_policy_codes_batch_like_any_other():
    """The full 5-policy PM axis is CloudParams data: one simulate_batch
    compile, per-point results identical to sequential simulate calls."""
    tr = _evac_trace()
    spec, base = _cloud("alwayson")
    pts = [dataclasses.replace(base, pm_sched=p)
           for p in ("alwayson", "ondemand", "consolidate", "defrag",
                     "evacuate")]
    batched = eng.simulate_batch(spec, tr, eng.stack_params(pts))
    for i, pt in enumerate(pts):
        single = eng.simulate(spec, tr, params=pt)
        np.testing.assert_array_equal(np.asarray(batched.energy[i]),
                                      np.asarray(single.energy))
        np.testing.assert_array_equal(
            np.asarray(batched.meters.pm_idle.energy[i]),
            np.asarray(single.meters.pm_idle.energy))
        np.testing.assert_array_equal(np.asarray(batched.completion[i]),
                                      np.asarray(single.completion))
        assert int(batched.n_events[i]) == int(single.n_events)
