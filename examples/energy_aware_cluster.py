"""Energy-aware scheduling on DAS-2 grid workloads — the paper's §4
methodology end to end.

Runs the scheduler *tournament* (every registered VM x PM policy pair,
repro.experiments.tournament) on a GWA DAS-2 trace, then a trace-*ensemble*
experiment — mean ± CI per policy over seed-perturbed DAS-2 traces
(docs/experiments.md) — then a live-migration policy demo (the in-loop
consolidate/defrag/evacuate PM schedulers, registry citizens from
repro.sched.policies, DESIGN.md §5-§6) and a per-tenant bill from the
per-VM Eq. 6 meters.

Run:  PYTHONPATH=src python examples/energy_aware_cluster.py
"""
import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.core.energy import tenant_energy
from repro.core.trace import gwa_like_trace
from repro.experiments import ensemble, tournament

print("=== scheduler tournament on a DAS-2 trace " + "=" * 25)
PM_CORES = 64.0
trace = gwa_like_trace("das2", 300, max_cores=int(PM_CORES), seed=2)
spec = engine.CloudSpec(n_pm=16, n_vm=128)
base = engine.CloudParams(pm_cores=PM_CORES)
print(f"cloud: {spec.n_pm} PMs x {PM_CORES:.0f} cores, {trace.n} tasks "
      f"over {float(trace.arrival[-1]) / 3600:.1f} h\n")
# the whole VM x PM matrix is one sharded simulate_batch call
rows = tournament.run(spec, trace, base).rows
# meter-stack columns: IT energy (whole-IaaS aggregate), the job-attributed
# share (per-VM Eq. 6 meters), idle waste, and HVAC (indirect meter)
print(f"{'VM sched':>14s} {'PM sched':>11s} {'IT kWh':>8s} {'job kWh':>8s} "
      f"{'idle kWh':>8s} {'HVAC kWh':>8s} {'makespan h':>11s} "
      f"{'rejected':>9s}")
for r in rows:
    print(f"{r['vm_sched']:>14s} {r['pm_sched']:>11s} "
          f"{r['energy_kwh']:8.2f} {r['job_kwh']:8.2f} "
          f"{r['idle_kwh']:8.2f} {r['hvac_kwh']:8.2f} "
          f"{r['makespan_s']/3600:11.2f} {r['jobs_rejected']:9d}")
# only compare policies that actually served the trace (non-queuing cells
# on sleeping PMs reject tasks outright — cheap, but not by doing the work)
served = [r for r in rows if r["jobs_rejected"] == 0] or rows
best = min(served, key=lambda r: r["energy_kwh"])
worst = max(served, key=lambda r: r["energy_kwh"])
print(f"\nbest policy: {best['vm_sched']}+{best['pm_sched']} saves "
      f"{100*(1-best['energy_kwh']/worst['energy_kwh']):.1f}% energy vs "
      f"{worst['vm_sched']}+{worst['pm_sched']}")

# ------------------------------------------------------------------ ensemble
# one trace is an anecdote: re-sample it and report mean ± 95% CI per
# policy (the trace-ensemble experiment, docs/experiments.md §5)
print("\n=== ensemble: mean ± 95% CI over 4 seeded DAS-2 traces " + "=" * 10)
traces = ensemble.gwa_ensemble("das2", 200, replicates=4, pm_cores=PM_CORES,
                               seed0=10)
policies = [("firstfit", "alwayson"), ("firstfit", "ondemand"),
            ("smallestfirst", "ondemand")]
er = ensemble.run_ensemble(
    spec, traces,
    [dataclasses.replace(base, vm_sched=v, pm_sched=p) for v, p in policies],
    labels=[{"policy": f"{v}+{p}"} for v, p in policies])
for r in er.rows:
    print(f"{r['policy']:>24s}  energy {r['energy_kwh_mean']:6.2f} "
          f"± {r['energy_kwh_ci']:5.2f} kWh  idle {r['idle_kwh_mean']:6.2f} "
          f"± {r['idle_kwh_ci']:5.2f} kWh  makespan "
          f"{r['makespan_s_mean']/3600:5.2f} ± {r['makespan_s_ci']/3600:4.2f} h")

# ---------------------------------------------------------------- migration
print("\n=== in-loop live-migration PM policies " + "=" * 27)
# Two 100-core machines.  Short wide tasks pin a long 25-core straggler to
# PM1; once they drain, PM1 idles under one small VM.  The migration PM
# policies (all ordinary registry codes — repro.sched.policies) watch the
# cloud from *inside* the engine loop, move the straggler to PM0 and power
# the donor down: consolidate/evacuate on the per-PM idle meter, defrag on
# pure bin-packing.  No manual start_migration call, and the whole policy
# axis is one batch.
spec = engine.CloudSpec(n_pm=2, n_vm=8)
ctrace = engine.Trace(
    arrival=jnp.asarray([0.0, 0.01, 0.02, 230.0], jnp.float32),
    cores=jnp.asarray([60.0, 35.0, 70.0, 25.0], jnp.float32),
    work=jnp.asarray([60e3 * 2, 7e3, 14e3, 50e3], jnp.float32))
cbase = engine.CloudParams(pm_cores=100.0)
pols = ("alwayson", "ondemand", "consolidate", "defrag", "evacuate")
cres = engine.simulate_batch(
    spec, ctrace,
    engine.stack_params([dataclasses.replace(cbase, pm_sched=p)
                         for p in pols]))
crd = cres.readings(spec)
for i, p in enumerate(pols):
    print(f"  {p:12s} {float(crd['iaas_total'][i])/3.6e6:7.3f} kWh  "
          f"idle {float(crd['vm_unattributed'][i])/3.6e6:6.3f} kWh  "
          f"makespan {float(cres.t_end[i]):7.0f} s")
print("the migration policies move the straggler off PM1 and switch the "
      "donor off for the tail")

# ------------------------------------------------------------------ billing
print("\n=== per-tenant billing from the Eq. 6 meters " + "=" * 21)
# the per-VM adjusted-aggregation meters are billing-grade: each tenant
# pays the PM power its own VMs induced; unattributed idle stays with the
# operator (docs/experiments.md §9)
rd_one = {k: v[2] for k, v in crd.items()}  # the consolidated run's row
owner = np.full(spec.n_vm, -1, np.int32)
owner[:4] = [0, 0, 1, 1]   # tasks dispatch in arrival order -> slots 0..3
PRICE = 0.12               # $/kWh
bill = np.asarray(tenant_energy(rd_one, owner, 2)) / 3.6e6
for t in range(2):
    print(f"  tenant {t}: {bill[t]:8.3f} kWh -> ${PRICE * bill[t]:7.2f}")
print(f"  operator idle (unbilled): "
      f"{float(rd_one['vm_unattributed'])/3.6e6:.3f} kWh")
