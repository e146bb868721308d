"""Quickstart: an energy-aware cloud simulation in under a minute (CPU).

Simulates one cloud scenario, reads its hierarchical meter stack, and
sweeps NIC bandwidth into an energy-vs-makespan Pareto frontier.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax.numpy as jnp

from repro.core import engine
from repro.core.trace import synthetic_trace
from repro.experiments import pareto

print("=== DISSECT-CF cloud simulation " + "=" * 33)
# CloudSpec holds the static shape (jit-recompiles when it changes);
# CloudParams holds every continuous knob + scheduler codes (traced data —
# change or batch them freely under one compile).
spec = engine.CloudSpec(n_pm=4, n_vm=64)
params = engine.CloudParams(pm_cores=64.0, pm_sched="ondemand")
trace = synthetic_trace(n_tasks=200, parallel=32, spread_s=20.0, seed=0)
res = engine.simulate(spec, trace, params=params)
print(f"simulated {trace.n} tasks in {int(res.n_events)} events; "
      f"makespan {float(res.t_end):.0f}s; "
      f"energy {float(jnp.sum(res.energy))/3.6e6:.2f} kWh; "
      f"rejected {int(res.rejected.sum())}")

# the hierarchical meter stack (paper §3.3): every simulate carries named
# meters — per-PM direct, per-VM Eq. 6 attribution, whole-IaaS aggregate,
# and a PUE-style HVAC indirect meter — read them by name:
rd = res.readings(spec)
vm_kwh = float(jnp.sum(rd["vm"])) / 3.6e6
print(f"meter stack: IaaS total {float(rd['iaas_total'])/3.6e6:.2f} kWh = "
      f"VM-attributed {vm_kwh:.2f} + idle/overhead "
      f"{float(rd['vm_unattributed'])/3.6e6:.2f}; "
      f"HVAC (indirect, PUE 1.58) {float(rd['hvac'])/3.6e6:.2f} kWh")

# batched sweeps are first-class experiments (docs/experiments.md): grid 4
# NIC bandwidths into one sharded simulate_batch call and read the
# energy-vs-makespan Pareto frontier off the meter stack
bws = [62.5, 125.0, 250.0, 500.0]
front = pareto.sweep(spec, trace, pareto.param_grid(params, net_bw=bws),
                     labels=pareto.grid_labels(net_bw=bws))
for r in front.rows:  # '*' marks frontier membership
    print(f"{'*' if r['on_frontier'] else ' '} net_bw={r['net_bw']:6.1f}  "
          f"energy {r['energy_kwh']:.2f} kWh  "
          f"makespan {r['makespan_s']:4.0f} s")

print("quickstart OK")
