"""IaaS service facade (paper §3.5.2): external APIs over the engine state.

Three API families, mirroring the paper:

* **information retrieval** — :func:`cloud_info` exposes the metrics the
  paper lists (running/total PM ratio, hosted VM count, total & running
  capacity, per-PM load, applied schedulers, queue length);
* **virtual-infrastructure management** — request/terminate VMs is the
  engine's trace protocol; :func:`repro.core.engine.start_migration` covers
  VM migration; reallocation = terminate+request (documented limitation);
* **infrastructure alteration** — PMs are (de)registered by masking them
  out of the spreader space (:func:`deregister_pm` abruptly kills hosted
  VMs, the paper's "violent deregistration" used for fault-injection).

The facade is what user-side schedulers consume.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from . import machine as mc
from .energy import PM_OFF, PM_RUNNING, meter_readings
from repro.sched import registry as _policy_registry

from .engine import (CloudParams, CloudSpec, CloudState, TASK_ACTIVE,
                     TASK_DONE, TASK_PENDING, TASK_REJECTED, Trace)


def _sched_name(code, layer: str) -> str:
    try:
        return _policy_registry.name_of(layer, int(jnp.asarray(code)))
    except (TypeError, jax.errors.ConcretizationTypeError):
        return "<traced>"
    except KeyError:
        # a code whose policy has been unregistered since the params were
        # built — keep the diagnostic dict usable
        return "<unregistered>"


def cloud_info(spec: CloudSpec, params: CloudParams, st: CloudState,
               trace: Trace) -> dict[str, Any]:
    """One-time-query information APIs (paper §3.5.2 list).

    Host-side, single-scenario: ``params`` must be an unbatched point."""
    P = spec.n_pm
    pm_cores = float(jnp.asarray(params.pm_cores))
    running = st.pstate == PM_RUNNING
    hosted = st.vstage != mc.VM_FREE
    queued = (st.task_state == TASK_PENDING) & (trace.arrival <= st.t)
    per_pm_vms = mc.vms_per_pm(hosted, st.vm_host, P)
    total_cores = pm_cores * P
    running_cores = float(jnp.sum(jnp.where(running, pm_cores, 0.0)))
    used = jnp.where(running, pm_cores - st.free_cores, 0.0)
    return {
        "t": float(st.t),
        "pm_running_ratio": float(running.sum()) / P,
        "pm_running": int(running.sum()),
        "pm_total": P,
        "vm_hosted": int(hosted.sum()),
        "capacity_total_cores": float(total_cores),
        "capacity_running_cores": running_cores,
        "capacity_allocated_cores": float(used.sum()),
        "pm_load": [float(x) for x in (used / pm_cores)],
        "pm_vm_count": [int(x) for x in per_pm_vms],
        "queue_len": int(queued.sum()),
        "vm_scheduler": _sched_name(params.vm_sched, "vm"),
        "pm_scheduler": _sched_name(params.pm_sched, "pm"),
        "tasks_done": int((st.task_state == TASK_DONE).sum()),
        "tasks_rejected": int((st.task_state == TASK_REJECTED).sum()),
        "tasks_active": int((st.task_state == TASK_ACTIVE).sum()),
        "energy_joules": float(st.meters.total.energy),
        # the whole meter stack, by name (per-PM, per-VM Eq. 6, groups,
        # whole-IaaS aggregate, indirect meters)
        "meters": {
            name: ([float(x) for x in jnp.ravel(v)]
                   if jnp.ndim(v) else float(v))
            for name, v in meter_readings(spec.meters, st.meters).items()
        },
    }


def deregister_pm(spec: CloudSpec, params: CloudParams, st: CloudState,
                  pm: int, trace: Trace) -> CloudState:
    """Violently deregister a PM (paper §3.5.2 infrastructure alteration):
    its VMs are terminated abruptly (tasks go back to PENDING so user-side
    schedulers can observe and re-submit — error-resilience scenarios)."""
    pm = jnp.asarray(pm, jnp.int32)
    victim = (st.vm_host == pm) & (st.vstage != mc.VM_FREE)
    tslot = jnp.where(victim, st.vm_task, trace.n)
    task_state = st.task_state.at[tslot].set(TASK_PENDING, mode="drop")
    task_vm = st.task_vm.at[tslot].set(-1, mode="drop")
    V = spec.n_vm
    return st._replace(
        task_state=task_state,
        task_vm=task_vm,
        vstage=jnp.where(victim, mc.VM_FREE, st.vstage),
        f_active=st.f_active.at[:V].set(
            jnp.where(victim, False, st.f_active[:V])),
        pstate=st.pstate.at[pm].set(PM_OFF),
        free_cores=st.free_cores.at[pm].set(
            jnp.asarray(params.pm_cores, jnp.float32)),
        running=jnp.bool_(True),
    )


def state_change_events(prev: CloudState, cur: CloudState) -> dict[str, Any]:
    """Notification-style diffs (paper §3.6.1): which VMs/PMs changed state,
    queue-length change, released allocations.  Host-side helper for
    user-side scheduler experiments."""
    vm_changed = jnp.nonzero(prev.vstage != cur.vstage)[0]
    pm_changed = jnp.nonzero(prev.pstate != cur.pstate)[0]
    return {
        "vm_transitions": [
            (int(v), int(prev.vstage[v]), int(cur.vstage[v])) for v in vm_changed],
        "pm_transitions": [
            (int(p), int(prev.pstate[p]), int(cur.pstate[p])) for p in pm_changed],
        "tasks_completed": int(((prev.task_state != TASK_DONE)
                                & (cur.task_state == TASK_DONE)).sum()),
    }
