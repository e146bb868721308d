"""Shared source/victim selection for the migration PM policies.

Consolidation, defragmentation and evacuation all reason over the same
host facts (who is RUNNING, how loaded, who hosts migratable VMs) and the
first/last two share the idle-dominance trigger — one implementation
here, so a change to the trigger or a tie-break cannot silently diverge
the policies.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import machine as mc
from repro.core.energy import PM_RUNNING
from repro.core.loop.state import CloudState


def host_load_facts(spec, params, st: CloudState):
    """``(running, used, movable, n_movable)``: per-PM RUNNING mask and
    allocated cores, per-VM migratable (RUNNING) mask, per-PM migratable
    counts."""
    running = st.pstate == PM_RUNNING
    used = jnp.asarray(params.pm_cores, jnp.float32) - st.free_cores
    movable = st.vstage == mc.VM_RUNNING
    n_movable = mc.vms_per_pm(movable, st.vm_host, spec.n_pm)
    return running, used, movable, n_movable


def idle_dominated_donor(params, st: CloudState, running, used, n_movable):
    """``(donor, src)`` for the idle-dominance trigger: the donor mask —
    RUNNING hosts with a migratable VM whose live meter reading is
    idle-dominated (``pm_idle.last_power / pm.last_power`` above
    ``CloudParams.consolidate_idle_frac``) — and the least-loaded such
    host as the source."""
    pm_w = st.meters.pm.last_power
    idle_w = st.meters.pm_idle.last_power
    idle_frac = idle_w / jnp.maximum(pm_w, 1e-30)
    donor = (running & (n_movable > 0)
             & (idle_frac > jnp.asarray(params.consolidate_idle_frac,
                                        jnp.float32)))
    src = jnp.argmin(jnp.where(donor, used, jnp.inf)).astype(jnp.int32)
    return donor, src


def feasible_destinations(running, used, free_cores, src, need,
                          free_mem=None, need_mem=None):
    """Mask of hosts a victim of ``need`` cores may move to: RUNNING, has
    the cores free (and ``need_mem`` of ``free_mem``, where the state has
    a memory dimension), is not the source, and is *at least as loaded*
    as the source — the load-ordering guard that makes every move strictly
    packing (never spreading) and breaks migration ping-pong between two
    equally loaded hosts."""
    P = running.shape[0]
    fit = (running & (free_cores >= need) & (jnp.arange(P) != src)
           & (used >= used[src]))
    if free_mem is not None:
        fit = fit & (free_mem >= need_mem)
    return fit


def mem_of(st: CloudState, v):
    """The memory VM slot ``v`` holds, or ``None`` without a memory
    dimension."""
    return None if st.vm_mem is None else st.vm_mem[v]


def smallest_victim_on(st: CloudState, movable, src):
    """``(on_src, v)``: the source host's migratable VMs and the
    smallest-cores one (the cheapest serialized state to re-place)."""
    on_src = movable & (st.vm_host == src)
    v = jnp.argmin(jnp.where(on_src, st.vm_cores, jnp.inf)).astype(jnp.int32)
    return on_src, v
