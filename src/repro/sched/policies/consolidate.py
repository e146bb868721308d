"""The meter-driven consolidation PM policy (``pm_sched="consolidate"``).

This is the cross-layer policy DISSECT-CF exists to make cheap (paper §1,
§3.4): a PM state scheduler that reads the *metering framework* — the live
per-PM direct and idle meters of the stack — and reacts inside the event
loop by rewriting VM and flow state.  It inherits on-demand's wake/sleep
pass and adds at most one masked migration decision per iteration:

* **source** — the least-loaded RUNNING host whose live meter reading is
  idle-dominated (``pm_idle.last_power / pm.last_power`` above
  ``CloudParams.consolidate_idle_frac``) and that hosts a migratable
  (RUNNING) VM;
* **victim** — the smallest-cores running VM on the source (cheapest to
  re-place);
* **destination** — the best-fit running host: least free cores among
  those that fit the victim, are not the source, and are *at least as
  loaded* as the source.  The load ordering makes moves strictly packing
  (never spreading) and breaks migration ping-pong between two
  equally-idle hosts.

Once a donor's last VM has resumed elsewhere the inherited sleep rule
powers it down.  Policy identity stays ``CloudParams`` data (the registry
code the loop's ``lax.switch`` dispatches on), so a consolidation cell
batches through the same compiled program as always-on / on-demand cells
(``simulate_batch``, tournaments, sharded sweeps — DESIGN.md §4-§6).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.loop.migrate import migrate_one
from repro.core.loop.state import CloudState

from .. import registry
from .baseline import WAKE_SLEEP_DELTA, wake_sleep_pass
from .select import (feasible_destinations, host_load_facts,
                     idle_dominated_donor, mem_of, smallest_victim_on)

# wake/sleep inherited, plus one masked migration's rewrite of the victim
# slot, both hosts' cores (and memory), and the loop-liveness flag
MIGRATION_DELTA = WAKE_SLEEP_DELTA + (
    "vstage", "vm_mig_dst", "vm_saved_pr", "free_cores", "free_mem",
    "running")


def consolidation_step(spec, params, st: CloudState) -> CloudState:
    """One masked consolidation decision, driven by the live meter stack."""
    running, used, movable, n_movable = host_load_facts(spec, params, st)
    donor, src = idle_dominated_donor(params, st, running, used, n_movable)
    on_src, v = smallest_victim_on(st, movable, src)
    need = st.vm_cores[v]

    fit = feasible_destinations(running, used, st.free_cores, src, need,
                                st.free_mem, mem_of(st, v))
    dst = jnp.argmin(jnp.where(fit, st.free_cores, jnp.inf)).astype(jnp.int32)

    do = donor.any() & on_src.any() & fit.any()
    return migrate_one(spec, params, st, v, dst, do)


def consolidate(spec, params, ctx, st: CloudState) -> CloudState:
    st = wake_sleep_pass(spec, params, ctx.trace, st)
    return consolidation_step(spec, params, st)


registry.register(
    "pm", "consolidate", consolidate, code=2, requires=MIGRATION_DELTA,
    doc="on-demand + one idle-meter-driven live migration per iteration")
