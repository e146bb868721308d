"""The defragmentation PM policy (``pm_sched="defrag"``).

Consolidation (:mod:`.consolidate`) triggers on *idle dominance*: a host
must waste most of its draw before its VMs move.  Defragmentation instead
migrates toward **bin-packing targets** whenever packing is possible at
all: if the least-loaded host's smallest running VM fits on a more-loaded
running host, move it there — fill the most-loaded feasible host, drain
the least-loaded one, and let the inherited on-demand sleep rule power the
emptied donor down.  On fragmented steady states (every host holding one
straggler) this reaches the packed fleet without waiting for any idle
threshold, which is why it can only shed *more* idle energy than
on-demand.

Guards (all masked, so refused iterations are bitwise no-ops):

* only acts when the request queue is empty — never competes with
  dispatch for capacity mid-wave;
* the destination must be *at least as loaded* as the donor, so moves
  strictly pack and two equally-loaded hosts cannot ping-pong (after one
  move the ordering is strict and only further packing qualifies);
* at most one move per loop iteration — the event loop re-evaluates on
  the migration's own events, so a fleet defragments over a handful of
  horizons.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.loop.migrate import migrate_one
from repro.core.loop.state import TASK_PENDING, CloudState

from .. import registry
from .baseline import wake_sleep_pass
from .consolidate import MIGRATION_DELTA
from .select import (feasible_destinations, host_load_facts, mem_of,
                     smallest_victim_on)


def defrag_step(spec, params, trace, st: CloudState) -> CloudState:
    """One masked bin-packing move: least-loaded donor's smallest VM onto
    the most-loaded running host that fits it."""
    running, used, movable, n_movable = host_load_facts(spec, params, st)
    queued = (st.task_state == TASK_PENDING) & (trace.arrival <= st.t)

    donor = running & (n_movable > 0)
    src = jnp.argmin(jnp.where(donor, used, jnp.inf)).astype(jnp.int32)

    on_src, v = smallest_victim_on(st, movable, src)
    need = st.vm_cores[v]

    # bin-packing target: the *most-loaded* running host the victim fits
    fit = feasible_destinations(running, used, st.free_cores, src, need,
                                st.free_mem, mem_of(st, v))
    dst = jnp.argmax(jnp.where(fit, used, -jnp.inf)).astype(jnp.int32)

    do = ~queued.any() & donor.any() & on_src.any() & fit.any()
    return migrate_one(spec, params, st, v, dst, do)


def defrag(spec, params, ctx, st: CloudState) -> CloudState:
    st = wake_sleep_pass(spec, params, ctx.trace, st)
    return defrag_step(spec, params, ctx.trace, st)


registry.register(
    "pm", "defrag", defrag, code=3, requires=MIGRATION_DELTA,
    doc="on-demand + bin-packing migrations toward the most-loaded "
        "feasible host (no idle-threshold trigger)")
