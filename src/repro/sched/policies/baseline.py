"""The paper's baseline policies (§3.5.1), as registry citizens.

PM layer: ``alwayson`` (the identity — machines never change power state
here) and ``ondemand`` (wake enough machines for the unmet queue, switch
off loadless machines when the queue is empty).  The on-demand wake/sleep
arithmetic is exposed as :func:`wake_sleep_pass` because every richer PM
policy in this package (consolidate / defrag / evacuate) inherits it
before adding migrations.

VM layer: ``firstfit`` / ``nonqueuing`` / ``smallestfirst``, thin
configurations of the queue-serving machinery in
:func:`repro.core.loop.vm_sched.serve_queue`.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import machine as mc
from repro.core.arrays import KIND_HIDDEN
from repro.core.energy import (PM_OFF, PM_RUNNING, PM_SWITCHING_OFF,
                               PM_SWITCHING_ON)
from repro.core.loop.state import TASK_PENDING, CloudState
from repro.core.loop.vm_sched import serve_queue

from .. import registry

# --------------------------------------------------------------- PM layer


def wake_sleep_pass(spec, params, trace, st: CloudState) -> CloudState:
    """On-demand's wake/sleep rules: wake enough OFF machines to cover the
    queued core deficit; switch off loadless RUNNING machines when nothing
    is queued.  Under the complex power model the transition work becomes
    the machine's hidden-consumer flow (paper Table 2)."""
    P = spec.n_pm
    table = params.power
    queued = (st.task_state == TASK_PENDING) & (trace.arrival <= st.t)
    q_cores = jnp.sum(jnp.where(queued, trace.cores, 0.0))
    soon = mc.pm_future_capacity(st.pstate)
    cap_soon = jnp.sum(jnp.where(soon, st.free_cores, 0.0))
    deficit = q_cores - cap_soon
    k = jnp.ceil(jnp.maximum(deficit, 0.0) / params.pm_cores).astype(jnp.int32)

    off = st.pstate == PM_OFF
    wake = off & (jnp.cumsum(off.astype(jnp.int32)) <= k)
    # loadless running PMs sleep only when nothing is queued
    hosted = mc.vms_per_pm(st.vstage != mc.VM_FREE, st.vm_host, P)
    idle = ((st.pstate == PM_RUNNING) & (hosted == 0) & ~queued.any())

    boot_s = table.duration[PM_SWITCHING_ON]
    halt_s = table.duration[PM_SWITCHING_OFF]
    pstate = jnp.where(wake, PM_SWITCHING_ON, st.pstate)
    pstate = jnp.where(idle, PM_SWITCHING_OFF, pstate)
    pstate_end = jnp.where(wake, st.t + boot_s, st.pstate_end)
    pstate_end = jnp.where(idle, st.t + halt_s, pstate_end)
    st = st._replace(pstate=pstate, pstate_end=pstate_end)

    if spec.complex_power:
        # hidden consumer carries the transition work; transition ends when
        # the hidden flow drains (pstate_end stays at +inf)
        lay = spec.layout
        V = spec.n_vm
        hid = jnp.arange(P) + V  # flow-slot indices of hidden consumers
        trans = wake | idle
        amount = jnp.where(wake, params.hidden_work_on, params.hidden_work_off)
        st = st._replace(
            pstate_end=jnp.where(trans, jnp.inf, pstate_end),
            f_pr=st.f_pr.at[hid].set(
                jnp.where(trans, amount, st.f_pr[hid])),
            f_total=st.f_total.at[hid].set(
                jnp.where(trans, amount, st.f_total[hid])),
            f_pl=st.f_pl.at[hid].set(
                jnp.where(trans, 0.2 * params.pm_cores, st.f_pl[hid])),
            f_prov=st.f_prov.at[hid].set(
                jnp.where(trans, lay.cpu0 + jnp.arange(P), st.f_prov[hid])),
            f_cons=st.f_cons.at[hid].set(
                jnp.where(trans, lay.hidden0 + jnp.arange(P), st.f_cons[hid])),
            f_active=st.f_active.at[hid].set(
                jnp.where(trans, True, st.f_active[hid])),
            f_release=st.f_release.at[hid].set(
                jnp.where(trans, st.t, st.f_release[hid])),
            f_kind=st.f_kind.at[hid].set(
                jnp.where(trans, KIND_HIDDEN, st.f_kind[hid])),
        )
    return st


def alwayson(spec, params, ctx, st: CloudState) -> CloudState:
    """Machines keep whatever power state they have (paper baseline)."""
    return st


def ondemand(spec, params, ctx, st: CloudState) -> CloudState:
    return wake_sleep_pass(spec, params, ctx.trace, st)


# --- event-gate triggers (registry ``trigger=``, DESIGN.md §7): each is a
# *necessary* condition for its policy to change state, letting the loop
# stage skip the policy body when nothing it reacts to happened.


def _queued_any(spec, params, ctx, st):
    """A request is queued — the only thing the queue-serving VM policies
    react to.  With no queued task, one serve_queue round selects the old
    value everywhere (every write is ``where(False, ...)`` or an exact
    ``+0.0`` add) and exits: bitwise identity."""
    return ((st.task_state == TASK_PENDING)
            & (ctx.trace.arrival <= st.t)).any()


def _never(spec, params, ctx, st):
    return jnp.bool_(False)


def _wake_sleep_trigger(spec, params, ctx, st):
    """On-demand acts only by waking (needs a queued-core deficit, hence a
    queued task) or sleeping a loadless RUNNING host — both conditions
    checked here verbatim; with neither, every write in
    :func:`wake_sleep_pass` selects the old value (``wake``/``idle`` all
    False), so skipping is bitwise identity."""
    queued = (st.task_state == TASK_PENDING) & (ctx.trace.arrival <= st.t)
    hosted = mc.vms_per_pm(st.vstage != mc.VM_FREE, st.vm_host, spec.n_pm)
    loadless = (st.pstate == PM_RUNNING) & (hosted == 0)
    return queued.any() | loadless.any()


# flow-slot fields rewritten by dispatch, migration, and (under the
# complex power model) the hidden transition consumers
FLOW_FIELDS = ("f_pr", "f_total", "f_pl", "f_prov", "f_cons", "f_active",
               "f_release", "f_kind")
WAKE_SLEEP_DELTA = ("pstate", "pstate_end") + FLOW_FIELDS

registry.register(
    "pm", "alwayson", alwayson, code=0, starts_running=True,
    trigger=_never,
    doc="identity: the whole fleet stays powered on")
registry.register(
    "pm", "ondemand", ondemand, code=1, requires=WAKE_SLEEP_DELTA,
    trigger=_wake_sleep_trigger,
    doc="wake machines against the queued core deficit, sleep loadless ones")

# --------------------------------------------------------------- VM layer


def firstfit(spec, params, ctx, st: CloudState) -> CloudState:
    return serve_queue(spec, params, ctx.trace, st)


def nonqueuing(spec, params, ctx, st: CloudState) -> CloudState:
    return serve_queue(spec, params, ctx.trace, st, reject_unfit=True)


def smallestfirst(spec, params, ctx, st: CloudState) -> CloudState:
    return serve_queue(spec, params, ctx.trace, st, smallest_first=True)


DISPATCH_DELTA = ("task_state", "task_vm", "vstage", "vm_task", "vm_host",
                  "vm_cores", "vm_expiry", "free_cores", "free_mem",
                  "vm_mem", "mem_bound", "overflow") + FLOW_FIELDS

registry.register(
    "vm", "firstfit", firstfit, code=0, requires=DISPATCH_DELTA,
    trigger=_queued_any,
    doc="arrival-ordered queue, first running host with the cores free")
registry.register(
    "vm", "nonqueuing", nonqueuing, code=1, requires=DISPATCH_DELTA,
    trigger=_queued_any,
    doc="first-fit, but a request that cannot start now is rejected")
registry.register(
    "vm", "smallestfirst", smallestfirst, code=2, requires=DISPATCH_DELTA,
    trigger=_queued_any,
    doc="serve the smallest queued task first (backfilling flavour)")
