"""Stage 6 — ``vm_sched``: the VM scheduler policy hook (§3.5.1).

Pure dispatch, like ``pm_sched``: the stage ``lax.switch``es on
``params.vm_sched`` over the registered branch list of the open policy
registry (:mod:`repro.sched.registry`, DESIGN.md §6); the builtin
first-fit / non-queuing / smallest-first policies live in
:mod:`repro.sched.policies.baseline`.

What stays here is the policy-free *machinery* those policies share:
:func:`serve_queue`, the masked inner loop that serves the request queue
until blocked or empty.  Its two knobs (queue ordering key, whether an
unservable head is rejected) are plain Python flags — a policy is a
partial application, and each specialisation is bitwise identical to the
old data-masked selection because ``jnp.where`` on a concrete flag folds
to the selected operand.

State delta: per dispatched request, the allocated VM slot (``vstage`` /
``vm_*``), its image-transfer flow, the host's ``free_cores`` (and
``free_mem``), and the task binding; per rejected request, its
``task_state``.  Context delta: the gate's verdict, the queue-serving
rounds (``serve_rounds``) and the memory-bound dispatches
(``mem_bound``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sched import registry

from .. import machine as mc
from ..arrays import KIND_IMAGE_XFER
from .state import (BIG, TASK_ACTIVE, TASK_PENDING, TASK_REJECTED,
                    CloudState, StageCtx)


def serve_queue(spec, params, trace, st: CloudState, *,
                smallest_first: bool = False,
                reject_unfit: bool = False) -> CloudState:
    """Serve the request queue until blocked or empty.

    ``smallest_first`` orders the queue by requested cores instead of
    arrival time; ``reject_unfit`` rejects a head request no running host
    can currently fit (the paper's non-queuing cloud) instead of leaving
    it queued.  Oversized requests (larger than one PM) are always
    rejected.

    When the trace carries ``mem`` (DESIGN.md §7), a host fits a request
    only with both its cores and its memory free, a request larger than a
    PM in either is oversized, and a dispatch takes both; ``mem_bound``
    counts the dispatches whose host differs from a fit on cores alone.
    """
    lay = spec.layout
    P, V, T = spec.n_pm, spec.n_vm, trace.n
    qkey = trace.cores if smallest_first else trace.arrival
    # Global task ids (streaming slot tables, DESIGN.md §8): slot order is
    # recycled, so queue-key ties must break on the *global* id to match
    # the monolithic engine, whose ``argmin`` tie-break is the task index
    # — i.e. the global id.  A monolithic trace (``gid is None``) keeps
    # the plain first-index ``argmin``: identical choice, identical
    # program.
    gid = getattr(trace, "gid", None)
    mem = getattr(trace, "mem", None)

    def queued_mask(task_state):
        return (task_state == TASK_PENDING) & (trace.arrival <= st.t)

    def cond(s):
        st2, progressed = s
        return progressed

    def body(s):
        st2, _ = s
        queued = queued_mask(st2.task_state)
        any_q = queued.any()
        key = jnp.where(queued, qkey, jnp.inf)
        if gid is None:
            head = jnp.argmin(key).astype(jnp.int32)
        else:
            best = jnp.min(key)
            cand = queued & (key == best)
            head_gid = jnp.min(jnp.where(cand, gid, jnp.iinfo(jnp.int32).max))
            head = jnp.argmax(cand & (gid == head_gid)).astype(jnp.int32)
        h_cores = trace.cores[head]

        oversize = h_cores > params.pm_cores  # can never fit -> reject always
        fit = mc.pm_accepting(st2.pstate) & (st2.free_cores >= h_cores)
        if mem is not None:
            h_mem = mem[head]
            oversize = oversize | (h_mem > params.pm_mem)
            pm_cores_only = jnp.argmax(fit).astype(jnp.int32)
            fit = fit & (st2.free_mem >= h_mem)
        any_fit = fit.any()
        pm = jnp.argmax(fit).astype(jnp.int32)  # first fit
        vfree = st2.vstage == mc.VM_FREE
        any_v = vfree.any()
        v = jnp.argmax(vfree).astype(jnp.int32)

        blocked = oversize | ~any_fit if reject_unfit else oversize
        do_reject = any_q & blocked
        do_dispatch = any_q & ~do_reject & any_fit & any_v
        overflow = any_q & ~do_reject & any_fit & ~any_v

        # --- reject head ---
        task_state = st2.task_state.at[head].set(
            jnp.where(do_reject, TASK_REJECTED, st2.task_state[head]))

        # --- dispatch head: VM -> INITIAL_TRANSFER, flow slot = image xfer ---
        def wv(arr, val):
            return arr.at[v].set(jnp.where(do_dispatch, val, arr[v]))

        st2 = st2._replace(
            task_state=task_state.at[head].set(
                jnp.where(do_dispatch, TASK_ACTIVE, task_state[head])),
            task_vm=st2.task_vm.at[head].set(
                jnp.where(do_dispatch, v, st2.task_vm[head])),
            vstage=wv(st2.vstage, mc.VM_INITIAL_TRANSFER),
            vm_task=wv(st2.vm_task, head),
            vm_host=wv(st2.vm_host, pm),
            vm_cores=wv(st2.vm_cores, h_cores),
            vm_expiry=wv(st2.vm_expiry, jnp.inf),
            free_cores=st2.free_cores.at[pm].add(
                jnp.where(do_dispatch, -h_cores, 0.0)),
            f_pr=wv(st2.f_pr, params.image_mb),
            f_total=wv(st2.f_total, params.image_mb),
            f_pl=wv(st2.f_pl, BIG),
            f_prov=wv(st2.f_prov, lay.repo_out),
            f_cons=wv(st2.f_cons, lay.netin0 + pm),
            f_active=wv(st2.f_active, True),
            f_release=wv(st2.f_release, st.t + params.latency_s),
            f_kind=wv(st2.f_kind, KIND_IMAGE_XFER),
            overflow=st2.overflow | overflow,
        )
        if mem is not None:
            st2 = st2._replace(
                vm_mem=wv(st2.vm_mem, h_mem),
                free_mem=st2.free_mem.at[pm].add(
                    jnp.where(do_dispatch, -h_mem, 0.0)),
                mem_bound=st2.mem_bound + (
                    do_dispatch & (pm != pm_cores_only)).astype(jnp.int32))
        progressed = do_dispatch | do_reject
        return st2, progressed

    st, _ = jax.lax.while_loop(cond, body, (st, jnp.bool_(True)))
    return st


def vm_sched(ctx: StageCtx, st: CloudState):
    code = jnp.asarray(ctx.params.vm_sched, jnp.int32)
    # Event gate (registry trigger, DESIGN.md §7): skip the whole policy
    # switch when the selected policy declares nothing-to-react-to —
    # e.g. the builtin dispatchers are bitwise identity on an empty
    # request queue.  Under vmap the cond lowers to a select (both sides
    # computed per lane), so batched sweeps stay one program.
    may = jax.lax.switch(code, registry.trigger_branches("vm", ctx), st)

    def run(s):
        s2 = jax.lax.switch(code, registry.stage_branches("vm", ctx), s)
        # serve_queue's rounds: every round but the last settles (starts or
        # rejects) exactly one queued task; the last settles none.
        settled = jnp.sum((s.task_state == TASK_PENDING)
                          & (s2.task_state != TASK_PENDING))
        return s2, settled.astype(jnp.int32) + 1

    st0 = st
    st, rounds = jax.lax.cond(may, run, lambda s: (s, jnp.int32(0)), st)
    mem_bound = (None if st.mem_bound is None
                 else st.mem_bound - st0.mem_bound)
    return ctx._replace(vm_gate=may, serve_rounds=rounds,
                        mem_bound=mem_bound), st
