"""Stage 3 — ``vm_lifecycle``: the Fig. 6 VM state machine.

Every flow completion reported by ``advance`` (``ctx.done``) moves its VM
slot along the paper's lifecycle by rewriting the slot's single
consumption in place: image transfer -> boot work -> the user task ->
destroy, plus the migration arrival (suspend-transfer completed on the
wire -> resume the saved task on the destination host) and the §3.4.2
allocation-expiry self-defence.

State delta: the VM-flow prefix of every ``f_*`` array, ``vstage``,
``vm_host`` (migration arrivals), ``free_cores`` / ``free_mem`` (released
cores and memory), ``task_state`` / ``t_done`` (completions).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import machine as mc
from ..arrays import KIND_BOOT, KIND_IMAGE_XFER, KIND_TASK
from .state import BIG, KIND_MIGRATE, TASK_DONE, CloudState, StageCtx


def vm_lifecycle(ctx: StageCtx, st: CloudState):
    # Event gate (DESIGN.md §7): the stage reacts only to VM-flow
    # completions and allocation expiries.  With neither, every write
    # below selects the old value (all the ``*_done``/``expired`` masks
    # are False, the scatter indices all drop, and ``free_cores`` gains an
    # exact ``+0.0``) — skipping is bitwise identity.  Under vmap the cond
    # lowers to a select; single-scenario runs skip the body outright.
    fired = (ctx.done[:ctx.spec.n_vm].any()
             | ((st.vstage == mc.VM_ALLOCATED)
                & (st.vm_expiry <= ctx.t_new)).any())
    return ctx._replace(lifecycle_gate=fired), jax.lax.cond(
        fired, lambda s: _vm_lifecycle_body(ctx, s), lambda s: s, st)


def _vm_lifecycle_body(ctx: StageCtx, st: CloudState) -> CloudState:
    spec, params, trace = ctx.spec, ctx.params, ctx.trace
    lay = spec.layout
    P, V, T = spec.n_pm, spec.n_vm, trace.n
    vm_slot = jnp.arange(V)
    t_new = ctx.t_new

    # Work on the VM-flow prefix [:V]; the hidden-consumer suffix belongs
    # to the pm_power stage.
    vdone = ctx.done[:V]
    kind = st.f_kind[:V]
    host = st.vm_host
    xfer_done = vdone & (kind == KIND_IMAGE_XFER)
    boot_done = vdone & (kind == KIND_BOOT)
    task_done = vdone & (kind == KIND_TASK)
    mig_done = vdone & (kind == KIND_MIGRATE)

    v_pr, v_total = st.f_pr[:V], st.f_total[:V]
    v_pl, v_kind = st.f_pl[:V], st.f_kind[:V]
    v_prov, v_cons = st.f_prov[:V], st.f_cons[:V]
    v_release, v_active = st.f_release[:V], st.f_active[:V]

    # image transfer -> startup: flow becomes boot work on the host CPU
    v_pr = jnp.where(xfer_done, params.boot_work, v_pr)
    v_total = jnp.where(xfer_done, params.boot_work, v_total)
    v_prov = jnp.where(xfer_done | boot_done, lay.cpu0 + host, v_prov)
    v_cons = jnp.where(xfer_done | boot_done, lay.vm0 + vm_slot, v_cons)
    v_pl = jnp.where(xfer_done, BIG, v_pl)
    v_kind = jnp.where(xfer_done, KIND_BOOT, v_kind)
    v_release = jnp.where(xfer_done | boot_done | mig_done, t_new, v_release)
    vstage = jnp.where(xfer_done, mc.VM_STARTUP, st.vstage)

    # boot -> running: flow becomes the user task
    tid = jnp.maximum(st.vm_task, 0)
    twork = trace.work[tid]
    tcores = trace.cores[tid]
    # a VM that uses a share of its cores runs at that share (DESIGN.md §7)
    util = getattr(trace, "util", None)
    cap = (tcores * params.perf_core if util is None
           else util[tid] * tcores * params.perf_core)
    v_pr = jnp.where(boot_done, twork, v_pr)
    v_total = jnp.where(boot_done, twork, v_total)
    v_pl = jnp.where(boot_done, cap, v_pl)
    v_kind = jnp.where(boot_done, KIND_TASK, v_kind)
    vstage = jnp.where(boot_done, mc.VM_RUNNING, vstage)

    # migration arrives: resume the task on the destination host
    new_host = jnp.where(mig_done, st.vm_mig_dst, host)
    v_pr = jnp.where(mig_done, st.vm_saved_pr, v_pr)
    v_total = jnp.where(mig_done, jnp.maximum(st.vm_saved_pr, 1e-9), v_total)
    v_pl = jnp.where(mig_done, cap, v_pl)
    v_kind = jnp.where(mig_done, KIND_TASK, v_kind)
    v_prov = jnp.where(mig_done, lay.cpu0 + new_host, v_prov)
    v_cons = jnp.where(mig_done, lay.vm0 + vm_slot, v_cons)
    vstage = jnp.where(mig_done, mc.VM_RUNNING, vstage)

    # task done -> destroy VM, release cores (and memory), complete task.
    # Cores freed by completion and by allocation expiry (§3.4.2, applied
    # below) share one scatter-add with the memory freed by completion
    # (allocations hold none); the columns reduce independently, so each
    # matches its standalone segment_sum bit-for-bit.
    expired = (st.vstage == mc.VM_ALLOCATED) & (st.vm_expiry <= t_new)
    cols = [jnp.where(task_done, st.vm_cores, 0.0),
            jnp.where(expired, st.vm_cores, 0.0)]
    if st.free_mem is not None:
        cols.append(jnp.where(task_done, st.vm_mem, 0.0))
    freed = jax.ops.segment_sum(jnp.stack(cols, axis=-1), host,
                                num_segments=P)
    free_cores = st.free_cores + freed[:, 0]
    task_state = st.task_state
    t_done_arr = st.t_done
    tslot = jnp.where(task_done, st.vm_task, T)  # T = scatter drop
    task_state = task_state.at[tslot].set(TASK_DONE, mode="drop")
    t_done_arr = t_done_arr.at[tslot].set(t_new, mode="drop")
    vstage = jnp.where(task_done, mc.VM_FREE, vstage)
    v_active = jnp.where(task_done, False, v_active)

    f_pr = st.f_pr.at[:V].set(v_pr)
    f_total = st.f_total.at[:V].set(v_total)
    f_pl = st.f_pl.at[:V].set(v_pl)
    f_prov = st.f_prov.at[:V].set(v_prov)
    f_cons = st.f_cons.at[:V].set(v_cons)
    f_release = st.f_release.at[:V].set(v_release)
    f_kind = st.f_kind.at[:V].set(v_kind)
    f_active = st.f_active.at[:V].set(v_active)

    # allocation expiry (§3.4.2 self-defence)
    free_cores = free_cores + freed[:, 1]
    vstage = jnp.where(expired, mc.VM_FREE, vstage)

    if st.free_mem is not None:
        st = st._replace(free_mem=st.free_mem + freed[:, 2])
    return st._replace(
        f_pr=f_pr, f_total=f_total, f_pl=f_pl, f_prov=f_prov, f_cons=f_cons,
        f_release=f_release, f_kind=f_kind, f_active=f_active,
        task_state=task_state, t_done=t_done_arr,
        vstage=vstage, vm_host=new_host, free_cores=free_cores)
