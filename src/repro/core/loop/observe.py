"""Stage 2 — ``observe``: the PR 2 metering hook over ``[t0, t_new]``.

Builds one :class:`~repro.core.energy.SimView` of the interval (paper
Fig. 7: utilisation counters -> consumption models -> meters) and calls
the pure :func:`repro.core.energy.observe` hook, which integrates every
meter in the declarative stack exactly over the piecewise-constant
interval and drives the paper's sampled meter on its tick.

State delta: ``meters`` only.  Context delta: publishes the ``view`` so
the policy stages (``pm_sched`` / ``vm_sched``) can read the same
observation surface the meters consumed, and the influence-label
``label_rounds``.

Everything in the view is read from *interval-start* facts: the rates in
``ctx.r``/``ctx.live`` were computed against the pre-advance state and are
constant over the whole interval, and the clock reference is ``ctx.t0``
(the ``advance`` stage has already moved ``st.t`` to the interval end).

With active-set compaction on (``ctx.compact``, DESIGN.md §7) the Eq. 6
half runs over the active-flow bucket: influence labels propagate over
the compacted live edges (every live edge has both endpoints in the
spreader bucket, and an untouched spreader keeps its singleton
self-label), and the per-VM attribution inputs scatter back into dense
``V``-sized views — a VM outside the bucket has no live flow, hence no
group membership and an exact-``+0.0`` rate fraction either way.  The
meter *integration* itself stays dense: the per-VM Kahan accumulators
fold their compensation term even on a zero-power interval, so skipping
settled VMs would not be bit-identical.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import machine as mc
from ..energy import MODEL_LINEAR, SimView, instantaneous_power, observe
from ..influence import coupled_vm_counts, influence_labels_rounds
from . import compact as cpk
from .state import TASK_PENDING, CloudState, StageCtx


def _eq6_views(ctx: StageCtx, st: CloudState, cpu_del: jax.Array):
    """(vm_rate_frac, vm_host, vms_on_host, label_rounds) — Eq. 6 group
    membership via the influence components, dense or bucket-compacted."""
    spec = ctx.spec
    lay = spec.layout
    P, V = spec.n_pm, spec.n_vm
    r, live = ctx.r, ctx.live
    cp = ctx.compact

    if cp is None:
        labels, rounds = influence_labels_rounds(st.f_prov, st.f_cons, live,
                                                 lay.S)
        in_grp, vms_on_host = coupled_vm_counts(
            labels, lay.cpu0 + st.vm_host, lay.vm0 + jnp.arange(V),
            st.vm_host, P)
        vm_rate_frac = (jnp.where(in_grp, r[:V], 0.0)
                        / jnp.maximum(cpu_del[st.vm_host], 1e-30))
        vm_host = jnp.where(in_grp, st.vm_host, -1)
        return vm_rate_frac, vm_host, vms_on_host, rounds

    live_b = cpk.gather_flows(cp, live, False)
    labels_b, rounds = cpk.influence_labels_compact(cp, live_b)
    is_vm = cp.fvalid & (cp.fidx < V)
    v_scatter = jnp.where(is_vm, cp.fidx, V)          # V = scatter drop
    v_c = jnp.minimum(v_scatter, V - 1)
    vmh_b = st.vm_host[v_c]
    la = cpk.label_lookup(cp, labels_b, lay.cpu0 + vmh_b)
    lb = cpk.label_lookup(cp, labels_b, lay.vm0 + v_c)
    in_grp_b = is_vm & (la == lb)
    vms_on_host = mc.vms_per_pm(in_grp_b, jnp.where(is_vm, vmh_b, P), P)
    r_b = cpk.gather_flows(cp, r, 0.0)
    frac_b = (jnp.where(in_grp_b, r_b, 0.0)
              / jnp.maximum(cpu_del[vmh_b], 1e-30))
    vm_rate_frac = jnp.zeros((V,), jnp.float32).at[v_scatter].set(
        frac_b, mode="drop")
    vm_host = jnp.full((V,), -1, jnp.int32).at[v_scatter].set(
        jnp.where(in_grp_b, vmh_b, -1), mode="drop")
    return vm_rate_frac, vm_host, vms_on_host, rounds


def build_view(ctx: StageCtx, st: CloudState):
    """The meter stack's observation surface for the current interval, and
    the influence-label rounds it took (``None`` without per-VM meters).

    The per-VM half wires Eq. 6 through :mod:`repro.core.influence`: a VM
    draws power iff its spreader sits in its host CPU spreader's influence
    group, and the idle-share divisor is that group's VM count
    (``|G(s_vm)| - 1``).
    """
    spec, params, trace = ctx.spec, ctx.params, ctx.trace
    lay = spec.layout
    P, V = spec.n_pm, spec.n_vm
    table = params.power

    # Per-provider delivered rate was already reduced by `advance`'s fused
    # provider scatter-add — reuse it instead of a second segment_sum.
    delivered = ctx.delivered
    cpu_del = delivered[lay.cpu0:lay.cpu0 + P]
    cpu_cap = jnp.maximum(params.pm_cores * params.perf_core, 1e-30)
    util = cpu_del / cpu_cap
    power = instantaneous_power(table, st.pstate, util)
    p_idle = table.p_min[st.pstate]
    p_span = jnp.where(table.mode[st.pstate] == MODEL_LINEAR,
                       table.p_max[st.pstate] - p_idle, 0.0)

    if spec.meters.vm_direct:
        vm_rate_frac, vm_host, vms_on_host, label_rounds = _eq6_views(
            ctx, st, cpu_del)
    else:
        label_rounds = None
        vms_on_host = jnp.zeros((P,), jnp.int32)
        vm_rate_frac = jnp.zeros((V,), jnp.float32)
        vm_host = jnp.full((V,), -1, jnp.int32)

    hosted = st.vstage != mc.VM_FREE
    queued = (st.task_state == TASK_PENDING) & (trace.arrival <= ctx.t0)
    return SimView(
        pm_power=power, pm_idle=p_idle, pm_span=p_span, pm_util=util,
        vm_rate_frac=vm_rate_frac, vm_host=vm_host, vms_on_host=vms_on_host,
        n_hosted=hosted.sum().astype(jnp.float32),
        n_queued=queued.sum().astype(jnp.float32),
        tick=ctx.tick, period=ctx.period), label_rounds


def observe_stage(ctx: StageCtx, st: CloudState):
    view, label_rounds = build_view(ctx, st)
    meters = observe(ctx.spec.meters, ctx.params.meter, view, ctx.dt,
                     st.meters)
    return (ctx._replace(view=view, label_rounds=label_rounds),
            st._replace(meters=meters))
