"""Stage 1 — ``advance``: unified resource sharing + clock-to-horizon.

Computes the per-spreader performance vector from the machine states
(Eq. 5), runs the low-level sharing scheduler (§3.2) for this interval's
rates, finds the event horizon ``dt = min(next completion, next arrival,
PM transition, allocation expiry, meter tick, t_stop)`` (§3.1), advances
the Kahan clock by exactly ``dt`` and drains every live flow.

With active-set compaction enabled (:mod:`repro.core.loop.compact`,
DESIGN.md §7) the fair-share solve, the flow-family horizon lanes and the
fused provider reduction all run over the active-flow bucket and scatter
back — bit-identical to the dense pass, at O(bucket) instead of
O(F + S) per event.  The task-arrival horizon family is likewise O(log T)
against the presorted arrival vector (``ctx.arrival_sorted``) instead of
an O(T) scan, and the allocation-expiry family pre-reduces to one scalar
lane (min is exactly associative).

State delta: ``t``/``t_c``/``n_events`` (the clock), ``meter_next`` (tick
consumed), ``f_pr`` (drained flows), ``processed`` (provider utilisation
counters).  Context delta: the full interval fact sheet (``r``, ``live``,
``thresh``, ``done``, ``dt``, ``t0``/``t_new``, ``has_event``, ``tick``,
``period``, ``compact``, ``compact_ok``) every later stage reads, and the
solve's ``fill_rounds``, ``fill_truncated`` and ``provider_only``.  The
bucket tier comes in ``ctx.bucket``, and whether every active flow's
consumer is its own in ``ctx.own_consumers``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import machine as mc
from ..energy import (PM_OFF, PM_RUNNING, PM_SWITCHING_OFF, PM_SWITCHING_ON,
                      kahan_add)
from ..fairshare import SCHEDULERS
from . import compact as cpk
from .state import BIG, TASK_PENDING, CloudState, StageCtx, live_threshold


def spreader_perf(spec, params, st: CloudState) -> jax.Array:
    """perf[S] from machine states (Eq. 5: power state gates processing)."""
    lay = spec.layout
    P, V = spec.n_pm, spec.n_vm
    cpu_cap = params.pm_cores * params.perf_core
    perf = jnp.zeros((lay.S,), jnp.float32)
    cpu_on = st.pstate == PM_RUNNING
    if spec.complex_power:
        cpu_on = cpu_on | (st.pstate == PM_SWITCHING_ON) | (
            st.pstate == PM_SWITCHING_OFF)
    perf = perf.at[lay.cpu0:lay.cpu0 + P].set(
        jnp.where(cpu_on, cpu_cap, 0.0))
    net_on = st.pstate != PM_OFF
    perf = perf.at[lay.netin0:lay.netin0 + P].set(
        jnp.where(net_on, params.net_bw, 0.0))
    perf = perf.at[lay.netout0:lay.netout0 + P].set(
        jnp.where(net_on, params.net_bw, 0.0))
    perf = perf.at[lay.repo_out].set(params.repo_bw)
    perf = perf.at[lay.repo_disk].set(params.repo_bw)
    vm_on = mc.vm_cpu_active(st.vstage) | (st.vstage == mc.VM_INITIAL_TRANSFER)
    perf = perf.at[lay.vm0:lay.vm0 + V].set(
        jnp.where(vm_on, jnp.maximum(st.vm_cores, 1.0) * params.perf_core, 0.0))
    perf = perf.at[lay.hidden0:lay.hidden0 + P].set(
        jnp.broadcast_to(cpu_cap, (P,)))
    return perf


def spreader_perf_at(spec, params, st: CloudState,
                     sidx: jax.Array) -> jax.Array:
    """Eq. 5 performance for the given spreader indices only — the
    compacted counterpart of :func:`spreader_perf`.  Each lane evaluates
    the same per-region expression the dense builder scatters, so the
    gathered values are bit-identical to ``spreader_perf(...)[sidx]``."""
    lay = spec.layout
    P, V = spec.n_pm, spec.n_vm
    s = jnp.minimum(sidx, lay.S - 1)
    cpu_cap = jnp.asarray(params.pm_cores * params.perf_core, jnp.float32)
    cpu_on = st.pstate == PM_RUNNING
    if spec.complex_power:
        cpu_on = cpu_on | (st.pstate == PM_SWITCHING_ON) | (
            st.pstate == PM_SWITCHING_OFF)
    net_on = st.pstate != PM_OFF

    is_cpu = s < lay.netin0
    is_netin = (s >= lay.netin0) & (s < lay.netout0)
    is_netout = (s >= lay.netout0) & (s < lay.repo_out)
    is_repo = (s >= lay.repo_out) & (s < lay.vm0)
    is_vm = (s >= lay.vm0) & (s < lay.hidden0)

    pm_cpu = jnp.clip(s, 0, P - 1)
    pm_netin = jnp.clip(s - lay.netin0, 0, P - 1)
    pm_netout = jnp.clip(s - lay.netout0, 0, P - 1)
    v_i = jnp.clip(s - lay.vm0, 0, V - 1)

    vm_on = mc.vm_cpu_active(st.vstage) | (st.vstage == mc.VM_INITIAL_TRANSFER)
    net_bw = jnp.asarray(params.net_bw, jnp.float32)
    repo_bw = jnp.asarray(params.repo_bw, jnp.float32)
    perf_core = jnp.asarray(params.perf_core, jnp.float32)

    out = jnp.broadcast_to(cpu_cap, s.shape)              # hidden suffix
    out = jnp.where(is_vm, jnp.where(
        vm_on[v_i],
        jnp.maximum(st.vm_cores[v_i], 1.0) * perf_core, 0.0), out)
    out = jnp.where(is_repo, repo_bw, out)
    out = jnp.where(is_netout,
                    jnp.where(net_on[pm_netout], net_bw, 0.0), out)
    out = jnp.where(is_netin,
                    jnp.where(net_on[pm_netin], net_bw, 0.0), out)
    out = jnp.where(is_cpu,
                    jnp.where(cpu_on[pm_cpu], cpu_cap, 0.0), out)
    return out.astype(jnp.float32)


def advance(ctx: StageCtx, st: CloudState):
    spec, params, trace = ctx.spec, ctx.params, ctx.trace
    lay = spec.layout
    P, V, T = spec.n_pm, spec.n_vm, trace.n
    F = V + P
    thresh = live_threshold(st.f_total)
    live = st.f_active & (st.t >= st.f_release) & (st.f_pr > thresh)
    rate_fn = SCHEDULERS[spec.scheduler]
    # utilisation caps lie below their VM spreader's share (DESIGN.md §7)
    flow_caps = getattr(trace, "util", None) is not None
    # no two active flows name one consumer: the jnp max-min solve reduces
    # over the providers alone, to the same bits (DESIGN.md §7)
    owned = (ctx.own_consumers
             if (spec.scheduler, spec.backend) == ("maxmin", "jnp") else None)

    if ctx.bucket is not None:
        # ---- compacted fair-share solve (DESIGN.md §7) ------------------
        # The solve sees the same live flows, capacities and rate limits in
        # the same index order, so its progressive-filling rounds — and the
        # resulting rates — are bit-identical to the dense call.  The
        # driver hands over the gather it built to choose the tier, if any.
        cp = (cpk.build_tier(spec, st, ctx.bucket) if ctx.compact is None
              else ctx.compact)
        live_b = cpk.gather_flows(cp, live, False)
        f_pr_b = cpk.gather_flows(cp, st.f_pr, 0.0)
        f_pl_b = cpk.gather_flows(cp, st.f_pl, 0.0)
        f_rel_b = cpk.gather_flows(cp, st.f_release, jnp.inf)
        perf_b = spreader_perf_at(spec, params, st, cp.sidx)
        r_b, fill_rounds, truncated = rate_fn(
            cp.bprov, cp.bcons, f_pl_b, live_b, perf_b,
            backend=spec.backend, max_iters=spec.max_fill_iters,
            flow_caps=flow_caps, owned=owned)
        r = cpk.scatter_flows(cp, F, r_b)
        flow_cand = [f_pr_b / jnp.maximum(r_b, 1e-30),   # completion  [FB]
                     f_rel_b - st.t]                     # latency     [FB]
        flow_mask = [live_b & (r_b > 0),
                     cp.fvalid & (st.t < f_rel_b)]
    else:
        cp = None
        perf = spreader_perf(spec, params, st)
        r, fill_rounds, truncated = rate_fn(
            st.f_prov, st.f_cons, st.f_pl, live, perf,
            backend=spec.backend, max_iters=spec.max_fill_iters,
            flow_caps=flow_caps, owned=owned)
        flow_cand = [st.f_pr / jnp.maximum(r, 1e-30),    # completion   [F]
                     st.f_release - st.t]                # latency      [F]
        flow_mask = [live & (r > 0),
                     st.f_active & (st.t < st.f_release)]

    # ---- event horizon: one fused masked-min reduction ------------------
    # Seven candidate families — flow completion, latency-gate release,
    # task arrival, PM power transition, allocation expiry, meter tick,
    # t_stop — reduced by a single masked min.  Min is order-insensitive
    # for the values that can occur here (no NaNs; a ±0 tie is erased by
    # the clamp below), so pre-reducing a family to one scalar lane, or
    # collapsing the arrival family to the first strictly-future sorted
    # arrival, is bit-identical to the flat per-lane min.
    trans = (st.pstate == PM_SWITCHING_ON) | (st.pstate == PM_SWITCHING_OFF)
    # Allocation-expiry family, pre-reduced (ALLOCATED slots only).
    exp_min = jnp.min(jnp.where(
        (st.vstage == mc.VM_ALLOCATED) & jnp.isfinite(st.vm_expiry),
        st.vm_expiry - st.t, BIG))
    tail_cand = [exp_min, st.meter_next - st.t, ctx.t_stop - st.t]
    tail_mask = [jnp.bool_(True), jnp.isfinite(st.meter_next),
                 jnp.isfinite(ctx.t_stop)]
    if ctx.arrival_sorted is not None:
        # O(log T) arrival family: the clock is monotone and dispatch
        # requires ``arrival <= t``, so every strictly-future arrival
        # still belongs to a PENDING task — the dense family's mask — and
        # its minimum is the first sorted arrival past ``t``.
        nxt = jnp.searchsorted(ctx.arrival_sorted, st.t, side="right")
        tail_cand.append(
            ctx.arrival_sorted[jnp.minimum(nxt, T - 1)] - st.t)
        tail_mask.append(nxt < T)
    # Streaming windows (DESIGN.md §8) add one more candidate: the first
    # arrival of the next, not-yet-loaded trace window.  Arrivals are
    # window-sorted, so this single sentinel is exactly the min the
    # monolithic engine would take over every future task's arrival — the
    # value (``t_next - t``) and mask (``pending future arrival``) match
    # the monolithic arrival lanes bit-for-bit.  ``ctx.t_next is None``
    # (monolithic run) keeps the candidate vector untouched.
    if ctx.t_next is not None:
        tail_cand.append(ctx.t_next - st.t)
        tail_mask.append(jnp.isfinite(ctx.t_next) & (ctx.t_next > st.t))
    dense_arrival = ([] if ctx.arrival_sorted is not None
                     else [(trace.arrival - st.t,
                            (st.task_state == TASK_PENDING)
                            & (trace.arrival > st.t))])
    cand = jnp.concatenate(
        flow_cand + [c for c, _ in dense_arrival]
        + [st.pstate_end - st.t,                         # PM transition [P]
           jnp.stack(tail_cand)])
    mask = jnp.concatenate(
        flow_mask + [m for _, m in dense_arrival]
        + [trans & jnp.isfinite(st.pstate_end),
           jnp.stack(tail_mask)])
    if spec.backend == "pallas":
        from repro.kernels import ops as _kops
        dt = _kops.masked_min_pallas(cand, mask)
    else:
        dt = jnp.min(jnp.where(mask, cand, BIG))
    has_event = dt < BIG
    dt = jnp.where(has_event, jnp.maximum(dt, 0.0), 0.0)

    # ---- clock + sampled-meter tick ------------------------------------
    t_new, t_c = kahan_add(st.t, st.t_c, dt)
    tick = jnp.isfinite(st.meter_next) & (st.meter_next <= t_new)
    period = jnp.asarray(params.metering_period, jnp.float32)
    meter_next = jnp.where(tick, st.meter_next + period, st.meter_next)

    # ---- drain flows ----------------------------------------------------
    f_pr = jnp.where(live, jnp.maximum(st.f_pr - r * dt, 0.0), st.f_pr)
    done = live & (f_pr <= thresh)
    # One 2-column scatter-add covers both provider-side reductions of the
    # interval: delivered rate (observe's utilisation numerator) and
    # processed work.  Columns scatter independently in identical segment
    # order, so each is bit-identical to its standalone segment_sum; the
    # compacted variant reduces the same (live) terms in the same flow
    # order and scatters per-spreader sums back (dropped terms are exact
    # ``+0.0`` contributions).
    if cp is not None:
        SBn = cp.sidx.shape[0]
        stats_b = jax.ops.segment_sum(
            jnp.stack([jnp.where(live_b, r_b, 0.0),
                       jnp.where(live_b, r_b * dt, 0.0)], axis=-1),
            cp.bprov, num_segments=SBn)
        delivered = jnp.zeros((lay.S,), jnp.float32).at[cp.sidx].set(
            stats_b[:, 0], mode="drop")
        processed = st.processed.at[cp.sidx].add(stats_b[:, 1], mode="drop")
    else:
        prov_stats = jax.ops.segment_sum(
            jnp.stack([jnp.where(live, r, 0.0),
                       jnp.where(live, r * dt, 0.0)], axis=-1),
            st.f_prov, num_segments=lay.S)
        delivered = prov_stats[:, 0]
        processed = st.processed + prov_stats[:, 1]

    ctx = ctx._replace(r=r, live=live, thresh=thresh, done=done,
                       delivered=delivered, dt=dt,
                       t0=st.t, t_new=t_new, has_event=has_event,
                       tick=tick, period=period, compact=cp,
                       compact_ok=None if cp is None else cp.ok,
                       fill_rounds=fill_rounds, fill_truncated=truncated,
                       provider_only=owned)
    st = st._replace(t=t_new, t_c=t_c, n_events=st.n_events + 1,
                     meter_next=meter_next, f_pr=f_pr, processed=processed)
    return ctx, st
