"""The thin driver composing the staged subsystem pipeline (DESIGN.md §5).

One loop iteration is exactly the stage sequence :data:`STAGES`:

    advance -> observe -> vm_lifecycle -> pm_power -> pm_sched -> vm_sched

followed by the :func:`termination` verdict.  The driver owns *no*
simulation semantics — it snapshots the machine/task state for the
progress guard, folds the state through the stages, and decides whether
the ``lax.while_loop`` continues.  Subsystems are added by editing the
stage modules; scheduling policies are added by registering them with
:mod:`repro.sched.registry` — never by editing this package.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import tracing
from ..energy import PM_SWITCHING_OFF, PM_SWITCHING_ON
from . import advance, lifecycle, observe, pm_sched, power, vm_sched
from .compact import build_compact, compact_tiers, dense_reachable
from .state import (TASK_PENDING, CloudState, LoopCounters, StageCtx,
                    live_threshold)

STAGES = (
    advance.advance,        # §3.1/§3.2 sharing + clock-to-horizon + drain
    observe.observe_stage,  # §3.3 meter stack over [t0, t_new]
    lifecycle.vm_lifecycle,  # §3.4.3 Fig. 6 VM transitions (+ migration)
    power.pm_power,         # §3.4.2 PM power-state transitions
    pm_sched.pm_sched,      # §3.5.1 PM policy hook (registry dispatch)
    vm_sched.vm_sched,      # §3.5.1 VM policy hook (registry dispatch)
)
# Each stage runs under its jax.named_scope (tracing.STAGE_SCOPES, same
# order): the scope names its ops in a profiler trace and changes nothing
# else in the compiled program.

# The management suffix of the pipeline (policy hooks).  Streaming windows
# gate exactly these two stages off on the hand-over iteration (the one
# whose horizon lands the clock on the next window's first arrival): the
# monolithic engine runs them *with* that arrival already queued, so the
# streaming step defers them to the next window's management pass, where
# the arrival is present — same stage inputs, bit-identical outputs
# (DESIGN.md §8).
N_MANAGEMENT_STAGES = 2

# The prefix of the pipeline that runs on the active-set bucket (DESIGN.md
# §7): ``advance`` builds the gather, ``observe`` reads it.  With two
# bucket tiers the driver runs these stages inside one ``lax.cond`` per
# pass, on the smallest tier that holds the active flows; only the
# bucket-independent facts (``StageCtx.facts``) leave it.
N_COMPACTED_STAGES = 2

# The batch axis name of ``vmap``-ed runs (``simulate_batch``, the shard
# and stream-batch runners).  Under it the tier predicate is the largest
# active count over the lanes, so the cond stays one branch for the whole
# batch instead of lowering to a select of both tiers.
LANE_AXIS = "lanes"


def termination(ctx: StageCtx, st: CloudState, snap) -> CloudState:
    """Continue while events remain, unless ``t_stop`` was reached.

    Progress guard: continue only if the horizon found an event or the
    management stages changed machine/task state this iteration (e.g. the
    very first dispatch at t=0).  A queued-but-unservable rest state
    (everything off, nothing waking) therefore terminates instead of
    spinning to ``max_events``.
    """
    ts0, vs0, ps0, fa0 = snap
    trace = ctx.trace
    queued = (st.task_state == TASK_PENDING) & (trace.arrival <= st.t)
    live2 = st.f_active & (st.f_pr > live_threshold(st.f_total))
    pend2 = (st.task_state == TASK_PENDING) & (trace.arrival > st.t)
    trans2 = (st.pstate == PM_SWITCHING_ON) | (st.pstate == PM_SWITCHING_OFF)
    more = live2.any() | pend2.any() | trans2.any() | queued.any()
    hit_stop = jnp.isfinite(ctx.t_stop) & (st.t >= ctx.t_stop)
    if ctx.t_next is not None:
        # Streaming window (DESIGN.md §8): tasks beyond this window are
        # work that remains (the monolithic pend2 would see them), and
        # reaching the next window's first arrival ends this window's
        # loop — the next step resumes from the identical carried state.
        more = more | (jnp.isfinite(ctx.t_next) & (ctx.t_next > st.t))
        hit_stop = hit_stop | (jnp.isfinite(ctx.t_next)
                               & (st.t >= ctx.t_next))
    changed = (jnp.any(st.task_state != ts0) | jnp.any(st.vstage != vs0)
               | jnp.any(st.pstate != ps0) | jnp.any(st.f_active != fa0))
    return st._replace(running=(ctx.has_event | changed) & more & ~hit_stop)


def own_consumers(spec, st: CloudState) -> jax.Array:
    """Whether no active flow names a shared consumer, an image transfer's
    or a migration's ``netin``: every consumer at or above ``layout.vm0``
    is one flow's own VM or hidden spreader, so the fair-share solve may
    reduce over the providers alone (DESIGN.md §7)."""
    return ~jnp.any(st.f_active & (st.f_cons < spec.layout.vm0))


# Coalesced event stepping (DESIGN.md §7): how many pipeline passes one
# ``lax.while_loop`` body runs when ``spec.steps_per_iter == 0`` (auto).
# Tuned by ``benchmarks/microbench_steps.py``: on XLA:CPU the while_loop
# round-trip costs a few hundred nanoseconds, so K = 1 wins outright
# (measured: K=1 3829 ev/s, K=2 3818, K=4 3623 at 20 PM x 256 VM) and
# coalescing is kept as an opt-in (``spec.steps_per_iter``) for
# dispatch-bound backends where the per-iteration overhead is worth
# amortizing across cond-guarded extra passes.
DEFAULT_STEPS_PER_ITER = 1


def steps_per_iter(spec) -> int:
    """The spec-static micro-step count K (>= 1)."""
    k = getattr(spec, "steps_per_iter", 0)
    return int(k) if k > 0 else DEFAULT_STEPS_PER_ITER


def make_body(spec, params, trace, t_stop, t_next=None, axis_name=None):
    """The ``lax.while_loop`` body over a ``(state, compact_ok, counters)``
    carry: K unrolled pipeline passes (coalesced event stepping, DESIGN.md
    §7) guarded by an early-settled mask.  ``counters`` sums the
    :class:`LoopCounters` each pass reports through its context.

    ``axis_name`` names the ``vmap`` axis the body runs under, if any
    (:data:`LANE_AXIS`): the bucket tier, and whether the fair-share solve
    reduces over providers alone, are then chosen for all lanes at once.

    ``t_next`` (streaming windows only, DESIGN.md §8) is the first arrival
    of the next trace window; ``None`` — the monolithic engine — composes
    exactly the pre-streaming body.  All events sharing one horizon
    timestamp are already coalesced *within* a pass (every stage applies
    its full completion/transition mask at ``t_new``); the K micro-steps
    amortize the ``while_loop`` dispatch across successive horizons.  A
    pass whose entry state is settled (``~running`` or the event budget
    spent) is discarded wholesale by a tree-select, so the carried state
    and event count are bit-identical to K == 1.
    """
    # Hoisted per-trace precomputation: the sorted arrival vector the
    # horizon's O(log T) searchsorted runs against (a loop constant).
    arrival_sorted = jnp.sort(jnp.asarray(trace.arrival, jnp.float32))

    stages = list(zip(tracing.STAGE_SCOPES, STAGES))
    compacted = stages[:N_COMPACTED_STAGES]
    rest = stages[N_COMPACTED_STAGES:-N_MANAGEMENT_STAGES]
    tiers = compact_tiers(spec)
    # Under the auto rule a pass whose active set outgrows the largest
    # tier runs the compacted stages dense in the same program, where the
    # trace and the slots can hold such a set (DESIGN.md §7).
    dense = dense_reachable(spec, trace.n)

    def run_compacted(ctx, st, tier):
        ctx = ctx._replace(bucket=tier)
        for name, stage in compacted:
            with tracing.scope(name):
                ctx, st = stage(ctx, st)
        return ctx, st

    def on_tier(ctx, tier, cp=None, is_dense=None):
        def run(st):
            out, st = run_compacted(ctx._replace(compact=cp), st, tier)
            if is_dense is not None:
                out = out._replace(dense_bucket=jnp.bool_(is_dense))
                if tier is None:
                    out = out._replace(compact_ok=jnp.bool_(True))
            return out.facts(), st
        return run

    def on_largest(ctx):
        top = tiers[-1]
        if not dense:
            return on_tier(ctx, top)

        def run(st):
            # the largest tier when its gather holds every active flow and
            # spreader of every lane, else dense: the same bits either way
            cp = build_compact(spec, st, *top)
            fits = cp.ok.astype(jnp.int32)
            if axis_name is not None:
                fits = jax.lax.pmin(fits, axis_name)
            return jax.lax.cond(fits > 0, on_tier(ctx, top, cp, False),
                                on_tier(ctx, None, None, True), st)
        return run

    def one_pass(st: CloudState):
        ctx = StageCtx(spec=spec, params=params, trace=trace, t_stop=t_stop,
                       t_next=t_next, arrival_sorted=arrival_sorted)
        snap = (st.task_state, st.vstage, st.pstate, st.f_active)
        n_active = jnp.sum(st.f_active, dtype=jnp.int32)
        # one verdict for every lane, so the solve's cond stays one branch
        own = own_consumers(spec, st)
        if axis_name is not None:
            own = jax.lax.pmin(own.astype(jnp.int32), axis_name) > 0
        ctx = ctx._replace(live_flows=n_active, own_consumers=own)
        if len(tiers) == 2:
            # Bucket tiers (DESIGN.md §7): the smallest that holds every
            # active flow, of every lane under vmap.  Either gives the
            # same bits.
            if axis_name is not None:
                n_active = jax.lax.pmax(n_active, axis_name)
            small = n_active <= tiers[0][0]
            facts, st = jax.lax.cond(
                small, on_tier(ctx, tiers[0], is_dense=False if dense
                               else None),
                on_largest(ctx), st)
            ctx = ctx._replace(**facts)._replace(small_bucket=small)
        elif tiers:
            facts, st = on_largest(ctx)(st)
            ctx = ctx._replace(**facts)
        else:
            ctx, st = run_compacted(ctx, st, None)
        for name, stage in rest:
            with tracing.scope(name):
                ctx, st = stage(ctx, st)
        st_pre = st
        for name, stage in stages[-N_MANAGEMENT_STAGES:]:
            with tracing.scope(name):
                ctx, st = stage(ctx, st)
        if t_next is not None:
            # Hand-over iteration: the clock reached the next window's
            # first arrival, so the management stages ran without that
            # (still unloaded) task queued.  Discard their delta — the
            # next window's step replays the identical pass with the
            # arrival present, matching the monolithic stage sequence.
            defer = jnp.isfinite(t_next) & (st_pre.t >= t_next)
            st = jax.tree.map(
                lambda pre, post: jnp.where(defer, pre, post), st_pre, st)
        ok = (ctx.compact_ok if ctx.compact_ok is not None
              else jnp.bool_(True))
        with tracing.scope(tracing.TERMINATION):
            st = termination(ctx, st, snap)
        return st, ok, LoopCounters.of(ctx)

    K = steps_per_iter(spec)

    def skip(st):
        return st, jnp.bool_(True), LoopCounters.zero()

    def body(carry):
        st, ok, counters = carry
        # The first micro-step needs no settled guard: the loop condition
        # that admitted this body already asserted it.
        st, ok1, c1 = one_pass(st)
        ok, counters = ok & ok1, counters.plus(c1)
        for _ in range(K - 1):
            # Guard via lax.cond: a settled state skips the pass outright
            # (single-scenario runs pay ~nothing; under vmap the cond
            # lowers to a per-lane select of both sides, same as the
            # tree-select formulation it replaces — bit-identical either
            # way, since a skipped pass returns the carry verbatim).
            cont = st.running & (st.n_events < spec.max_events)
            st, ok2, c2 = jax.lax.cond(cont, one_pass, skip, st)
            ok, counters = ok & ok2, counters.plus(c2)
        return st, ok, counters

    return body


def lane_carry(carry, axis_name):
    """``carry`` batched along the named ``vmap`` axis, values unchanged.

    Under ``vmap`` the loop's continue flag differs per lane, so JAX
    batches every carried leaf anyway.  Batching them before the loop lets
    its batching rule settle in one pass over the body, where it would
    otherwise batch the body (both bucket tiers and every policy branch)
    again for each leaf that turns batched: set-up time, not run time.
    """
    if axis_name is None:
        return carry
    lane = jax.lax.axis_index(axis_name)
    return jax.tree.map(lambda x: jnp.where(lane >= 0, x, x), carry)


def management_pass(spec, params, trace, st: CloudState) -> CloudState:
    """The pre-loop scheduler pass: arrivals at exactly the current clock
    (e.g. t=0) must be served before the first horizon jump — later
    arrivals get their pass inside the loop because the horizon stops at
    each arrival time."""
    ctx = StageCtx(spec=spec, params=params, trace=trace,
                   t_stop=jnp.float32(jnp.inf))
    with tracing.scope(tracing.MANAGEMENT_PASS):
        for name, stage in zip(tracing.STAGE_SCOPES[-N_MANAGEMENT_STAGES:],
                               STAGES[-N_MANAGEMENT_STAGES:]):
            with tracing.scope(name):
                _, st = stage(ctx, st)
    return st
