"""The engine loop's state protocol: entity constants, the dense
:class:`CloudState` pytree, and the per-iteration :class:`StageCtx`.

The event-loop body is a *staged subsystem pipeline* (DESIGN.md §5): a
sequence of pure stage functions, each with the signature

    ``stage(ctx: StageCtx, st: CloudState) -> (StageCtx, CloudState)``

``CloudState`` is the only value carried across ``lax.while_loop``
iterations; ``StageCtx`` is rebuilt every iteration and threads the
*interval facts* (rates, event horizon, completion masks, the meter
stack's :class:`~repro.core.energy.SimView`) from the stages that compute
them to the stages that consume them.  Each stage returns an updated
``CloudState`` whose touched fields are that stage's explicit state delta
— the driver (:mod:`repro.core.loop.driver`) only composes, never edits.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..energy import MeterState

BIG = jnp.float32(3.0e38)


def live_threshold(f_total: jax.Array) -> jax.Array:
    """The live-flow completion epsilon: a flow counts as drained once its
    remaining work falls to ``1e-6 * registered_total + 1e-9``.

    One definition shared by the ``advance`` stage's live mask and the
    driver's termination verdict — the two must agree bit-for-bit or a
    flow could progress forever without ever terminating the loop.
    """
    return 1e-6 * f_total + 1e-9


# Consumption kinds: what a VM slot's single flow currently carries.
KIND_MIGRATE = 5

# Task states
TASK_PENDING = 0   # submitted (queued once arrival <= t)
TASK_ACTIVE = 1    # bound to a VM
TASK_DONE = 2
TASK_REJECTED = 3

# VM/PM scheduler identity is an integer code into the open policy
# registry (repro.sched.registry, DESIGN.md §6) — the management stages
# lax.switch over the registered branch list, so policies are *data* and a
# tournament over any subset of the matrix shares one compiled program
# (DESIGN.md §1, §4).  The core holds no policy names: registered codes
# and names come from registry.names("vm") / registry.names("pm").


class CloudState(NamedTuple):
    t: jax.Array          # f32 simulated clock
    t_c: jax.Array        # f32 Kahan compensation for the clock
    n_events: jax.Array   # i32

    # consumption slots: [0:V] VM flows, [V:V+P] hidden consumers
    f_pr: jax.Array       # f32[V+P] remaining processing
    f_total: jax.Array    # f32[V+P] amount at registration
    f_pl: jax.Array       # f32[V+P] rate limit
    f_prov: jax.Array     # i32[V+P]
    f_cons: jax.Array     # i32[V+P]
    f_active: jax.Array   # bool[V+P]
    f_release: jax.Array  # f32[V+P] latency gate
    f_kind: jax.Array     # i8[V+P]

    task_state: jax.Array  # i8[T]
    task_vm: jax.Array     # i32[T]
    t_done: jax.Array      # f32[T]

    vstage: jax.Array      # i8[V]
    vm_task: jax.Array     # i32[V]
    vm_host: jax.Array     # i32[V]
    vm_cores: jax.Array    # f32[V]
    vm_expiry: jax.Array   # f32[V]  (ALLOCATED slots; inf otherwise)
    vm_saved_pr: jax.Array  # f32[V] remaining task work across suspend/migrate
    vm_mig_dst: jax.Array  # i32[V]

    pstate: jax.Array      # i8[P]
    pstate_end: jax.Array  # f32[P] (simple model transition deadline)
    free_cores: jax.Array  # f32[P]

    meters: MeterState     # the meter stack's accumulated readings (§3.3)
    meter_next: jax.Array  # f32 next sample tick (inf when disabled)
    processed: jax.Array   # f32[S] provider-side utilisation counters

    overflow: jax.Array    # bool — VM slot pool exhausted at some dispatch
    running: jax.Array     # bool

    # The memory dimension (DESIGN.md §7), present only when the trace
    # carries ``mem``; ``None`` is not a pytree leaf, so a trace without it
    # carries and compiles exactly the state above.
    free_mem: jax.Array | None = None   # f32[P] GB free per PM
    vm_mem: jax.Array | None = None     # f32[V] GB each VM slot holds
    mem_bound: jax.Array | None = None  # i32 dispatches whose fit on both
    #                                     dimensions chose another PM than
    #                                     a fit on cores alone (cumulative)

    # Pre-meter-stack views (the default stack's per-PM direct meters).
    @property
    def energy_hi(self) -> jax.Array:
        return self.meters.pm.energy_hi

    @property
    def energy_lo(self) -> jax.Array:
        return self.meters.pm.energy_lo

    @property
    def energy_sampled(self) -> jax.Array:
        return self.meters.pm_sampled


class LoopCounters(NamedTuple):
    """Work counts of the event loop, summed over its iterations (one set
    per lane of a batched run).  Carried beside the loop state, never
    inside :class:`CloudState`; exposed as ``res.counters``.

    ``gate_opens`` counts, per event gate, the iterations in which the
    gate's trigger fired and its stage body ran: ``vm_lifecycle``,
    ``pm_power``, ``pm_sched``, ``vm_sched`` in that order.
    ``small_bucket_iters`` counts the iterations whose compacted stages
    ran on the small bucket tier (``loop.compact.SMALL_TIER``),
    ``dense_iters`` those that ran them dense because the active set
    outgrew the largest tier, and ``live_flows`` sums the active flows
    each iteration started with.  ``mem_bound`` counts dispatches whose
    fit on cores and memory chose another PM than a fit on cores alone;
    ``fill_truncated`` fair-share solves that stopped at
    ``spec.max_fill_iters`` with a flow unfrozen;
    ``provider_only_solves`` the solves that reduced over providers alone,
    no two active flows naming one consumer (DESIGN.md §7).
    """

    fill_rounds: jax.Array   # i32 progressive-filling rounds (fair share)
    label_rounds: jax.Array  # i32 influence-label propagation rounds
    serve_rounds: jax.Array  # i32 queue-serving rounds of the VM policy
    gate_opens: jax.Array    # i32[4] iterations each event gate opened
    small_bucket_iters: jax.Array  # i32 iterations on the small tier
    dense_iters: jax.Array   # i32 iterations on the in-program dense branch
    live_flows: jax.Array    # i32 active flows, summed over iterations
    mem_bound: jax.Array     # i32 memory-bound dispatches
    fill_truncated: jax.Array  # i32 solves cut at max_fill_iters
    provider_only_solves: jax.Array  # i32 solves over providers alone

    @classmethod
    def zero(cls) -> "LoopCounters":
        z = jnp.int32(0)
        return cls(z, z, z, jnp.zeros((4,), jnp.int32), z, z, z, z, z, z)

    @classmethod
    def of(cls, ctx: "StageCtx") -> "LoopCounters":
        """The counts one pipeline pass reported through ``ctx``."""
        def count(x):
            return jnp.int32(0) if x is None else jnp.asarray(x, jnp.int32)
        return cls(count(ctx.fill_rounds), count(ctx.label_rounds),
                   count(ctx.serve_rounds),
                   jnp.stack([count(ctx.lifecycle_gate),
                              count(ctx.power_gate), count(ctx.pm_gate),
                              count(ctx.vm_gate)]),
                   count(ctx.small_bucket), count(ctx.dense_bucket),
                   count(ctx.live_flows), count(ctx.mem_bound),
                   count(ctx.fill_truncated), count(ctx.provider_only))

    def plus(self, other: "LoopCounters") -> "LoopCounters":
        return jax.tree.map(jnp.add, self, other)


class StageCtx(NamedTuple):
    """Read-mostly context threaded through one pipeline pass.

    The scenario inputs (``spec``, ``params``, ``trace``, ``t_stop``) are
    fixed for the whole simulation; the interval fields are ``None`` until
    the stage that owns them runs (``advance`` fills the rates/horizon
    facts, ``observe`` publishes the :class:`~repro.core.energy.SimView`
    the policy stages may read).  Stages communicate *only* through this
    context and the returned :class:`CloudState`.
    """

    spec: Any                    # CloudSpec (jit-static)
    params: Any                  # CloudParams pytree
    trace: Any                   # Trace
    t_stop: jax.Array            # f32 scalar
    # Streaming-window sentinel (DESIGN.md §8): the first arrival of the
    # *next* trace window, or ``None`` for a monolithic run.  When set it
    # (a) joins the event-horizon candidates so the loop advances exactly
    # to the next unseen arrival, (b) keeps the termination guard's
    # "work remains" verdict true while future windows exist, and (c)
    # gates the management stages off on the hand-over iteration — their
    # pass is replayed by the next window's step once its tasks are
    # present, reproducing the monolithic stage sequence bit-for-bit.
    t_next: jax.Array | None = None
    # Arrivals presorted once per trace (hoisted out of the loop by
    # ``make_body``): the horizon's task-arrival family collapses to one
    # ``searchsorted`` against this vector — the next pending arrival is
    # always the first strictly-future one, because a task whose arrival
    # lies beyond the monotone clock can only ever be PENDING.  ``None``
    # (e.g. the pre-loop management pass) keeps the dense arrival scan.
    arrival_sorted: jax.Array | None = None
    # The ``(flows, spreaders)`` bucket tier the compacted stages run on
    # this pass (one of ``loop.compact.compact_tiers``, chosen by the
    # driver); ``None`` runs them dense.
    bucket: tuple[int, int] | None = None
    # Whether no active flow names a shared consumer (an image transfer's
    # or a migration's ``netin``), so every live flow's consumer is its own
    # VM or hidden spreader: the fair-share solve may then reduce over the
    # providers alone (DESIGN.md §7).  Set by the driver, one value for
    # every lane under vmap; ``None`` runs the solve over both sides.
    own_consumers: jax.Array | None = None

    # -- filled by the `advance` stage -----------------------------------
    compact: Any = None          # loop.compact.Compact of this iteration
    #                              (None: dense; bucket-shaped, so it never
    #                              leaves the driver's tier cond)
    compact_ok: jax.Array | None = None  # bool — the bucket held the
    #                                      active set (Compact.ok)
    r: jax.Array | None = None        # f32[F] fair-share rates this interval
    live: jax.Array | None = None     # bool[F] flows that progressed
    thresh: jax.Array | None = None   # f32[F] completion epsilon
    done: jax.Array | None = None     # bool[F] flows that completed
    delivered: jax.Array | None = None  # f32[S] per-provider rate this
    #                                     interval (observe's utilisation
    #                                     numerator — computed once in
    #                                     advance's fused provider reduce)
    dt: jax.Array | None = None       # f32 the event horizon
    t0: jax.Array | None = None       # f32 interval start (pre-advance clock)
    t_new: jax.Array | None = None    # f32 interval end (== state clock after)
    has_event: jax.Array | None = None  # bool — the horizon found an event
    tick: jax.Array | None = None     # bool — sampled-meter tick fired
    period: jax.Array | None = None   # f32 metering period

    fill_rounds: jax.Array | None = None  # i32 fair-share solve rounds
    fill_truncated: jax.Array | None = None  # bool the solve stopped at
    #                                          max_fill_iters unfinished
    provider_only: jax.Array | None = None  # bool the solve reduced over
    #                                         providers alone

    # -- filled by the `observe` stage -----------------------------------
    view: Any = None             # energy.SimView of [t0, t_new]
    label_rounds: jax.Array | None = None  # i32 influence-label rounds
    #                                        (None: no per-VM meters)

    # -- event gates of the later stages (LoopCounters.gate_opens) -------
    lifecycle_gate: jax.Array | None = None  # bool vm_lifecycle body ran
    power_gate: jax.Array | None = None      # bool pm_power body ran
    pm_gate: jax.Array | None = None         # bool PM policy body ran
    vm_gate: jax.Array | None = None         # bool VM policy body ran
    serve_rounds: jax.Array | None = None    # i32 queue-serving rounds
    mem_bound: jax.Array | None = None       # i32 memory-bound dispatches

    # -- set by the driver -----------------------------------------------
    small_bucket: jax.Array | None = None    # bool compacted stages ran on
    #                                          the small tier
    dense_bucket: jax.Array | None = None    # bool compacted stages ran
    #                                          dense above the largest tier
    live_flows: jax.Array | None = None      # i32 active flows at entry

    def facts(self) -> dict:
        """The fields the stages filled, without the bucket-shaped
        ``compact``: the shape-independent results of a pass, which is
        what may leave a ``lax.cond`` over bucket tiers."""
        first = self._fields.index("compact")
        return {k: getattr(self, k) for k in self._fields[first + 1:]}
