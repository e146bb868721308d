"""The vectorized IaaS cloud engine (paper §3.1-§3.5 in one event loop).

Configuration is split into two halves so that *many scenarios share one
compiled program*:

* :class:`CloudSpec` — shape/topology/compile-time choices only (``n_pm``,
  ``n_vm``, the low-level sharing-scheduler name, backend, event caps).  It
  is hashable and passed to ``jax.jit`` as a static argument; changing it
  recompiles.
* :class:`CloudParams` — every continuous knob (bandwidths, image size,
  boot work, latency, metering period, hidden-consumer work, the
  :class:`~repro.core.energy.PowerStateTable`) **and** the VM/PM scheduler
  selection (integer codes).  It is a registered-dataclass pytree traced as
  data: two simulations with different ``CloudParams`` reuse the same XLA
  executable, and any leaf may carry a leading batch axis for
  :func:`simulate_batch`.

One :func:`simulate` call runs a whole trace-driven cloud scenario to
completion inside a single jitted ``lax.while_loop``; one
:func:`simulate_batch` call ``jax.vmap``s that loop over stacked traces
and/or stacked parameter points — an 8-point scenario sweep (Pareto fronts
over power models, trace ensembles, scheduler tournaments) compiles once
and runs hardware-parallel, which is how this reproduction extends the
paper's "fast evaluation of many scheduling scenarios" goal (§1, §4.3).
Batch-axis semantics and the device-sharding layout are in DESIGN.md §4;
the first-class experiment kinds live in :mod:`repro.experiments`.

The loop body itself is a **staged subsystem pipeline**
(:mod:`repro.core.loop`, DESIGN.md §5): pure stage functions over the
explicit :class:`CloudState` / ``StageCtx`` protocol —

* **advance** — timed/time-jump control (§3.1) + unified resource sharing
  (§3.2): every iteration computes the event horizon ``dt = min(next
  completion, next task arrival, PM power-state end, allocation expiry,
  meter tick, t_stop)`` and advances the clock by exactly that; rates are
  piecewise-constant between events so the jump is exact.
* **observe** — energy metering (§3.3): the declarative *meter stack*
  (spec-static :class:`~repro.core.energy.MeterTopology` in
  ``spec.meters``, batchable :class:`~repro.core.energy.MeterParams` in
  ``params.meter``); every horizon the stage builds one
  :class:`~repro.core.energy.SimView` and calls the pure
  :func:`~repro.core.energy.observe` hook.  The default stack yields
  per-PM direct + per-PM idle-component meters, per-VM Eq. 6 adjusted
  aggregation, the whole-IaaS aggregate and a PUE-style HVAC indirect
  meter; the paper's periodic *sampled* metering runs when
  ``params.metering_period > 0``.
* **vm_lifecycle / pm_power** — infrastructure (§3.4): the VM lifecycle
  (Fig. 6; each VM slot rewrites its single consumption in place: image
  transfer -> boot -> task -> optional migration) and the PM power-state
  machine (Table 1/2, incl. the *hidden consumer* complex model).
* **pm_sched / vm_sched** — management (§3.5): policy hooks reading the
  fresh ``SimView`` and live meter state.  Each stage ``lax.switch``es on
  the ``params.vm_sched`` / ``params.pm_sched`` integer code over the open
  policy registry (:mod:`repro.sched.registry`, DESIGN.md §6) — the codes
  stay traced data, so the whole scheduler matrix batches through one
  compile, and the policies themselves (first-fit / non-queuing /
  smallest-first VM dispatchers; always-on / on-demand / consolidate /
  defrag / evacuate PM state schedulers, the latter three with in-loop
  live migration driven by the per-PM idle meter) are
  :mod:`repro.sched.policies` citizens the core does not know by name.

The per-entity capacities (PMs ``P``, VM slots ``V``, tasks ``T``) are
static; overflow is reported, never silent.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import compile_cache as _compile_cache
from . import loop
from . import machine as mc
from . import tracing
from .energy import (PM_OFF, PM_RUNNING, PM_SWITCHING_OFF, PM_SWITCHING_ON,
                     MeterParams, MeterState, MeterTopology, PowerStateTable,
                     meter_readings)
from .fairshare import SCHEDULERS
from .loop.migrate import migrate_one
from .loop.state import (BIG as _BIG, KIND_MIGRATE, TASK_ACTIVE, TASK_DONE,
                         TASK_PENDING, TASK_REJECTED, CloudState,
                         LoopCounters)
from repro.sched import registry as _policy_registry

# Persistent XLA cache (DESIGN.md §7): the first engine compile of a
# process is a disk hit when an earlier process compiled the same program.
_compile_cache.enable()

__all__ = [
    "CloudSpec", "CloudParams", "CloudState", "CloudResult", "Trace",
    "make_cloud", "stack_params", "stack_traces", "init_state", "simulate",
    "simulate_batch", "simulate_batch_sharded", "start_migration",
    "make_allocation", "VM_SCHEDULERS", "PM_SCHEDULERS",
    "StreamCarry", "StreamResult", "simulate_stream", "init_stream",
    "default_n_slots", "LoopCounters",
]


def __getattr__(name: str):
    """Registry-backed views (PEP 562): ``VM_SCHEDULERS``/``PM_SCHEDULERS``
    are the registered name tuples (index == code, never stale after a
    ``repro.sched.registry.register`` call), and ``VM_<NAME>``/``PM_<NAME>``
    resolve to the policy's stable integer code (``engine.PM_CONSOLIDATE``,
    ``engine.VM_SMALLESTFIRST``, ...)."""
    if name == "VM_SCHEDULERS":
        return _policy_registry.names("vm")
    if name == "PM_SCHEDULERS":
        return _policy_registry.names("pm")
    for prefix, layer in (("VM_", "vm"), ("PM_", "pm")):
        if name.startswith(prefix):
            try:
                return _policy_registry.code_of(layer,
                                                name[len(prefix):].lower())
            except KeyError:
                break
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


@dataclasses.dataclass(frozen=True)
class CloudSpec:
    """Static cloud description (hashable -> jit-static).

    Only shape/topology and compile-time algorithm choices live here;
    every continuous knob is in :class:`CloudParams`.
    """

    n_pm: int = 4
    n_vm: int = 64               # max simultaneously existing VMs
    complex_power: bool = False  # Table 2 hidden-consumer transition model
    scheduler: str = "maxmin"    # low-level sharing logic (fairshare.SCHEDULERS)
    backend: str = "jnp"         # 'jnp' | 'pallas' segmented reductions
    max_events: int = 2_000_000
    max_fill_iters: int = 64
    max_migrations: int = 4      # per-iteration move cap for multi-VM
    #                              evacuation policies (static: plan length)
    meters: MeterTopology = MeterTopology()  # which meters exist (§3.3)
    compact: int = -1            # active-set compaction bucket (DESIGN.md §7):
    #                              -1 auto watermark, 0 off, >0 explicit size
    #                              (rounded up to a power of two)
    steps_per_iter: int = 0      # coalesced event stepping: pipeline passes
    #                              per while_loop body (0 = tuned default)

    def __post_init__(self):
        assert self.scheduler in SCHEDULERS, (
            f"unknown sharing scheduler {self.scheduler!r}; "
            f"registered: {sorted(SCHEDULERS)}")
        assert self.compact >= -1, (
            f"spec.compact must be -1 (auto), 0 (off) or a positive bucket "
            f"size, got {self.compact}")
        assert self.steps_per_iter >= 0, (
            f"spec.steps_per_iter must be >= 0 (0 = auto), "
            f"got {self.steps_per_iter}")

    @property
    def layout(self) -> mc.SpreaderLayout:
        return mc.SpreaderLayout(self.n_pm, self.n_vm)


def _sched_code(value, layer: str):
    """Map a scheduler name to its registered integer code
    (:mod:`repro.sched.registry`); range-check concrete codes; pass
    traced/batched values through."""
    names = _policy_registry.names(layer)
    if isinstance(value, str):
        if value not in names:
            raise ValueError(f"unknown scheduler {value!r}; one of {names}")
        return names.index(value)
    concrete_int = (isinstance(value, int) and not isinstance(value, bool))
    if (value is not None and not concrete_int and jnp.ndim(value) == 0
            and not isinstance(value, jax.core.Tracer)):
        try:  # concrete 0-d integer arrays/np scalars are checkable too
            concrete_int = jnp.issubdtype(jnp.asarray(value).dtype,
                                          jnp.integer)
        except TypeError:
            concrete_int = False
    if concrete_int and not 0 <= int(value) < len(names):
        raise ValueError(
            f"scheduler code {int(value)} out of range; "
            f"0..{len(names) - 1} index {names}")
    return value


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CloudParams:
    """Continuous/traced cloud parameters — a pytree of (batchable) leaves.

    Scalars may be python floats, 0-d arrays, or ``[B]`` arrays for a
    batched sweep via :func:`simulate_batch`; ``power`` is a
    :class:`PowerStateTable` whose rows may likewise carry a leading batch
    axis.  ``vm_sched`` / ``pm_sched`` accept scheduler *names* at
    construction time and store integer codes (indices into
    :data:`VM_SCHEDULERS` / :data:`PM_SCHEDULERS`), so the scheduler matrix
    is data — sweeping it does not recompile.
    """

    pm_cores: object = 64.0
    perf_core: object = 1.0       # processing units per core-second
    net_bw: object = 125.0        # MB/s per PM NIC (1 Gb/s)
    repo_bw: object = 250.0       # MB/s repository egress
    image_mb: object = 100.0      # VM image size (paper §4.2.2 uses 100 MB)
    boot_work: object = 10.0      # core-seconds of boot processing
    vm_mem_mb: object = 1024.0    # serialized memory state (migration)
    pm_mem: object = 256.0        # GB of memory per PM (read only when the
    #                               trace carries ``mem``, DESIGN.md §7)
    latency_s: object = 0.001
    metering_period: object = 0.0  # 0 => exact integration only (no ticks)
    hidden_work_on: object = 40.0  # core-s consumed while switching on (complex)
    hidden_work_off: object = 2.4  # core-s consumed while switching off
    vm_sched: object = 0           # code into VM_SCHEDULERS (str accepted)
    pm_sched: object = 0           # code into PM_SCHEDULERS (str accepted)
    consolidate_idle_frac: object = 0.6  # consolidation trigger: a RUNNING PM
    #                                whose live idle-meter share of its draw
    #                                exceeds this is an evacuation source
    power: PowerStateTable = None  # per-power-state consumption model
    meter: MeterParams = None      # meter-stack coefficients (spec.meters)

    def __post_init__(self):
        object.__setattr__(self, "vm_sched",
                           _sched_code(self.vm_sched, "vm"))
        object.__setattr__(self, "pm_sched",
                           _sched_code(self.pm_sched, "pm"))
        if self.power is None:
            object.__setattr__(self, "power", PowerStateTable.simple())
        if self.meter is None:
            object.__setattr__(
                self, "meter", MeterParams.for_topology(MeterTopology()))

    @classmethod
    def for_spec(cls, spec: CloudSpec, **kw) -> "CloudParams":
        """Defaults consistent with ``spec`` (complex power model when
        ``spec.complex_power``, meter coefficients shaped to
        ``spec.meters``), overridable per keyword."""
        if "power" not in kw:
            kw["power"] = (PowerStateTable.complex_model()
                           if spec.complex_power else PowerStateTable.simple())
        if "meter" not in kw:
            kw["meter"] = MeterParams.for_topology(spec.meters)
        return cls(**kw)


def make_cloud(**kw) -> tuple[CloudSpec, CloudParams]:
    """Build a (CloudSpec, CloudParams) pair from one flat kwargs dict,
    routing each keyword to the half it belongs to."""
    spec_names = {f.name for f in dataclasses.fields(CloudSpec)}
    param_names = {f.name for f in dataclasses.fields(CloudParams)}
    unknown = set(kw) - spec_names - param_names
    if unknown:
        raise TypeError(f"unknown cloud option(s): {sorted(unknown)}")
    spec = CloudSpec(**{k: v for k, v in kw.items() if k in spec_names})
    params = CloudParams.for_spec(
        spec, **{k: v for k, v in kw.items() if k in param_names})
    return spec, params


def stack_params(params: Sequence[CloudParams]) -> CloudParams:
    """Stack parameter points leaf-wise along a new leading batch axis
    (input to :func:`simulate_batch`; batch-axis semantics in
    DESIGN.md §4)."""
    return jax.tree.map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *params)


class Trace(NamedTuple):
    """Task trace: one VM request per task (paper §4.2.2 protocol).

    ``gid`` is the streaming engine's *global task id* (DESIGN.md §8):
    ``None`` for a monolithic trace (the task axis IS the id), an
    ``i32[T]`` array for a slot-table window where recycled slots hold
    arbitrary ids and ``-1`` marks a free/padded slot.  ``None`` is not a
    pytree leaf, so monolithic traces batch/vmap exactly as before.

    ``mem`` and ``util`` (DESIGN.md §7) give a VM request a second
    resource dimension, GB of memory that placement must find free on the
    host, and the share of its cores the VM uses once booted (its task
    runs at ``util * cores * perf_core``).  ``None`` leaves each out, and
    the engine then compiles the program it compiled without them.
    """

    arrival: jax.Array  # f32[T] submission times (sorted not required)
    cores: jax.Array    # f32[T]
    work: jax.Array     # f32[T] total processing units (= runtime*cores*perf,
    #                     times util where the trace has util)
    gid: jax.Array | None = None  # i32[T] global ids (streaming); -1 = free
    mem: jax.Array | None = None   # f32[T] GB of memory the VM reserves
    util: jax.Array | None = None  # f32[T] share of its cores the VM uses

    @property
    def n(self) -> int:
        return self.arrival.shape[0]


def stack_traces(traces: Sequence[Trace]) -> Trace:
    """Stack equal-length traces along a new leading batch axis
    (DESIGN.md §4)."""
    traces = list(traces)
    if not traces:
        raise ValueError("stack_traces needs at least one trace")
    lengths = [t.n for t in traces]
    if len(set(lengths)) > 1:
        raise ValueError(
            f"stack_traces needs equal-length traces (one static task axis "
            f"per compile), got lengths {lengths}; pad the traces to one "
            f"length, or chunk them with repro.core.trace.chunk_trace and "
            f"replay via simulate_stream instead")
    with_gid = [t.gid is not None for t in traces]
    if any(with_gid) and not all(with_gid):
        raise ValueError(
            "stack_traces cannot mix gid-carrying (streaming) and "
            "monolithic traces: set gid on all windows or on none")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *traces)


class CloudResult(NamedTuple):
    state: CloudState
    completion: jax.Array   # f32[T] task completion times (inf: not finished)
    rejected: jax.Array     # bool[T]
    energy: jax.Array       # f32[P] per-PM integrated energy (J) — a view of
    #                         meters.pm, kept for pre-meter-stack callers
    energy_sampled: jax.Array  # f32[P] — view of meters.pm_sampled
    meters: MeterState      # the full meter stack (per-PM, per-VM Eq. 6,
    #                         PM groups, whole-IaaS, indirect meters)
    n_events: jax.Array
    t_end: jax.Array
    overflow: jax.Array
    counters: LoopCounters  # the loop's round and event-gate counts

    def readings(self, spec: "CloudSpec") -> dict[str, jax.Array]:
        """Named energy readings of the stack (see
        :func:`repro.core.energy.meter_readings`)."""
        return meter_readings(spec.meters, self.meters)


def _check_meter_params(spec: CloudSpec, params: CloudParams) -> None:
    """Meter coefficients must match the spec's topology (trailing K axis)."""
    K = spec.meters.n_indirect
    for name in ("indirect_base", "indirect_coeff"):
        shape = jnp.shape(getattr(params.meter, name))
        if shape[-1:] != (K,):
            raise ValueError(
                f"CloudParams.meter.{name} has shape {shape} but "
                f"spec.meters declares {K} indirect meter(s); build the "
                f"params with CloudParams.for_spec(spec) or "
                f"MeterParams.for_topology(spec.meters)")


def init_state(spec: CloudSpec, trace: Trace,
               params: CloudParams | None = None) -> CloudState:
    if params is None:
        params = CloudParams.for_spec(spec)
    _check_meter_params(spec, params)
    P, V, T = spec.n_pm, spec.n_vm, trace.n
    lay = spec.layout
    F = V + P
    zf = jnp.zeros((F,), jnp.float32)
    zi = jnp.zeros((F,), jnp.int32)
    # Discrete enum fields are int8: every write site assigns weak-typed
    # python constants (jnp.where / .at[].set keep the array dtype), and
    # the value range is tiny (power states 0-3, VM stages 0-9, flow kinds
    # 0-5).  Index fields (f_prov/f_cons/task_vm/...) stay int32.
    zk = jnp.zeros((F,), jnp.int8)
    # policies registered with starts_running=True (always-on) begin with
    # the fleet powered on; the rest start off and wake machines against
    # the queue deficit
    start_codes = _policy_registry.start_running_codes()
    start_running = (jnp.isin(jnp.asarray(params.pm_sched),
                              jnp.asarray(start_codes, jnp.int32))
                     if start_codes else jnp.bool_(False))
    pstate0 = jnp.broadcast_to(
        jnp.where(start_running, PM_RUNNING, PM_OFF), (P,)).astype(jnp.int8)
    period = jnp.asarray(params.metering_period, jnp.float32)
    mem = {} if trace.mem is None else dict(
        free_mem=jnp.full((P,), jnp.asarray(params.pm_mem, jnp.float32)),
        vm_mem=jnp.zeros((V,), jnp.float32), mem_bound=jnp.int32(0))
    return CloudState(
        t=jnp.float32(0.0), t_c=jnp.float32(0.0), n_events=jnp.int32(0),
        f_pr=zf, f_total=zf, f_pl=zf + _BIG, f_prov=zi, f_cons=zi,
        f_active=jnp.zeros((F,), bool), f_release=zf, f_kind=zk,
        task_state=jnp.full((T,), TASK_PENDING, jnp.int8),
        task_vm=jnp.full((T,), -1, jnp.int32),
        t_done=jnp.full((T,), jnp.inf, jnp.float32),
        vstage=jnp.full((V,), mc.VM_FREE, jnp.int8),
        vm_task=jnp.full((V,), -1, jnp.int32),
        vm_host=jnp.zeros((V,), jnp.int32),
        vm_cores=jnp.zeros((V,), jnp.float32),
        vm_expiry=jnp.full((V,), jnp.inf, jnp.float32),
        vm_saved_pr=jnp.zeros((V,), jnp.float32),
        vm_mig_dst=jnp.zeros((V,), jnp.int32),
        pstate=pstate0,
        pstate_end=jnp.full((P,), jnp.inf, jnp.float32),
        free_cores=jnp.full((P,), jnp.asarray(params.pm_cores, jnp.float32)),
        meters=MeterState.zero(spec.meters, P, V),
        meter_next=jnp.where(period > 0, period, jnp.inf).astype(jnp.float32),
        processed=jnp.zeros((lay.S,), jnp.float32),
        overflow=jnp.bool_(False),
        running=jnp.bool_(True),
        **mem,
    )


def _simulate_impl(spec: CloudSpec, trace: Trace, params: CloudParams,
                   state: CloudState | None, t_stop: jax.Array,
                   axis_name: str | None = None
                   ) -> tuple[CloudResult, jax.Array]:
    """Single-scenario engine: the staged pipeline (repro.core.loop) inside
    one ``lax.while_loop``.  Trace it once, run it for every parameter
    point — no python branch here depends on a params value.

    Returns ``(result, compact_ok)``: the second element is the loop's
    accumulated active-set-compaction verdict (DESIGN.md §7) — ``False``
    means a bucket overflowed at some iteration and the run must be
    replayed with ``spec.compact = 0`` (the host wrappers do).
    ``axis_name`` names the ``vmap`` axis of a batched caller
    (``loop.LANE_AXIS``), over which the loop picks its bucket tier."""
    st0 = init_state(spec, trace, params) if state is None else state
    st0 = loop.management_pass(spec, params, trace, st0)
    t_stop = jnp.asarray(t_stop, jnp.float32)

    def cond(carry):
        return carry[0].running & (carry[0].n_events < spec.max_events)

    st, ok, counters = jax.lax.while_loop(
        cond, loop.make_body(spec, params, trace, t_stop,
                             axis_name=axis_name),
        loop.lane_carry((st0, jnp.bool_(True), LoopCounters.zero()),
                        axis_name))
    return CloudResult(
        state=st,
        completion=st.t_done,
        rejected=st.task_state == TASK_REJECTED,
        energy=st.meters.pm.energy,
        energy_sampled=st.meters.pm_sampled,
        meters=st.meters,
        n_events=st.n_events,
        t_end=st.t,
        overflow=st.overflow,
        counters=counters,
    ), ok


def dense_spec(spec: CloudSpec) -> CloudSpec:
    """``spec`` with active-set compaction disabled — the overflow-replay
    target (bit-identical results, no bucket to overflow)."""
    return dataclasses.replace(spec, compact=0)


def _needs_dense_rerun(spec: CloudSpec, ok) -> bool:
    """Host-side overflow verdict: True when compaction was enabled for
    ``spec`` and some lane's bucket overflowed.  Inside a trace (``ok`` is
    a tracer — e.g. the shard_map runners) the check is deferred to the
    outermost host wrapper, which sees the concrete flag."""
    from .loop.compact import compact_bucket
    if compact_bucket(spec) == 0:
        return False
    if isinstance(ok, jax.core.Tracer):
        return False
    return not bool(np.all(np.asarray(ok)))


def _warn_dense_rerun(spec: CloudSpec):
    import warnings
    from .loop.compact import compact_bucket
    warnings.warn(
        f"active-set compaction bucket ({compact_bucket(spec)}) overflowed; "
        f"replaying the scenario with compact=0 (results are bit-identical; "
        f"set spec.compact to a larger bucket to avoid the replay)",
        RuntimeWarning, stacklevel=3)


def _checked_rerun(spec: CloudSpec, ok) -> bool:
    """:func:`_needs_dense_rerun` under its host span: reading the flag is
    where the host waits for the device."""
    with tracing.span(tracing.COMPACT_CHECK):
        return _needs_dense_rerun(spec, ok)


@functools.partial(jax.jit, static_argnames=("spec",),
                   donate_argnames=("state",))
def _simulate_jit(spec: CloudSpec, trace: Trace,
                  params: CloudParams,
                  state: CloudState | None,
                  t_stop: float | jax.Array):
    return _simulate_impl(spec, trace, params, state, t_stop)


def simulate(spec: CloudSpec, trace: Trace,
             params: CloudParams | None = None,
             state: CloudState | None = None,
             t_stop: float | jax.Array = jnp.inf) -> CloudResult:
    """Run the cloud to completion (or ``t_stop`` — Timed.simulateUntil).

    A caller-provided ``state`` is *donated*: its buffers are reused for
    the result's carried state and must not be read again afterwards (copy
    with ``jax.tree.map(jnp.copy, st)`` to keep a live snapshot).  Because
    donation makes an overflow replay impossible, a resumed run disables
    active-set compaction up front — bit-identical either way (DESIGN.md
    §7).
    """
    with tracing.entry("simulate"):
        if params is None:
            params = CloudParams.for_spec(spec)
        if state is not None:
            spec = dense_spec(spec)
        with tracing.span(tracing.LAUNCH):
            res, ok = _simulate_jit(spec, trace, params, state, t_stop)
        if _checked_rerun(spec, ok):
            _warn_dense_rerun(spec)
            with tracing.span(tracing.DENSE_REPLAY):
                res, _ = _simulate_jit(dense_spec(spec), trace, params, None,
                                       t_stop)
        return res


simulate.clear_cache = _simulate_jit.clear_cache  # registry invalidation


def _trace_axes(trace: Trace):
    return jax.tree.map(lambda l: 0 if jnp.ndim(l) > 1 else None, trace)


def _params_axes(spec: CloudSpec, params: CloudParams):
    template = CloudParams.for_spec(spec)
    return jax.tree.map(
        lambda l, r: 0 if jnp.ndim(l) > jnp.ndim(r) else None,
        params, template)


@functools.partial(jax.jit, static_argnames=("spec",))
def _simulate_batch_jit(spec: CloudSpec, trace: Trace, params: CloudParams,
                        t_stop: float | jax.Array):
    """The vmapped engine returning ``(results, per-lane compact_ok)`` —
    the traced core of :func:`simulate_batch`, also the entry point the
    shard_map runner (:mod:`repro.experiments.shard`) wraps so *its* host
    wrapper can check the concrete overflow flags."""
    taxes = _trace_axes(trace)
    paxes = _params_axes(spec, params)
    flat_axes = jax.tree.flatten((taxes, paxes),
                                 is_leaf=lambda x: x is None)[0]
    if all(a is None for a in flat_axes):
        raise ValueError(
            "simulate_batch needs at least one batched leaf (leading batch "
            "axis) in `trace` or `params`; use simulate() for a single "
            "scenario")
    run = jax.vmap(
        lambda tr, pp: _simulate_impl(spec, tr, pp, None, t_stop,
                                      axis_name=loop.LANE_AXIS),
        in_axes=(taxes, paxes), axis_name=loop.LANE_AXIS)
    return run(trace, params)


def simulate_batch(spec: CloudSpec, trace: Trace, params: CloudParams,
                   t_stop: float | jax.Array = jnp.inf) -> CloudResult:
    """Batched scenario sweep: one jit, one trace of the engine, ``vmap``
    over every :class:`Trace` and/or :class:`CloudParams` leaf that carries
    a leading batch axis (leaves without one broadcast).

    Returns a :class:`CloudResult` whose every leaf has the batch as its
    leading axis.  Per-point results are numerically identical to the
    corresponding sequential :func:`simulate` calls.  Batch-axis semantics
    and the recompile rules are documented in DESIGN.md §4; use
    :func:`simulate_batch_sharded` (or the experiment layer in
    :mod:`repro.experiments`) to spread the batch over multiple devices.
    An active-set-compaction bucket overflow on any lane (DESIGN.md §7)
    replays the whole sweep with ``compact=0`` — bit-identical results.
    """
    with tracing.entry("simulate_batch"):
        with tracing.span(tracing.LAUNCH):
            res, ok = _simulate_batch_jit(spec, trace, params, t_stop)
        if _checked_rerun(spec, ok):
            _warn_dense_rerun(spec)
            with tracing.span(tracing.DENSE_REPLAY):
                res, _ = _simulate_batch_jit(dense_spec(spec), trace, params,
                                             t_stop)
        return res


simulate_batch.clear_cache = _simulate_batch_jit.clear_cache


def simulate_batch_sharded(spec: CloudSpec, trace: Trace,
                           params: CloudParams,
                           t_stop: float | jax.Array = jnp.inf,
                           devices=None) -> CloudResult:
    """:func:`simulate_batch` with the batch axis sharded over ``devices``
    via ``shard_map`` (DESIGN.md §4) — the entry point big parameter grids
    should use so a sweep fills a whole pod instead of one core.

    Per-point results are bit-identical to the unsharded call; with a
    single device it falls back to plain :func:`simulate_batch`, and batch
    sizes that don't divide the device count are padded and masked so the
    full mesh is still used.  Implemented in
    :mod:`repro.experiments.shard` (imported lazily: the core engine has no
    dependency on the experiment layer).
    """
    from repro.experiments.shard import simulate_batch_sharded as impl
    return impl(spec, trace, params, t_stop, devices)


# ---------------------------------------------------------------------------
# Streaming trace windows (DESIGN.md §8)
# ---------------------------------------------------------------------------

class StreamCarry(NamedTuple):
    """The per-window carry of :func:`simulate_stream` (DESIGN.md §8).

    ``state`` is the ordinary :class:`CloudState` whose task axis is the
    fixed slot pool (``Q`` slots, never the total trace length); ``slots``
    is the slot-table :class:`Trace` those task indices resolve against —
    a free slot has ``gid == -1``, ``arrival == inf``, ``task_state ==
    TASK_DONE``, which makes it inert in every queue/horizon/termination
    mask.  ``compact_ok`` accumulates the active-set-compaction bucket
    check (DESIGN.md §7) across windows so the host can replay the whole
    stream densely on overflow; ``counters`` sums the loop's
    :class:`LoopCounters` across windows.  All leaves are donated to each
    window step.
    """

    state: CloudState
    slots: Trace
    compact_ok: jax.Array
    counters: LoopCounters


class StreamResult(NamedTuple):
    """:class:`CloudResult`-shaped result of a windowed replay: per-task
    outputs are re-assembled over the *global* task axis (``T_total``),
    meters/state are the final carried values — field-for-field comparable
    with the monolithic result, plus per-window progress curves."""

    state: CloudState
    completion: jax.Array   # f32[T_total] completion times (inf: unfinished)
    rejected: jax.Array     # bool[T_total]
    energy: jax.Array       # f32[P] — view of meters.pm (as CloudResult)
    energy_sampled: jax.Array  # f32[P]
    meters: MeterState
    n_events: jax.Array
    t_end: jax.Array
    overflow: jax.Array
    window_t_end: jax.Array   # f32[n_windows] clock after each window
    window_energy: jax.Array  # f32[n_windows] total PM energy after each
    counters: LoopCounters    # summed over windows; the deferred
    #                           management passes make them differ from
    #                           the monolithic run's (the answers do not)

    def readings(self, spec: "CloudSpec") -> dict[str, jax.Array]:
        """Named energy readings of the stack — same API as
        :meth:`CloudResult.readings`."""
        return meter_readings(spec.meters, self.meters)


def default_n_slots(spec: CloudSpec, window: int) -> int:
    """Default slot-pool size: room for a full window of fresh arrivals on
    top of every VM the cloud can run simultaneously (plus queue slack) —
    overflow is reported, never silent, so tight pools fail loudly."""
    return max(2 * window, spec.n_vm + window)


def init_stream(spec: CloudSpec, n_slots: int,
                params: CloudParams | None = None,
                like: Trace | None = None) -> StreamCarry:
    """The streaming engine's initial carry: an empty slot table and a
    :func:`init_state` whose every task slot is free (inert ``TASK_DONE``,
    ``arrival == inf``).  The slot table carries ``mem`` and ``util``
    where the window ``like`` does."""
    Q = int(n_slots)
    zq = jnp.zeros((Q,), jnp.float32)
    slots = Trace(
        arrival=jnp.full((Q,), jnp.inf, jnp.float32),
        cores=zq, work=zq,
        gid=jnp.full((Q,), -1, jnp.int32),
        mem=None if like is None or like.mem is None else zq,
        util=None if like is None or like.util is None else zq,
    )
    st = init_state(spec, slots, params)
    st = st._replace(task_state=jnp.full((Q,), TASK_DONE, jnp.int8))
    # init_state shares its zero buffers across fields; the window step
    # *donates* the carry, and donating one buffer twice is an XLA error —
    # copy leaf-wise so every donated leaf owns its storage.
    return jax.tree.map(jnp.copy, StreamCarry(
        state=st, slots=slots, compact_ok=jnp.bool_(True),
        counters=LoopCounters.zero()))


def _stream_step_impl(spec: CloudSpec, carry: StreamCarry, window: Trace,
                      params: CloudParams, t_prev_next: jax.Array,
                      t_next: jax.Array, t_stop: jax.Array,
                      axis_name: str | None = None):
    """One window of the streaming engine (DESIGN.md §8).  ``axis_name``
    as in :func:`_simulate_impl`.

    1. *Insert*: the window's valid tasks (``gid >= 0``) scatter into free
       slots in rank order (i-th incoming task -> i-th free slot); pool
       exhaustion raises ``overflow``, never drops silently.
    2. *Replay*: the previous window's loop ended on the hand-over
       iteration with its management delta discarded (the monolithic
       engine ran that pass with the next arrival already queued) — replay
       it now that the arrivals are present.  ``t_prev_next`` tells whether
       the previous loop ended on a hand-over (``t >= t_prev_next``) or on
       ``t_stop``/exhaustion (no discarded pass -> no replay).  A
       same-instant cohort split across the window boundary
       (``t >= t_next``) defers the pass — and the whole loop — again.
    3. *Loop*: the ordinary staged pipeline with the ``t_next`` sentinel
       joining the horizon/termination masks; it runs exactly the
       monolithic iteration sequence up to the next hand-over.
    4. *Flush*: terminal slots emit ``(gid, t_done, rejected)`` and are
       freed for the next window.
    """
    st, slots = carry.state, carry.slots
    Q = slots.n

    # ---- 1. insert: rank-matched scatter of valid tasks into free slots
    with tracing.scope(tracing.STREAM_INSERT):
        free = slots.gid < 0
        valid = window.gid >= 0
        free_rank = jnp.cumsum(free) - 1          # each free slot's rank
        slot_of_rank = jnp.full((Q,), Q, jnp.int32).at[
            jnp.where(free, free_rank, Q)].set(
            jnp.arange(Q, dtype=jnp.int32), mode="drop")
        pos = jnp.cumsum(valid) - 1               # each incoming task's rank
        take = valid & (pos < jnp.sum(free))
        dest = jnp.where(take, slot_of_rank[jnp.clip(pos, 0, Q - 1)], Q)
        slots = jax.tree.map(
            lambda a, b: a.at[dest].set(b, mode="drop"), slots, window)
        st = st._replace(
            task_state=st.task_state.at[dest].set(TASK_PENDING,
                                                  mode="drop"),
            task_vm=st.task_vm.at[dest].set(-1, mode="drop"),
            t_done=st.t_done.at[dest].set(jnp.inf, mode="drop"),
            overflow=st.overflow | jnp.any(valid & ~take),
        )

    # ---- 2. gated management replay
    with tracing.scope(tracing.STREAM_REPLAY):
        replay = jnp.isfinite(t_prev_next) & (st.t >= t_prev_next)
        split = jnp.isfinite(t_next) & (st.t >= t_next)
        stopped = jnp.isfinite(t_stop) & (st.t >= t_stop)
        do_mp = replay & ~split
        st_mp = loop.management_pass(spec, params, slots, st)
        st = jax.tree.map(lambda a, b: jnp.where(do_mp, a, b), st_mp, st)
        st = st._replace(running=do_mp & ~stopped)

    # ---- 3. the staged loop up to the next hand-over
    def cond(c):
        s = c[0]
        return s.running & (s.n_events < spec.max_events)

    st, compact_ok, counters = jax.lax.while_loop(
        cond, loop.make_body(spec, params, slots, t_stop, t_next,
                             axis_name=axis_name),
        loop.lane_carry((st, carry.compact_ok, carry.counters), axis_name))

    # ---- 4. flush terminal slots (compacted to the front), free them
    with tracing.scope(tracing.STREAM_FLUSH):
        term = ((st.task_state == TASK_DONE)
                | (st.task_state == TASK_REJECTED)) & (slots.gid >= 0)
        out_idx = jnp.where(term, jnp.cumsum(term) - 1, Q)
        out = {
            "gid": jnp.full((Q,), -1, jnp.int32).at[out_idx].set(
                slots.gid, mode="drop"),
            "t_done": jnp.full((Q,), jnp.inf, jnp.float32).at[out_idx].set(
                st.t_done, mode="drop"),
            "rejected": jnp.zeros((Q,), bool).at[out_idx].set(
                st.task_state == TASK_REJECTED, mode="drop"),
            "t_end": st.t,
            "energy": jnp.sum(st.meters.pm.energy),
        }
        slots = _free_slots(slots, term)
        st = st._replace(
            task_state=jnp.where(term, TASK_DONE, st.task_state),
            task_vm=jnp.where(term, -1, st.task_vm),
            t_done=jnp.where(term, jnp.inf, st.t_done),
        )
    return StreamCarry(state=st, slots=slots, compact_ok=compact_ok,
                       counters=counters), out


@functools.partial(jax.jit, static_argnames=("spec",),
                   donate_argnames=("carry",))
def _stream_step(spec: CloudSpec, carry: StreamCarry, window: Trace,
                 params: CloudParams, t_prev_next: jax.Array,
                 t_next: jax.Array, t_stop: jax.Array):
    """The one compiled program of a streaming replay: its compile key is
    ``(spec, W, Q)`` — never the total trace length — so a datacenter-year
    trace re-traces nothing after the first window."""
    return _stream_step_impl(spec, carry, window, params,
                             t_prev_next, t_next, t_stop)


def _free_slots(slots: Trace, free: jax.Array) -> Trace:
    """``slots`` with the entries under ``free`` reset to a free slot:
    ``arrival == inf``, ``gid == -1``, every other field 0."""
    fill = Trace(arrival=jnp.inf, cores=0.0, work=0.0, gid=-1, mem=0.0,
                 util=0.0)
    return Trace(*(None if a is None else jnp.where(free, f, a)
                   for a, f in zip(slots, fill)))


def _as_window_iter(windows, window_size=None):
    """Normalize ``windows`` into ``(iterator of gid-carrying Traces, W)``.

    Accepts a ``repro.core.trace.WindowedTrace``, a sequence, or a
    generator of :class:`Trace` windows (each either gid-carrying — e.g.
    ``WindowedTrace.window(k)`` — or plain, in which case sequential
    global ids are assigned in arrival order).  Windows must be
    time-sorted globally; ``chunk_trace`` guarantees that, generators
    promise it (DESIGN.md §8).
    """
    if hasattr(windows, "n_windows") and hasattr(windows, "window"):
        seq = (windows.window(k) for k in range(windows.n_windows))
        return seq, int(windows.window_size)

    def gen():
        offset = 0
        W = window_size
        for w in windows:
            if w.gid is None:
                w = w._replace(gid=jnp.arange(offset, offset + w.n,
                                              dtype=jnp.int32))
                offset += w.n
            if W is not None and w.n != W:
                if w.n > W:
                    raise ValueError(
                        f"window of {w.n} tasks exceeds the stream's "
                        f"window size {W}; all windows must share one "
                        f"shape (pad the last window, as chunk_trace does)")
                tail = jax.tree.map(lambda a: jnp.zeros((W - w.n,), a.dtype),
                                    w)
                tail = _free_slots(tail, jnp.ones((W - w.n,), bool))
                w = jax.tree.map(lambda a, b: jnp.concatenate([a, b]), w,
                                 tail)
            yield w

    return gen(), window_size


def _first_arrival(w: Trace) -> jax.Array:
    """The window's first valid arrival — the ``t_next`` sentinel value.
    Windows are time-sorted, so this is exactly the min the monolithic
    horizon takes over every not-yet-loaded arrival."""
    return jnp.min(jnp.where(w.gid >= 0, w.arrival,
                             jnp.float32(jnp.inf))).astype(jnp.float32)


def simulate_stream(spec: CloudSpec, windows,
                    params: CloudParams | None = None, *,
                    n_slots: int | None = None,
                    t_stop: float | jax.Array = jnp.inf) -> StreamResult:
    """Replay a windowed trace through one compiled window step
    (DESIGN.md §8) — bit-identical to the monolithic :func:`simulate` on
    the concatenated trace, but compiled once per ``(spec, W, Q)`` instead
    of once per total length.

    ``windows`` is a :class:`repro.core.trace.WindowedTrace` (from
    ``chunk_trace``), or any sequence/generator of time-sorted
    :class:`Trace` windows (e.g.
    :func:`repro.data.pipeline.gwa_window_stream` — the full trace is
    never materialised).  ``n_slots`` bounds the
    simultaneously-live task population (default
    :func:`default_n_slots`); exhaustion sets ``overflow``.
    """
    with tracing.entry("simulate_stream"):
        return _simulate_stream(spec, windows, params, n_slots, t_stop)


def _simulate_stream(spec, windows, params, n_slots, t_stop):
    if params is None:
        params = CloudParams.for_spec(spec)
    _check_meter_params(spec, params)
    with tracing.span(tracing.STREAM_INIT):
        it, W = _as_window_iter(windows)
        cur = next(iter(it), None) if W is None else next(it, None)
        if cur is None:
            raise ValueError("simulate_stream needs at least one window")
        if W is None:  # generator input: first window fixes the shape
            it, _ = _as_window_iter(_chain_one(cur, it), window_size=cur.n)
            cur = next(it)
        Q = default_n_slots(spec, cur.n) if n_slots is None else int(n_slots)
        carry = init_stream(spec, Q, params, like=cur)
        t_stop = jnp.asarray(t_stop, jnp.float32)
        # t_prev_next = 0 makes the first step run the monolithic pre-loop
        # management pass (the clock starts at 0 >= 0).
        t_prev_next = jnp.float32(0.0)
    outs = []
    while cur is not None:
        with tracing.span(tracing.STREAM_WINDOW):
            with tracing.span(tracing.STREAM_NEXT_WINDOW):
                nxt = next(it, None)
                t_next = (jnp.float32(jnp.inf) if nxt is None
                          else _first_arrival(nxt))
            with tracing.span(tracing.LAUNCH):
                carry, ys = _stream_step(spec, carry, cur, params,
                                         t_prev_next, t_next, t_stop)
        outs.append(ys)
        t_prev_next, cur = t_next, nxt
    if _checked_rerun(spec, carry.compact_ok):
        # A window's active set outgrew the compaction bucket.  Replayable
        # inputs (WindowedTrace) restart the whole stream densely — the
        # carried state already consumed compacted windows, so a mid-stream
        # switch would not be bit-identical.  Consumed generators cannot be
        # replayed; fail loudly rather than return silently-dense results.
        if hasattr(windows, "n_windows") and hasattr(windows, "window"):
            _warn_dense_rerun(spec)
            with tracing.span(tracing.DENSE_REPLAY):
                return _simulate_stream(dense_spec(spec), windows, params,
                                        Q, t_stop)
        raise RuntimeError(
            "active-set compaction bucket overflowed mid-stream and the "
            "window source is a consumed generator that cannot be "
            "replayed; rerun with spec.compact=0 (dense) or pass a "
            "replayable WindowedTrace")
    with tracing.span(tracing.STREAM_ASSEMBLE):
        return _assemble_stream(spec, carry, outs)


def _chain_one(first, rest):
    yield first
    yield from rest


def _assemble_stream(spec: CloudSpec, carry: StreamCarry,
                     outs: list[dict]) -> StreamResult:
    """Scatter the per-window flushes back onto the global task axis."""
    gids = jnp.concatenate([o["gid"] for o in outs])
    t_done = jnp.concatenate([o["t_done"] for o in outs])
    rej = jnp.concatenate([o["rejected"] for o in outs])
    # unfinished tasks (still live in the carry at stream end) count too
    live_gid = jnp.where(carry.slots.gid >= 0, carry.slots.gid, -1)
    n_total = int(jnp.maximum(jnp.max(gids, initial=-1),
                              jnp.max(live_gid, initial=-1))) + 1
    idx = jnp.where(gids >= 0, gids, n_total)
    completion = jnp.full((n_total,), jnp.inf, jnp.float32).at[idx].set(
        t_done, mode="drop")
    rejected = jnp.zeros((n_total,), bool).at[idx].set(rej, mode="drop")
    st = carry.state
    return StreamResult(
        state=st,
        completion=completion,
        rejected=rejected,
        energy=st.meters.pm.energy,
        energy_sampled=st.meters.pm_sampled,
        meters=st.meters,
        n_events=st.n_events,
        t_end=st.t,
        overflow=st.overflow,
        window_t_end=jnp.stack([o["t_end"] for o in outs]),
        window_energy=jnp.stack([o["energy"] for o in outs]),
        counters=carry.counters,
    )


@functools.partial(jax.jit, static_argnames=("spec",))
def start_migration(spec: CloudSpec, params: CloudParams, st: CloudState,
                    v: jax.Array, dst: jax.Array) -> CloudState:
    """Begin live-migrating VM slot ``v`` to PM ``dst`` (paper Fig. 6:
    running -> suspend-transfer/migrating -> resume on the new host).

    The public out-of-loop shim over the one shared masked-migration
    primitive (:func:`repro.core.loop.migrate.migrate_one`) — the in-loop
    migration policies (``pm_sched="consolidate"``/``"defrag"``/
    ``"evacuate"``, :mod:`repro.sched.policies`) issue the identical
    update from inside the pipeline.  The caller must ensure the
    destination fits; cores move src->dst immediately (allocation
    semantics).
    """
    st = migrate_one(spec, params, st, v, dst, jnp.bool_(True))
    return st._replace(running=jnp.bool_(True))


@functools.partial(jax.jit, static_argnames=("spec",))
def make_allocation(spec: CloudSpec, st: CloudState, pm: jax.Array,
                    cores: jax.Array, expiry: jax.Array) -> tuple[CloudState, jax.Array]:
    """Reserve cores on ``pm`` as an expiring resource allocation (§3.4.2).
    Returns (state, vm-slot or -1)."""
    vfree = st.vstage == mc.VM_FREE
    v = jnp.argmax(vfree).astype(jnp.int32)
    ok = vfree.any() & (st.free_cores[pm] >= cores) & (st.pstate[pm] == PM_RUNNING)

    def w(arr, val):
        return arr.at[v].set(jnp.where(ok, val, arr[v]))

    st = st._replace(
        vstage=w(st.vstage, mc.VM_ALLOCATED),
        vm_host=w(st.vm_host, jnp.asarray(pm, jnp.int32)),
        vm_cores=w(st.vm_cores, jnp.asarray(cores, jnp.float32)),
        vm_expiry=w(st.vm_expiry, jnp.asarray(expiry, jnp.float32)),
        free_cores=st.free_cores.at[pm].add(jnp.where(ok, -cores, 0.0)),
        running=jnp.bool_(True),
    )
    return st, jnp.where(ok, v, -1)
