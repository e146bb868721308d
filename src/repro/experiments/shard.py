"""Shard a ``simulate_batch`` sweep's batch axis over the available devices.

A stacked sweep (built with :func:`repro.core.engine.stack_params` /
``stack_traces`` or :func:`repro.experiments.pareto.param_grid`) is one
``vmap``ed program whose batch axis is embarrassingly parallel: scenario
points never communicate.  This module splits that axis over a 1-D device
mesh with ``shard_map`` — each device runs the identical vmapped engine on
its slice, so an N-point grid uses a whole TPU/GPU pod instead of one core
(DESIGN.md §4).

* The mesh uses ``min(batch size, device count)`` shards.  A batch that
  does not divide evenly (a prime batch on a mismatched pod) is
  **padded and masked**: the batched leaves are padded with copies of the
  leading rows up to the next multiple of the shard count, the padded
  sweep runs on the full mesh, and the pad rows are sliced off the result
  — so an awkward batch size costs at most one extra lane per device
  instead of falling back to a single core.  Only a single device (or a
  single-point batch) falls back to plain
  :func:`~repro.core.engine.simulate_batch` — same results, no mesh.
* Per-point results are *bit-identical* to the unsharded call: ``vmap``
  computes each lane independently, so slicing the batch over devices —
  or appending pad lanes that are later dropped — changes the layout,
  never the arithmetic of the valid rows (tested in
  ``tests/test_experiments.py``).
* On a CPU-only host the path is testable by forcing a multi-device
  topology: ``XLA_FLAGS=--xla_force_host_platform_device_count=2``
  (set before ``jax`` initialises).

Every experiment kind in this package (:mod:`~repro.experiments.pareto`,
:mod:`~repro.experiments.ensemble`, :mod:`~repro.experiments.tournament`)
routes its batch through :func:`run_batch`, so sharding is a flag, not a
rewrite.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import engine, loop, tracing


def batch_flags(spec: engine.CloudSpec, trace: engine.Trace,
                params: engine.CloudParams) -> tuple[bool, ...]:
    """Per-leaf "carries a leading batch axis" flags, aligned with
    ``jax.tree.leaves((trace, params))`` — derived from the engine's own
    vmap-axis rule so shard_map's layout can never diverge from
    ``simulate_batch``."""
    axes = (engine._trace_axes(trace), engine._params_axes(spec, params))
    # flatten_up_to aligns one axis entry per *value* leaf — structural
    # Nones (e.g. a monolithic Trace's gid) stay structure on both sides,
    # while a None axis over a real array leaf still yields a flag
    entries = jax.tree.structure((trace, params)).flatten_up_to(axes)
    return tuple(a == 0 for a in entries)


def batch_size(spec: engine.CloudSpec, trace: engine.Trace,
               params: engine.CloudParams) -> int:
    """Length of the sweep's leading batch axis (every batched leaf must
    agree)."""
    flags = batch_flags(spec, trace, params)
    leaves = jax.tree.leaves((trace, params))
    sizes = {int(jnp.shape(l)[0]) for l, f in zip(leaves, flags) if f}
    if not sizes:
        raise ValueError(
            "no batched leaf (leading batch axis) in `trace` or `params`; "
            "stack points with stack_params/stack_traces first")
    if len(sizes) > 1:
        raise ValueError(
            f"inconsistent batch-axis lengths across leaves: {sorted(sizes)}")
    return sizes.pop()


def shard_count(n_points: int, n_devices: int | None = None) -> int:
    """Number of mesh shards :func:`simulate_batch_sharded` uses:
    ``min(n_points, n_devices)`` — batch sizes that don't divide evenly are
    padded up to the next multiple (see :func:`pad_rows`) rather than
    dropping to fewer devices."""
    if n_devices is None:
        n_devices = jax.device_count()
    return max(min(n_points, n_devices), 1)


def pad_rows(n_points: int, n_shards: int) -> int:
    """How many pad lanes :func:`simulate_batch_sharded` appends so the
    batch divides over ``n_shards`` (0 when it already divides)."""
    return -n_points % max(n_shards, 1)


def _pad_batch(trace_params, flags, pad: int):
    """Append ``pad`` copies of the leading rows to every batched leaf."""
    leaves, treedef = jax.tree.flatten(trace_params)
    padded = [jnp.concatenate([l, l[:pad]], axis=0) if f else l
              for l, f in zip(leaves, flags)]
    return jax.tree.unflatten(treedef, padded)


@functools.lru_cache(maxsize=64)
def _sharded_runner(spec, devs, treedef, flags):
    """One compiled shard_map program per (spec, device set, tree structure,
    batch-flag signature) — repeated sweeps reuse it."""
    mesh = Mesh(np.asarray(devs), ("batch",))
    in_specs = treedef.unflatten(
        [P("batch") if f else P() for f in flags])

    def run(trace_params, t_stop):
        trace, params = trace_params
        # the checked (results, compact_ok) variant: the host wrapper below
        # inspects the concrete per-lane flags and replays densely on a
        # compaction-bucket overflow (DESIGN.md §7)
        return engine._simulate_batch_jit(spec, trace, params, t_stop)

    fn = jax.shard_map(run, mesh=mesh, in_specs=(in_specs, P()),
                       out_specs=(P("batch"), P("batch")), check_vma=False)
    return jax.jit(fn)


def simulate_batch_sharded(
        spec: engine.CloudSpec, trace: engine.Trace,
        params: engine.CloudParams,
        t_stop: float | jax.Array = jnp.inf,
        devices=None) -> engine.CloudResult:
    """:func:`repro.core.engine.simulate_batch`, batch axis sharded over
    ``devices`` (default: all of ``jax.devices()``) with ``shard_map``.

    Batch sizes that don't divide the shard count are padded with copies
    of the leading rows and the pad lanes sliced off the result, so even a
    prime-sized sweep fills the whole mesh.  Falls back to the plain
    single-device ``vmap`` only when one shard fits (one device, or a
    single point).  Valid rows are bit-identical either way; only the
    device layout changes.
    """
    with tracing.entry("simulate_batch_sharded"):
        return _simulate_batch_sharded(spec, trace, params, t_stop, devices)


def _simulate_batch_sharded(spec, trace, params, t_stop, devices):
    trace = jax.tree.map(jnp.asarray, trace)
    params = jax.tree.map(jnp.asarray, params)
    n = batch_size(spec, trace, params)
    devs = tuple(jax.devices() if devices is None else devices)
    d = shard_count(n, len(devs))
    if d <= 1:
        return engine.simulate_batch(spec, trace, params, t_stop)
    flags = batch_flags(spec, trace, params)
    pad = pad_rows(n, d)
    if pad:
        with tracing.span(tracing.SHARD_PAD):
            trace, params = _pad_batch((trace, params), flags, pad)
    treedef = jax.tree.structure((trace, params))
    runner = _sharded_runner(spec, devs[:d], treedef, flags)
    with tracing.span(tracing.LAUNCH):
        res, ok = runner((trace, params), jnp.asarray(t_stop, jnp.float32))
    if engine._checked_rerun(spec, ok[:n]):
        engine._warn_dense_rerun(spec)
        runner = _sharded_runner(engine.dense_spec(spec), devs[:d],
                                 treedef, flags)
        with tracing.span(tracing.DENSE_REPLAY):
            res, _ = runner((trace, params),
                            jnp.asarray(t_stop, jnp.float32))
    if pad:
        with tracing.span(tracing.SHARD_UNPAD):
            res = jax.tree.map(lambda l: l[:n], res)
    return res


@functools.lru_cache(maxsize=64)
def _stream_runner(spec, devs, treedef, flags):
    """One compiled *batched* window step per (spec, device set, params
    structure, batch-flag signature) — the streaming counterpart of
    :func:`_sharded_runner`: ``vmap`` over the carried state + batched
    params leaves, ``shard_map`` over the mesh when more than one device
    holds a shard.  Windows are replicated (every lane replays the same
    trace; the sweep axis is the parameter/scheduler grid)."""
    paxes = treedef.unflatten([0 if f else None for f in flags])

    def step(carry, window, params, t_prev_next, t_next, t_stop):
        return engine._stream_step_impl(spec, carry, window, params,
                                        t_prev_next, t_next, t_stop,
                                        axis_name=loop.LANE_AXIS)

    vstep = jax.vmap(step, in_axes=(0, None, paxes, None, None, None),
                     axis_name=loop.LANE_AXIS)
    if len(devs) > 1:
        mesh = Mesh(np.asarray(devs), ("batch",))
        pspecs = treedef.unflatten([P("batch") if f else P() for f in flags])
        vstep = jax.shard_map(
            vstep, mesh=mesh,
            in_specs=(P("batch"), P(), pspecs, P(), P(), P()),
            out_specs=P("batch"), check_vma=False)
    return jax.jit(vstep, donate_argnums=(0,))


def simulate_stream_batch(
        spec: engine.CloudSpec, windows, params: engine.CloudParams, *,
        n_slots: int | None = None,
        t_stop: float | jax.Array = jnp.inf,
        devices=None) -> engine.StreamResult:
    """:func:`repro.core.engine.simulate_stream` over a batched parameter
    sweep (stacked with ``stack_params``/``param_grid``): every lane
    replays the same windowed trace under its own parameter/scheduler
    point, vmapped through one compiled window step and sharded over
    ``devices`` exactly like :func:`simulate_batch_sharded` (pad-and-mask
    on awkward batch sizes, single-device fallback, per-lane results
    bit-identical to sequential :func:`simulate_stream` calls).

    Returns a :class:`~repro.core.engine.StreamResult` whose every leaf
    carries the batch as its leading axis.
    """
    with tracing.entry("simulate_stream_batch"):
        return _simulate_stream_batch(spec, windows, params, n_slots, t_stop,
                                      devices)


def _simulate_stream_batch(spec, windows, params, n_slots, t_stop, devices):
    params = jax.tree.map(jnp.asarray, params)
    paxes = engine._params_axes(spec, params)
    flags = tuple(a == 0 for a in
                  jax.tree.structure(params).flatten_up_to(paxes))
    if not any(flags):
        raise ValueError(
            "simulate_stream_batch needs at least one batched params leaf "
            "(leading batch axis); use simulate_stream for a single point")
    sizes = {int(jnp.shape(l)[0]) for l, f in
             zip(jax.tree.leaves(params), flags) if f}
    if len(sizes) > 1:
        raise ValueError(
            f"inconsistent batch-axis lengths across leaves: {sorted(sizes)}")
    n = sizes.pop()
    params0 = params               # pre-pad view, for the dense replay
    devs = tuple(jax.devices() if devices is None else devices)
    d = shard_count(n, len(devs))
    pad = pad_rows(n, d) if d > 1 else 0
    if pad:
        with tracing.span(tracing.SHARD_PAD):
            params = _pad_batch(params, flags, pad)
    treedef = jax.tree.structure(params)
    runner = _stream_runner(spec, devs[:d] if d > 1 else devs[:1],
                            treedef, flags)
    paxes = engine._params_axes(spec, params)

    with tracing.span(tracing.STREAM_INIT):
        it, W = engine._as_window_iter(windows)
        cur = next(it, None)
        if cur is None:
            raise ValueError(
                "simulate_stream_batch needs at least one window")
        if W is None:
            it, _ = engine._as_window_iter(engine._chain_one(cur, it),
                                           window_size=cur.n)
            cur = next(it)
        Q = (engine.default_n_slots(spec, cur.n) if n_slots is None
             else int(n_slots))
        carry = jax.vmap(lambda pp: engine.init_stream(spec, Q, pp,
                                                       like=cur),
                         in_axes=(paxes,))(params)
        t_stop = jnp.asarray(t_stop, jnp.float32)
        t_prev_next = jnp.float32(0.0)
    outs = []
    while cur is not None:
        with tracing.span(tracing.STREAM_WINDOW):
            with tracing.span(tracing.STREAM_NEXT_WINDOW):
                nxt = next(it, None)
                t_next = (jnp.float32(jnp.inf) if nxt is None
                          else engine._first_arrival(nxt))
            with tracing.span(tracing.LAUNCH):
                carry, ys = runner(carry, cur, params, t_prev_next, t_next,
                                   t_stop)
        outs.append(ys)
        t_prev_next, cur = t_next, nxt

    if engine._checked_rerun(spec, carry.compact_ok[:n]):
        # same policy as simulate_stream: replayable window sources restart
        # the whole sweep densely; consumed generators fail loudly
        if hasattr(windows, "n_windows") and hasattr(windows, "window"):
            engine._warn_dense_rerun(spec)
            with tracing.span(tracing.DENSE_REPLAY):
                return _simulate_stream_batch(
                    engine.dense_spec(spec), windows, params0, Q, t_stop,
                    devices)
        raise RuntimeError(
            "active-set compaction bucket overflowed mid-stream and the "
            "window source is a consumed generator that cannot be "
            "replayed; rerun with spec.compact=0 (dense) or pass a "
            "replayable WindowedTrace")

    with tracing.span(tracing.STREAM_ASSEMBLE):
        res = _assemble_stream_batch(carry, outs)
    if pad:
        with tracing.span(tracing.SHARD_UNPAD):
            res = jax.tree.map(lambda l: l[:n], res)
    return res


def _assemble_stream_batch(carry, outs) -> engine.StreamResult:
    """Scatter each lane's per-window flushes back onto the global task
    axis."""
    gids = jnp.concatenate([o["gid"] for o in outs], axis=-1)
    t_done = jnp.concatenate([o["t_done"] for o in outs], axis=-1)
    rej = jnp.concatenate([o["rejected"] for o in outs], axis=-1)
    n_total = int(jnp.maximum(
        jnp.max(gids, initial=-1), jnp.max(carry.slots.gid, initial=-1))) + 1

    def scatter(g, td, rj):
        idx = jnp.where(g >= 0, g, n_total)
        completion = jnp.full((n_total,), jnp.inf, jnp.float32).at[idx].set(
            td, mode="drop")
        rejected = jnp.zeros((n_total,), bool).at[idx].set(rj, mode="drop")
        return completion, rejected

    completion, rejected = jax.vmap(scatter)(gids, t_done, rej)
    st = carry.state
    return engine.StreamResult(
        state=st,
        completion=completion,
        rejected=rejected,
        energy=st.meters.pm.energy,
        energy_sampled=st.meters.pm_sampled,
        meters=st.meters,
        n_events=st.n_events,
        t_end=st.t,
        overflow=st.overflow,
        window_t_end=jnp.stack([o["t_end"] for o in outs], axis=-1),
        window_energy=jnp.stack([o["energy"] for o in outs], axis=-1),
        counters=carry.counters,
    )


def run_batch(spec: engine.CloudSpec, trace: engine.Trace,
              params: engine.CloudParams, *,
              t_stop: float | jax.Array = jnp.inf,
              sharded: bool = True, devices=None) -> engine.CloudResult:
    """The experiment layer's one batch-execution path: sharded over the
    available devices by default, plain ``simulate_batch`` on request."""
    if not sharded:
        return engine.simulate_batch(spec, trace, params, t_stop)
    return simulate_batch_sharded(spec, trace, params, t_stop, devices)
