"""Streaming-window engine suite (DESIGN.md §8).

The load-bearing property: :func:`repro.core.engine.simulate_stream` is
*float-bit-identical* to the monolithic :func:`~repro.core.engine.simulate`
on the concatenated trace — completions, rejections and every meter
reading — for every registered VM x PM policy combination, both with a
single window (``W >= T``) and with the trace split four ways
(``W = T/4``).  Around it: ``chunk_trace``/``stack_traces`` input
validation, the buffer-donation contract of ``simulate``'s
``donate_argnames`` (and the stream driver's carry handling), the
compile-once-per-window-shape key, and hypothesis properties over
randomized traces/window sizes (work conservation, monotone Kahan meters,
completion bounds, slot-recycling uniqueness).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import engine
from repro.core.trace import chunk_trace, filter_fitting, gwa_like_trace
from repro.sched import registry

# ---------------------------------------------------------------------------
# bitwise equivalence across the full policy grid
# ---------------------------------------------------------------------------

GRID = [(vm, pm) for vm in registry.names("vm")
        for pm in registry.names("pm")]


def _bits(x) -> np.ndarray:
    x = np.asarray(x)
    if np.issubdtype(x.dtype, np.floating):
        return x.view({2: np.uint16, 4: np.uint32, 8: np.uint64}[x.itemsize])
    return x


@pytest.fixture(scope="module")
def grid_scenario():
    spec, _ = engine.make_cloud(n_pm=4, n_vm=16, pm_cores=8.0)
    trace = filter_fitting(gwa_like_trace("das2", 40, seed=3), 8.0)
    return spec, trace


@pytest.mark.parametrize("vm,pm", GRID, ids=[f"{v}x{p}" for v, p in GRID])
def test_stream_matches_monolithic_bitwise(grid_scenario, vm, pm):
    spec, trace = grid_scenario
    params = engine.CloudParams.for_spec(spec, vm_sched=vm, pm_sched=pm,
                                         metering_period=25.0)
    mono = jax.block_until_ready(engine.simulate(spec, trace, params))
    mono_readings = mono.readings(spec)
    T = trace.n
    for W in (T, max(T // 4, 1)):
        sr = jax.block_until_ready(
            engine.simulate_stream(spec, chunk_trace(trace, W), params))
        np.testing.assert_array_equal(
            _bits(mono.completion), _bits(sr.completion),
            err_msg=f"{vm}x{pm} W={W}: completion bits diverge")
        np.testing.assert_array_equal(
            np.asarray(mono.rejected), np.asarray(sr.rejected),
            err_msg=f"{vm}x{pm} W={W}: rejection set diverges")
        stream_readings = sr.readings(spec)
        assert set(stream_readings) == set(mono_readings)
        for key in mono_readings:
            np.testing.assert_array_equal(
                _bits(mono_readings[key]), _bits(stream_readings[key]),
                err_msg=f"{vm}x{pm} W={W}: meter {key!r} bits diverge")
        assert int(sr.n_events) == int(mono.n_events)
        assert _bits(sr.t_end) == _bits(mono.t_end)


def test_stream_result_readings_api(grid_scenario):
    spec, trace = grid_scenario
    res = engine.simulate_stream(spec, chunk_trace(trace, 8))
    readings = res.readings(spec)
    assert "iaas_total" in readings and "pm" in readings
    # per-window progress curves cover every window
    assert res.window_t_end.shape == res.window_energy.shape
    assert res.window_t_end.shape[0] == chunk_trace(trace, 8).n_windows


# ---------------------------------------------------------------------------
# chunk_trace / stack_traces input validation
# ---------------------------------------------------------------------------

def _ramp_trace(n: int) -> engine.Trace:
    return engine.Trace(
        arrival=jnp.arange(n, dtype=jnp.float32),
        cores=jnp.ones((n,), jnp.float32),
        work=jnp.full((n,), 5.0, jnp.float32))


def test_chunk_trace_pads_and_masks_last_window():
    wt = chunk_trace(_ramp_trace(10), 4)
    assert (wt.n_windows, wt.window_size, wt.n_tasks) == (3, 4, 10)
    last = wt.window(2)
    np.testing.assert_array_equal(np.asarray(last.gid), [8, 9, -1, -1])
    assert np.all(np.isinf(np.asarray(last.arrival)[2:]))
    assert np.all(np.asarray(last.cores)[2:] == 0.0)
    assert np.all(np.asarray(last.work)[2:] == 0.0)
    # valid entries round-trip in order
    valid = np.asarray(wt.gid).ravel() >= 0
    np.testing.assert_array_equal(
        np.asarray(wt.arrival).ravel()[valid], np.arange(10, dtype=np.float32))
    np.testing.assert_array_equal(
        np.asarray(wt.gid).ravel()[valid], np.arange(10))


def test_chunk_trace_sorts_unsorted_stably():
    # Unsorted input is stably argsorted by arrival: ties keep their
    # original relative order, cores/work travel with their task, and gid
    # carries the *original* index so per-task outputs still align with
    # the caller's trace axis.
    tr = engine.Trace(
        arrival=jnp.asarray([2.0, 0.0, 1.0, 1.0], jnp.float32),
        cores=jnp.asarray([1.0, 2.0, 4.0, 8.0], jnp.float32),
        work=jnp.asarray([10.0, 20.0, 40.0, 80.0], jnp.float32))
    wt = chunk_trace(tr, 2)
    np.testing.assert_array_equal(
        np.asarray(wt.arrival).ravel(), [0.0, 1.0, 1.0, 2.0])
    np.testing.assert_array_equal(np.asarray(wt.gid).ravel(), [1, 2, 3, 0])
    np.testing.assert_array_equal(
        np.asarray(wt.cores).ravel(), [2.0, 4.0, 8.0, 1.0])
    np.testing.assert_array_equal(
        np.asarray(wt.work).ravel(), [20.0, 40.0, 80.0, 10.0])


def test_chunk_trace_unsorted_stream_matches_sorted():
    # Shuffling the task axis must not change the streamed simulation: the
    # stable sort reconstructs the time order and gid maps results back.
    spec, _ = engine.make_cloud(n_pm=2, n_vm=8, pm_cores=8.0)
    trace = filter_fitting(gwa_like_trace("das2", 24, seed=11), 8.0)
    perm = np.random.RandomState(0).permutation(trace.n)
    shuffled = engine.Trace(arrival=trace.arrival[perm],
                            cores=trace.cores[perm],
                            work=trace.work[perm],
                            gid=jnp.asarray(perm, jnp.int32))
    ref = jax.block_until_ready(
        engine.simulate_stream(spec, chunk_trace(trace, 8)))
    got = jax.block_until_ready(
        engine.simulate_stream(spec, chunk_trace(shuffled, 8)))
    np.testing.assert_array_equal(_bits(ref.completion),
                                  _bits(got.completion))
    np.testing.assert_array_equal(np.asarray(ref.rejected),
                                  np.asarray(got.rejected))
    assert _bits(ref.t_end) == _bits(got.t_end)


def test_chunk_trace_rejects_bad_window():
    with pytest.raises(ValueError, match="window must be positive"):
        chunk_trace(_ramp_trace(4), 0)


def test_stack_traces_rejects_unequal_lengths():
    with pytest.raises(ValueError, match="equal-length"):
        engine.stack_traces([_ramp_trace(4), _ramp_trace(5)])


def test_stack_traces_rejects_mixed_gid():
    with_gid = _ramp_trace(4)._replace(gid=jnp.arange(4, dtype=jnp.int32))
    with pytest.raises(ValueError, match="mix"):
        engine.stack_traces([_ramp_trace(4), with_gid])


def test_stack_traces_still_stacks_equal_lengths():
    stacked = engine.stack_traces([_ramp_trace(4), _ramp_trace(4)])
    assert stacked.arrival.shape == (2, 4)
    assert stacked.gid is None


# ---------------------------------------------------------------------------
# donation contract
# ---------------------------------------------------------------------------

def test_simulate_donates_state_buffer():
    """PR 6 gotcha made executable: ``simulate`` donates a caller-provided
    ``state``; reading the donated buffers afterwards must raise (callers
    keep a live snapshot only via ``jax.tree.map(jnp.copy, st)``)."""
    spec, params = engine.make_cloud(n_pm=2, n_vm=8, pm_cores=4.0)
    trace = _ramp_trace(6)
    st = jax.tree.map(jnp.copy, engine.init_state(spec, trace, params))
    probe = st.t
    jax.block_until_ready(engine.simulate(spec, trace, params, state=st))
    if not probe.is_deleted():
        pytest.skip("backend did not donate the state buffers")
    with pytest.raises(RuntimeError):
        np.asarray(probe)


def test_stream_carry_survives_donation():
    """The stream driver's carry is donated every window step; a replay
    over many windows — and a back-to-back second replay over the same
    ``WindowedTrace`` — must never trip on a deleted buffer."""
    spec, params = engine.make_cloud(n_pm=2, n_vm=8, pm_cores=4.0)
    wt = chunk_trace(_ramp_trace(12), 3)
    first = jax.block_until_ready(engine.simulate_stream(spec, wt, params))
    second = jax.block_until_ready(engine.simulate_stream(spec, wt, params))
    np.testing.assert_array_equal(_bits(first.completion),
                                  _bits(second.completion))


def test_init_stream_carry_leaves_are_unaliased():
    """Donating one buffer twice is an XLA error; ``init_stream`` must
    hand the first window step a carry whose leaves own their storage."""
    spec, params = engine.make_cloud(n_pm=2, n_vm=8, pm_cores=4.0)
    carry = engine.init_stream(spec, 8, params)
    buffers = [leaf.unsafe_buffer_pointer()
               for leaf in jax.tree.leaves(carry) if leaf.ndim > 0]
    assert len(buffers) == len(set(buffers))


# ---------------------------------------------------------------------------
# compile-key semantics
# ---------------------------------------------------------------------------

def test_stream_compiles_once_across_trace_lengths():
    spec, params = engine.make_cloud(n_pm=2, n_vm=8, pm_cores=4.0)
    engine._stream_step.clear_cache()
    for n in (8, 12, 16):  # three total lengths, one (W, Q) shape
        engine.simulate_stream(spec, chunk_trace(_ramp_trace(n), 4),
                               params, n_slots=16)
    assert engine._stream_step._cache_size() == 1, (
        "the window step's compile key must be (spec, W, Q), never the "
        "total trace length")


# ---------------------------------------------------------------------------
# hypothesis properties over randomized traces / window sizes
# ---------------------------------------------------------------------------

_PROP_SPEC, _ = engine.make_cloud(n_pm=2, n_vm=8, pm_cores=4.0)
_PROP_T = 12
_PROP_SLOTS = 24  # fixed so only W varies the compile key


def _random_trace(seed: int) -> engine.Trace:
    """Integer arrival times (duplicates force same-instant cohorts that
    split across window boundaries) and tied core counts (exercise the
    smallest-first gid tie-break)."""
    rng = np.random.RandomState(seed)
    arrival = np.sort(rng.randint(0, 20, _PROP_T)).astype(np.float32)
    cores = (2.0 ** rng.randint(0, 2, _PROP_T)).astype(np.float32)
    work = (rng.uniform(1.0, 25.0, _PROP_T) * cores).astype(np.float32)
    return engine.Trace(arrival=jnp.asarray(arrival),
                        cores=jnp.asarray(cores), work=jnp.asarray(work))


_window_sizes = st.sampled_from([3, 4, 6, 12])
_seeds = st.integers(min_value=0, max_value=2**20)
_policies = st.sampled_from(
    [("firstfit", "ondemand"), ("smallestfirst", "alwayson"),
     ("nonqueuing", "ondemand")])


@settings(max_examples=8, deadline=None)
@given(seed=_seeds, W=_window_sizes, policy=_policies)
def test_property_stream_equals_monolithic(seed, W, policy):
    vm, pm = policy
    params = engine.CloudParams.for_spec(_PROP_SPEC, vm_sched=vm,
                                         pm_sched=pm)
    trace = _random_trace(seed)
    mono = jax.block_until_ready(engine.simulate(_PROP_SPEC, trace, params))
    sr = jax.block_until_ready(engine.simulate_stream(
        _PROP_SPEC, chunk_trace(trace, W), params, n_slots=_PROP_SLOTS))
    np.testing.assert_array_equal(_bits(mono.completion),
                                  _bits(sr.completion))
    np.testing.assert_array_equal(np.asarray(mono.rejected),
                                  np.asarray(sr.rejected))
    np.testing.assert_array_equal(_bits(mono.energy), _bits(sr.energy))


@settings(max_examples=8, deadline=None)
@given(seed=_seeds, W=_window_sizes)
def test_property_stream_invariants(seed, W):
    """Work conservation, monotone Kahan meters, completion bounds."""
    trace = _random_trace(seed)
    sr = jax.block_until_ready(engine.simulate_stream(
        _PROP_SPEC, chunk_trace(trace, W), None, n_slots=_PROP_SLOTS))
    completion = np.asarray(sr.completion)
    rejected = np.asarray(sr.rejected)
    arrival = np.asarray(trace.arrival)
    # work conservation across windows: every task is exactly one of
    # completed / rejected / still-unfinished
    done = np.isfinite(completion)
    assert completion.shape == (trace.n,)
    assert not np.any(done & rejected)
    # every completion inside [arrival, t_end]
    assert np.all(completion[done] >= arrival[done])
    assert np.all(completion[done] <= float(sr.t_end))
    # Kahan meter accumulators are monotone non-decreasing across windows
    we = np.asarray(sr.window_energy)
    assert np.all(np.diff(we) >= 0.0)
    assert we[-1] == pytest.approx(float(np.asarray(sr.energy).sum()))
    wt_end = np.asarray(sr.window_t_end)
    assert np.all(np.diff(wt_end) >= 0.0)


@settings(max_examples=6, deadline=None)
@given(seed=_seeds, W=_window_sizes)
def test_property_slot_recycling_never_double_assigns(seed, W):
    """Drive the window step directly: no global id is ever flushed twice,
    and the live slot table never holds one gid in two slots."""
    trace = _random_trace(seed)
    wt = chunk_trace(trace, W)
    params = engine.CloudParams.for_spec(_PROP_SPEC)
    carry = engine.init_stream(_PROP_SPEC, _PROP_SLOTS, params)
    windows = list(wt.windows())
    t_prev_next, t_stop = jnp.float32(0.0), jnp.float32(jnp.inf)
    flushed: list[np.ndarray] = []
    for k, w in enumerate(windows):
        t_next = (engine._first_arrival(windows[k + 1])
                  if k + 1 < len(windows) else jnp.float32(jnp.inf))
        live = np.asarray(carry.slots.gid)
        live = live[live >= 0]
        assert len(live) == len(set(live.tolist())), (
            "one gid occupies two live slots")
        carry, ys = engine._stream_step(_PROP_SPEC, carry, w, params,
                                        t_prev_next, t_next, t_stop)
        gids = np.asarray(ys["gid"])
        flushed.append(gids[gids >= 0])
        t_prev_next = t_next
    allf = np.concatenate(flushed)
    assert len(allf) == len(set(allf.tolist())), (
        "a gid was flushed from the slot table twice")
    # conservation: flushed + still-live == submitted
    live = np.asarray(carry.slots.gid)
    survivors = set(live[live >= 0].tolist())
    assert set(allf.tolist()) | survivors == set(range(trace.n))


# ---------------------------------------------------------------------------
# batched streaming sweeps (experiments/shard.simulate_stream_batch)
# ---------------------------------------------------------------------------

def _sweep_points(spec, n):
    import dataclasses
    base = engine.CloudParams.for_spec(spec)
    names_vm = registry.names("vm")
    names_pm = registry.names("pm")
    return [dataclasses.replace(
        base, net_bw=jnp.float32(60.0 + 20.0 * i),
        vm_sched=registry.code_of("vm", names_vm[i % len(names_vm)]),
        pm_sched=registry.code_of("pm", names_pm[i % len(names_pm)]))
        for i in range(n)]


def test_stream_batch_matches_sequential_bitwise():
    """Every lane of ``simulate_stream_batch`` is bit-identical to its own
    sequential ``simulate_stream`` call (vmap computes lanes independently;
    heterogeneous policy codes stay traced data), but for the
    provider-only solves, which the lanes take together: a lane counts at
    most its own run's."""
    from repro.experiments.shard import simulate_stream_batch
    spec, _ = engine.make_cloud(n_pm=2, n_vm=8, pm_cores=4.0)
    wt = chunk_trace(_ramp_trace(10), 5)
    pts = _sweep_points(spec, 3)
    batch = jax.block_until_ready(simulate_stream_batch(
        spec, wt, engine.stack_params(pts)))
    assert batch.completion.shape == (3, 10)
    for i, p in enumerate(pts):
        one = jax.block_until_ready(engine.simulate_stream(spec, wt, p))
        lane = jax.tree.map(lambda l: l[i], batch)
        own = one.counters.provider_only_solves
        assert lane.counters.provider_only_solves <= own, i
        lane = lane._replace(counters=lane.counters._replace(
            provider_only_solves=own))
        for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(lane)):
            np.testing.assert_array_equal(_bits(a), _bits(b))


def test_stream_batch_two_devices_subprocess():
    """The ``shard_map`` branch of the batched window step: forced 2-host
    -device topology, even and padded (prime) batch sizes, every valid
    lane bitwise vs sequential ``simulate_stream``, but for the
    provider-only solves, which a shard's lanes take together."""
    import os
    import pathlib
    import subprocess
    import sys
    src = str(pathlib.Path(__file__).resolve().parent.parent / "src")
    code = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.core import engine
from repro.core.trace import chunk_trace
from repro.experiments.shard import simulate_stream_batch
from repro.sched import registry

assert jax.device_count() == 2, jax.devices()
spec, _ = engine.make_cloud(n_pm=2, n_vm=8, pm_cores=4.0)
tr = engine.Trace(arrival=jnp.arange(10, dtype=jnp.float32),
                  cores=jnp.ones((10,), jnp.float32),
                  work=jnp.full((10,), 5.0, jnp.float32))
wt = chunk_trace(tr, 5)
base = engine.CloudParams.for_spec(spec)
pms = registry.names("pm")
def pts(n):
    return [dataclasses.replace(base, net_bw=jnp.float32(60.0 + 20.0 * i),
                                pm_sched=registry.code_of("pm", pms[i % len(pms)]))
            for i in range(n)]
def bits(x):
    x = np.atleast_1d(np.asarray(x))
    if x.dtype.kind == "f":
        return x.view({2: np.uint16, 4: np.uint32, 8: np.uint64}[x.itemsize])
    return x
for n in (4, 3):  # even split, then pad-and-mask (3 lanes over 2 devices)
    batch = simulate_stream_batch(spec, wt, engine.stack_params(pts(n)))
    assert batch.completion.shape == (n, 10)
    for i, p in enumerate(pts(n)):
        one = engine.simulate_stream(spec, wt, p)
        lane = jax.tree.map(lambda l: l[i], batch)
        own = one.counters.provider_only_solves
        assert lane.counters.provider_only_solves <= own, i
        lane = lane._replace(counters=lane.counters._replace(
            provider_only_solves=own))
        for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(lane)):
            np.testing.assert_array_equal(bits(a), bits(b))
print("STREAM_SHARDED_BITWISE_OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=src, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=560)
    assert "STREAM_SHARDED_BITWISE_OK" in r.stdout, r.stdout + r.stderr[-2000:]
