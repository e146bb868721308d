"""Active-set compaction + coalesced stepping (DESIGN.md §7): the dense
pipeline is the oracle — compaction, bucket overflow replay, K-step
coalescing and the event-gated management stages must all reproduce its
results *bit for bit*."""
from __future__ import annotations

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.engine import CloudParams, CloudSpec, Trace
from repro.core.loop import compact as cpk
from repro.core.trace import chunk_trace
from repro.sched import registry


def _bits(x) -> np.ndarray:
    x = np.asarray(x)
    if np.issubdtype(x.dtype, np.floating):
        return x.view({2: np.uint16, 4: np.uint32, 8: np.uint64}[x.itemsize])
    return x


def _assert_tree_bitwise(a, b, msg=""):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_array_equal(
            _bits(x), _bits(y), err_msg=f"{msg}: leaf {i} diverges")


def _scenario(n_pm=3, n_vm=24, T=32, spread=400.0, seed=1):
    rng = np.random.default_rng(seed)
    spec = CloudSpec(n_pm=n_pm, n_vm=n_vm, compact=0)
    arr = np.sort(rng.uniform(0, spread, T)).astype(np.float32)
    trace = Trace(
        arrival=jnp.asarray(arr),
        cores=jnp.asarray(rng.integers(1, 3, T).astype(np.float32)),
        work=jnp.asarray(rng.uniform(5, 20, T).astype(np.float32)))
    return spec, trace


# ---------------------------------------------------------------------------
# watermark rule
# ---------------------------------------------------------------------------

def test_watermark_rule():
    # auto: next_pow2(4P + 32), enabled only when <= half the flow count
    assert cpk.compact_bucket(CloudSpec(n_pm=20, n_vm=256)) == 128
    assert cpk.compact_bucket(CloudSpec(n_pm=20, n_vm=1024)) == 128
    assert cpk.compact_bucket(CloudSpec(n_pm=3, n_vm=12)) == 0    # too small
    assert cpk.compact_bucket(CloudSpec(n_pm=6, n_vm=120)) == 0
    # explicit: rounded up to a power of two, only when < dense F
    assert cpk.compact_bucket(CloudSpec(n_pm=3, n_vm=24, compact=8)) == 8
    assert cpk.compact_bucket(CloudSpec(n_pm=3, n_vm=24, compact=12)) == 16
    assert cpk.compact_bucket(CloudSpec(n_pm=3, n_vm=24, compact=64)) == 0
    assert cpk.compact_bucket(CloudSpec(n_pm=3, n_vm=24, compact=0)) == 0
    # tiers: auto adds the small tier below a watermark of >= 4 x 64
    # flows; an explicit bucket pins one tier; compact=0 runs dense
    small = (64, 128)
    assert cpk.SMALL_TIER == small
    assert cpk.compact_tiers(CloudSpec(n_pm=20, n_vm=256)) == ((128, 128),)
    assert cpk.compact_tiers(CloudSpec(n_pm=25, n_vm=512)) == (
        small, (256, 256))
    assert cpk.compact_tiers(CloudSpec(n_pm=64, n_vm=1024)) == (
        small, (512, 512))
    assert cpk.compact_tiers(CloudSpec(n_pm=500, n_vm=4096)) == (
        small, (2048, 2048))
    assert cpk.compact_tiers(
        CloudSpec(n_pm=25, n_vm=512, compact=256)) == ((256, 256),)
    assert cpk.compact_tiers(
        CloudSpec(n_pm=25, n_vm=512, compact=64)) == ((64, 64),)
    assert cpk.compact_tiers(CloudSpec(n_pm=25, n_vm=512, compact=0)) == ()
    assert cpk.compact_tiers(CloudSpec(n_pm=3, n_vm=12)) == ()


def test_build_compact_ascending_and_ok():
    # ascending fidx (the bit-identity invariant for segment sums) and an
    # honest ok verdict
    spec = CloudSpec(n_pm=3, n_vm=13, compact=8)
    st = engine.init_state(spec, _scenario()[1])
    f_active = jnp.zeros((16,), bool).at[jnp.asarray([9, 2, 11, 5])].set(True)
    st = st._replace(f_active=f_active)
    cp = cpk.build_compact(spec, st, 8, 8)
    got = np.asarray(cp.fidx)[np.asarray(cp.fvalid)]
    np.testing.assert_array_equal(got, [2, 5, 9, 11])
    assert bool(cp.ok)


# ---------------------------------------------------------------------------
# bitwise equality: compacted vs dense
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bucket", [8, 16])
def test_compact_matches_dense_bitwise(bucket):
    spec, trace = _scenario()
    spec_c = dataclasses.replace(spec, compact=bucket)
    assert cpk.compact_bucket(spec_c) == bucket  # compaction really on
    res_d = jax.block_until_ready(engine.simulate(spec, trace))
    res_c = jax.block_until_ready(engine.simulate(spec_c, trace))
    _assert_tree_bitwise(res_d, res_c, f"bucket={bucket}")


def test_compact_overflow_warns_and_replays_dense():
    # a 2-lane bucket cannot hold the active set: the checked compaction
    # must warn and replay densely — same bits, never a wrong answer
    spec, trace = _scenario()
    res_d = jax.block_until_ready(engine.simulate(spec, trace))
    spec_tiny = dataclasses.replace(spec, compact=2)
    assert cpk.compact_bucket(spec_tiny) == 2
    with pytest.warns(RuntimeWarning, match="overflowed"):
        res_t = jax.block_until_ready(engine.simulate(spec_tiny, trace))
    _assert_tree_bitwise(res_d, res_t, "overflow replay")


@pytest.mark.parametrize("k", [2, 4])
def test_coalesced_steps_match_k1(k):
    # K micro-steps per while_loop body: the cond-guarded extra passes are
    # exact skips once settled, so any K gives the K=1 bits
    spec, trace = _scenario()
    spec_c = dataclasses.replace(spec, compact=8)
    res_1 = jax.block_until_ready(
        engine.simulate(dataclasses.replace(spec_c, steps_per_iter=1), trace))
    res_k = jax.block_until_ready(
        engine.simulate(dataclasses.replace(spec_c, steps_per_iter=k), trace))
    _assert_tree_bitwise(res_1, res_k, f"K={k}")


def test_stream_compact_matches_dense_bitwise():
    spec, trace = _scenario()
    spec_c = dataclasses.replace(spec, compact=8)
    wt = chunk_trace(trace, 8)
    res_d = jax.block_until_ready(engine.simulate_stream(spec, wt))
    res_c = jax.block_until_ready(engine.simulate_stream(spec_c, wt))
    _assert_tree_bitwise(res_d, res_c, "stream compact")


def test_batch_compact_matches_dense_bitwise():
    spec, trace = _scenario()
    spec_c = dataclasses.replace(spec, compact=8)
    params = CloudParams.for_spec(spec)
    batched = jax.tree.map(
        lambda x: jnp.stack([x, x * np.float32(1.25)]),
        params.perf_core)
    params_b = dataclasses.replace(params, perf_core=batched)
    res_d = jax.block_until_ready(
        engine.simulate_batch(spec, trace, params_b))
    res_c = jax.block_until_ready(
        engine.simulate_batch(spec_c, trace, params_b))
    _assert_tree_bitwise(res_d, res_c, "batch compact")


# ---------------------------------------------------------------------------
# event-gated management (registry triggers, DESIGN.md §7)
# ---------------------------------------------------------------------------

def test_trigger_gates_are_identity():
    """A policy ``trigger`` is a *necessary* condition: forcing every gate
    open (always running the stage bodies) must not change a single bit —
    the gates only skip iterations whose body would have been a no-op."""
    spec, trace = _scenario(seed=5)
    params = CloudParams.for_spec(spec, vm_sched="firstfit",
                                  pm_sched="ondemand")
    real_branches = registry.trigger_branches
    try:
        res_gated = jax.block_until_ready(
            engine.simulate(spec, trace, params))

        def all_open(layer, ctx):
            return tuple(lambda st: jnp.bool_(True)
                         for _ in registry.policies(layer))

        registry.trigger_branches = all_open
        engine.simulate.clear_cache()
        res_open = jax.block_until_ready(
            engine.simulate(spec, trace, params))
    finally:
        registry.trigger_branches = real_branches
        engine.simulate.clear_cache()
    # the answers, not the gate counts (res.counters), which the forced
    # gates change by construction
    _assert_tree_bitwise(res_gated._replace(counters=None),
                         res_open._replace(counters=None), "trigger gate")


def test_trigger_registration_contract():
    # every registered trigger is callable; trigger_branches gives the
    # constant-True branch to trigger-less policies
    for layer in ("vm", "pm"):
        for p in registry.policies(layer):
            assert p.trigger is None or callable(p.trigger)


# ---------------------------------------------------------------------------
# bucket tiers (DESIGN.md §7): the smallest spec on which auto compaction
# holds both tiers, under a trace whose active set crosses 64 both ways
# ---------------------------------------------------------------------------

TIERED = CloudSpec(n_pm=25, n_vm=512)


def _tier_trace(burst: int, T=160, seed=3) -> Trace:
    """``burst`` one-core tasks within a second of t = 10, the rest spread
    over 600 s: the live set rises past 64 flows and falls back."""
    rng = np.random.default_rng(seed)
    arr = np.sort(np.concatenate([10.0 + rng.uniform(0, 1, burst),
                                  rng.uniform(0, 600, T - burst)]))
    return Trace(arrival=jnp.asarray(arr.astype(np.float32)),
                 cores=jnp.ones((T,), jnp.float32),
                 work=jnp.asarray(rng.uniform(5, 50, T).astype(np.float32)))


def _answers(res):
    """Everything but the loop counters: ``small_bucket_iters`` reads 0
    on the dense run by construction."""
    return res._replace(counters=res.counters._replace(
        small_bucket_iters=None))


@pytest.fixture(scope="module")
def dense_burst():
    tr = _tier_trace(90)
    return tr, jax.block_until_ready(
        engine.simulate(dataclasses.replace(TIERED, compact=0), tr))


def test_small_build_matches_build_compact():
    spec = TIERED
    S = spec.layout.S
    F = spec.n_vm + spec.n_pm
    st0 = engine.init_state(spec, _tier_trace(0, T=8))
    small = jax.jit(lambda st: cpk.build_small(spec, st, *cpk.SMALL_TIER))
    ref = jax.jit(lambda st: cpk.build_compact(spec, st, *cpk.SMALL_TIER))
    rng = np.random.default_rng(0)
    for n in (0, 1, 2, 17, 63, 64, 65, 200, F):
        active = np.zeros(F, bool)
        active[rng.choice(F, n, replace=False)] = True
        # endpoints shared between flows, so de-duplication matters
        st = st0._replace(
            f_active=jnp.asarray(active),
            f_prov=jnp.asarray(rng.integers(0, S, F).astype(np.int32)),
            f_cons=jnp.asarray(rng.integers(0, 40, F).astype(np.int32)))
        got, want = small(st), ref(st)
        _assert_tree_bitwise(got, want, f"n={n}")
        assert bool(got.ok) == (n <= 64)


@pytest.mark.parametrize("entry", ["simulate", "simulate_stream",
                                   "simulate_batch"])
def test_tiered_matches_dense_bitwise(entry, dense_burst):
    tr, res_d = dense_burst
    assert cpk.compact_tiers(TIERED)[0] == cpk.SMALL_TIER
    if entry == "simulate":
        res = jax.block_until_ready(engine.simulate(TIERED, tr))
        _assert_tree_bitwise(_answers(res), _answers(res_d), entry)
        small = int(res.counters.small_bucket_iters)
        assert 0 < small < int(res.n_events)  # both tiers ran
    elif entry == "simulate_stream":
        wt = chunk_trace(tr, 40)
        dense = dataclasses.replace(TIERED, compact=0)
        res = jax.block_until_ready(engine.simulate_stream(TIERED, wt))
        want = jax.block_until_ready(engine.simulate_stream(dense, wt))
        _assert_tree_bitwise(_answers(res), _answers(want), entry)
        small = int(res.counters.small_bucket_iters)
        assert 0 < small < int(res.n_events)
    else:
        # lanes of different occupancy: the burst lane sets the tier of
        # the quiet one, which must not change its bits
        quiet = _tier_trace(0)
        res = jax.block_until_ready(engine.simulate_batch(
            TIERED, engine.stack_traces([tr, quiet]),
            CloudParams.for_spec(TIERED)))
        res_q = engine.simulate(dataclasses.replace(TIERED, compact=0),
                                quiet)
        for i, want in enumerate((res_d, res_q)):
            lane = jax.tree.map(lambda x: x[i], res)
            # the lanes take the provider-only solves together: a lane
            # counts at most what its own run counts
            own = lane.counters.provider_only_solves
            assert own <= want.counters.provider_only_solves, i
            lane = lane._replace(counters=lane.counters._replace(
                provider_only_solves=want.counters.provider_only_solves))
            _assert_tree_bitwise(_answers(lane), _answers(want),
                                 f"lane {i}")
        small = np.asarray(res.counters.small_bucket_iters)
        n = np.asarray(res.n_events)
        assert 0 < small[0] < n[0] and small[1] < n[1]


def test_small_bucket_iters():
    quiet = _tier_trace(0)
    res = engine.simulate(TIERED, quiet)
    # nothing exceeds 64 flows: every iteration ran on the small tier
    assert int(res.counters.small_bucket_iters) == int(res.n_events)
    pinned = engine.simulate(dataclasses.replace(TIERED, compact=256),
                             quiet)
    assert int(pinned.counters.small_bucket_iters) == 0
    _assert_tree_bitwise(_answers(res), _answers(pinned), "pinned")
    dense = engine.simulate(dataclasses.replace(TIERED, compact=0), quiet)
    assert int(dense.counters.small_bucket_iters) == 0


# ---------------------------------------------------------------------------
# the in-program dense branch (DESIGN.md §7): under the auto rule a pass
# whose active set outgrows the largest tier runs dense in the same
# program, where the slots and the trace can hold such a set
# ---------------------------------------------------------------------------

# auto watermark next_pow2(4 * 8 + 32) = 64 flows, one tier; 96 one-core
# tasks within a second outgrow it on 8 PMs of 64 cores
DENSE = CloudSpec(n_pm=8, n_vm=256)


def _dense_trace(T=140, burst=96, seed=4) -> Trace:
    rng = np.random.default_rng(seed)
    arr = np.sort(np.concatenate([5.0 + rng.uniform(0, 1, burst),
                                  rng.uniform(0, 900, T - burst)]))
    cores = rng.choice([1.0, 2.0], T)
    util = rng.uniform(0.05, 1.0, T)
    return Trace(arrival=jnp.asarray(arr.astype(np.float32)),
                 cores=jnp.asarray(cores.astype(np.float32)),
                 work=jnp.asarray((rng.uniform(20, 400, T) * util * cores)
                                  .astype(np.float32)),
                 mem=jnp.asarray((cores * 4.0).astype(np.float32)),
                 util=jnp.asarray(util.astype(np.float32)))


def test_dense_branch_reachability():
    assert cpk.compact_tiers(DENSE) == ((64, 64),)
    assert cpk.dense_reachable(DENSE, 140)
    assert not cpk.dense_reachable(DENSE, 56)   # 56 + 8 flows fit 64
    assert not cpk.dense_reachable(dataclasses.replace(DENSE, compact=64),
                                   140)         # explicit: host replay
    assert not cpk.dense_reachable(dataclasses.replace(DENSE, compact=0),
                                   140)
    das2 = CloudSpec(n_pm=500, n_vm=4096)       # trace1k: 1000 + 500
    assert not cpk.dense_reachable(das2, 1000)
    assert cpk.dense_reachable(das2, 4608)      # the stream's slot pool
    grid = CloudSpec(n_pm=100, n_vm=1024)       # the sweeps: 250 + 100
    assert not cpk.dense_reachable(grid, 250)


def _no_replay(run):
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        return jax.block_until_ready(run())


@pytest.fixture(scope="module")
def dense_runs():
    tr = _dense_trace()
    want = jax.block_until_ready(
        engine.simulate(dataclasses.replace(DENSE, compact=0), tr))
    return tr, want


def _dense_answers(res):
    """Everything but the counters of the bucket tiers, which the dense
    run reads 0 by construction."""
    return res._replace(counters=res.counters._replace(
        small_bucket_iters=None, dense_iters=None))


@pytest.mark.parametrize("entry", ["simulate", "simulate_batch"])
def test_dense_branch_matches_dense_bitwise(dense_runs, entry):
    tr, want = dense_runs
    if entry == "simulate":
        res = _no_replay(lambda: engine.simulate(DENSE, tr))
    else:
        params = engine.stack_params([CloudParams.for_spec(DENSE)] * 2)
        res = jax.tree.map(lambda x: x[1], _no_replay(
            lambda: engine.simulate_batch(DENSE, tr, params)))
    _assert_tree_bitwise(_dense_answers(res), _dense_answers(want), entry)
    dense, n = int(res.counters.dense_iters), int(res.n_events)
    assert 0 < dense < n                          # both branches ran
    assert int(res.counters.live_flows) > 64 * dense
    assert int(res.counters.fill_truncated) == 0


def test_stream_from_a_generator_above_the_watermark(dense_runs):
    """A window generator cannot be replayed: above the watermark the
    stream runs dense in the program, and gives the monolithic bits."""
    tr, want = dense_runs
    wt = chunk_trace(tr, 32)
    res = _no_replay(lambda: engine.simulate_stream(
        DENSE, (w for w in wt.windows())))
    assert int(res.counters.dense_iters) > 0
    for name in ("completion", "rejected", "t_end", "n_events"):
        np.testing.assert_array_equal(_bits(getattr(res, name)),
                                      _bits(getattr(want, name)), name)
    _assert_tree_bitwise(res.meters, want.meters, "meters")
