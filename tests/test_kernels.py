"""Pallas kernel sweeps: every kernel validated against its pure-jnp oracle
in interpret mode (CPU) over shape/dtype/feature grids."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.horizon import NB, masked_min
from repro.kernels.maxmin import fill_stats, maxmin_solve


# ---------------------------------------------------------------------------
# maxmin fill stats
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("C,S,seed", [(8, 4, 0), (64, 16, 1), (300, 40, 2),
                                      (1024, 128, 3), (2000, 260, 4)])
def test_fill_stats_matches_ref(C, S, seed):
    rng = np.random.RandomState(seed)
    provider = jnp.asarray(rng.randint(0, S, C), jnp.int32)
    consumer = jnp.asarray(rng.randint(0, S, C), jnp.int32)
    r = jnp.asarray(rng.rand(C).astype(np.float32))
    live = jnp.asarray(rng.rand(C) < 0.8)
    unfrozen = live & jnp.asarray(rng.rand(C) < 0.7)
    perf = jnp.asarray((rng.rand(S) * 10).astype(np.float32))
    dp_ref, dc_ref = ref.fill_stats_ref(provider, consumer, r, live,
                                        unfrozen, perf)
    dp, dc = fill_stats(provider, consumer, r, live, unfrozen, perf,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(dp), np.asarray(dp_ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(dc), np.asarray(dc_ref), rtol=1e-5)


def test_fill_stats_degenerate_empty():
    C, S = 16, 8
    z = jnp.zeros((C,), jnp.int32)
    none = jnp.zeros((C,), bool)
    perf = jnp.ones((S,), jnp.float32)
    dp, dc = fill_stats(z, z, jnp.zeros((C,)), none, none, perf,
                        interpret=True)
    dp_ref, dc_ref = ref.fill_stats_ref(z, z, jnp.zeros((C,)), none, none,
                                        perf)
    np.testing.assert_allclose(np.asarray(dp), np.asarray(dp_ref))
    np.testing.assert_allclose(np.asarray(dc), np.asarray(dc_ref))


# ---------------------------------------------------------------------------
# fused maxmin full solve
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("C,S,seed", [(8, 4, 0), (64, 16, 1), (300, 40, 2),
                                      (1024, 130, 3),
                                      # exact compaction-bucket shapes
                                      # (DESIGN.md §7): C = FB, S = 2*SB+2
                                      (128, 258, 5), (129, 258, 6)])
def test_maxmin_solve_matches_ref(C, S, seed):
    rng = np.random.RandomState(seed)
    provider = jnp.asarray(rng.randint(0, S, C), jnp.int32)
    consumer = jnp.asarray(rng.randint(0, S, C), jnp.int32)
    p_l = jnp.asarray((rng.rand(C) * 4 + 0.1).astype(np.float32))
    live = jnp.asarray(rng.rand(C) < 0.8)
    perf = jnp.asarray((rng.rand(S) * 10).astype(np.float32))
    want = ref.maxmin_solve_ref(provider, consumer, p_l, live, perf)
    got = maxmin_solve(provider, consumer, p_l, live, perf, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_maxmin_solve_degenerate_empty():
    C, S = 16, 8
    z = jnp.zeros((C,), jnp.int32)
    none = jnp.zeros((C,), bool)
    got = maxmin_solve(z, z, jnp.ones((C,), jnp.float32), none,
                       jnp.ones((S,), jnp.float32), interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.zeros((C,), np.float32))


def test_maxmin_solve_matches_engine_scheduler():
    """The fused solve must agree with the engine's jnp maxmin_rates (the
    golden path) — same freeze recurrence, same rel_eps semantics."""
    from repro.core.fairshare import maxmin_rates
    rng = np.random.RandomState(7)
    C, S = 200, 30
    provider = jnp.asarray(rng.randint(0, S, C), jnp.int32)
    consumer = jnp.asarray(rng.randint(S // 2, S, C), jnp.int32)
    p_l = jnp.asarray((rng.rand(C) * 3 + 0.05).astype(np.float32))
    live = jnp.asarray(rng.rand(C) < 0.9)
    perf = jnp.asarray((rng.rand(S) * 8).astype(np.float32))
    want = maxmin_rates(provider, consumer, p_l, live, perf, backend="jnp")
    got = maxmin_solve(provider, consumer, p_l, live, perf, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("C,S,seed", [(64, 80, 0), (300, 340, 1)])
def test_maxmin_distinct_caps_pallas_round_rule(C, S, seed):
    """Distinct per-flow caps: under ``flow_caps`` the Pallas backend runs
    the round-wise ``fill_stats`` kernel under the engine's round rule
    (the fused solve keeps the rule without caps below the shares) and
    gives the jnp rates; where every cap lies at or above its consumer's
    share, the fused solve gives them too."""
    from repro.core.fairshare import maxmin_fill
    rng = np.random.RandomState(seed)
    hosts = S - C
    provider = jnp.asarray(rng.randint(0, hosts, C), jnp.int32)
    consumer = jnp.asarray(hosts + np.arange(C), jnp.int32)
    vm = rng.choice([1.0, 2.0, 4.0, 8.0], C)
    perf = jnp.asarray(np.concatenate([np.full(hosts, 16.0), vm]),
                       jnp.float32)
    live = jnp.asarray(rng.rand(C) < 0.9)
    caps = jnp.asarray(vm * np.clip(rng.beta(0.6, 2.4, C), 0.01, 1),
                       jnp.float32)
    want, _, cut = maxmin_fill(provider, consumer, caps, live, perf,
                               flow_caps=True)
    got, _, cut_p = maxmin_fill(provider, consumer, caps, live, perf,
                                backend="pallas", flow_caps=True)
    assert not bool(cut) and not bool(cut_p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    above = jnp.asarray(vm * (1.0 + rng.rand(C)), jnp.float32)
    want = maxmin_fill(provider, consumer, above, live, perf)[0]
    got = maxmin_solve(provider, consumer, above, live, perf,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# event-horizon masked min
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("N,seed", [(1, 0), (7, 1), (128, 2), (1025, 3),
                                    (5000, 4)])
def test_masked_min_matches_ref(N, seed):
    rng = np.random.RandomState(seed)
    cand = jnp.asarray((rng.randn(N) * 100).astype(np.float32))
    mask = jnp.asarray(rng.rand(N) < 0.6)
    want = ref.masked_min_ref(cand, mask)
    got = masked_min(cand, mask, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_masked_min_empty_mask_is_big():
    cand = jnp.arange(10, dtype=jnp.float32)
    mask = jnp.zeros((10,), bool)
    got = masked_min(cand, mask, interpret=True)
    assert float(got) == float(ref.masked_min_ref(cand, mask))
    assert float(got) == float(np.float32(3.0e38))


def test_masked_min_infinite_unmasked_lanes():
    """Unmasked +inf lanes (disabled meter / t_stop) must not leak."""
    cand = jnp.asarray([np.inf, 3.5, np.inf, 2.0], jnp.float32)
    mask = jnp.asarray([False, True, False, True])
    got = masked_min(cand, mask, interpret=True)
    assert float(got) == 2.0


@pytest.mark.parametrize("N", [3, 277, NB - 1, NB, NB + 1,
                               2 * NB - 1, 2 * NB, 2 * NB + 1])
def test_masked_min_block_boundaries(N):
    """Sizes straddling the block boundary route through both kernel
    variants: ``N <= NB`` hits the single-block bucket kernel (the shape
    the active-set-compacted horizon produces, DESIGN.md §7), ``N > NB``
    the grid sweep with the carried VMEM scratch — one extra element must
    never change the reduction."""
    rng = np.random.RandomState(N)
    cand = jnp.asarray((rng.randn(N) * 50).astype(np.float32))
    mask = jnp.asarray(rng.rand(N) < 0.5)
    want = ref.masked_min_ref(cand, mask)
    got = masked_min(cand, mask, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("N", [5, NB, NB + 1, 2 * NB])
def test_masked_min_all_masked_is_big(N):
    """An all-masked candidate vector yields the _BIG sentinel through
    both the single-block and the grid variant (the empty-horizon case the
    engine maps to 'no event')."""
    cand = jnp.asarray(np.linspace(-1e6, 1e6, N).astype(np.float32))
    mask = jnp.zeros((N,), bool)
    got = masked_min(cand, mask, interpret=True)
    assert float(got) == float(np.float32(3.0e38))


def test_masked_min_single_lane_survivor_at_block_edge():
    """Exactly one unmasked lane, sitting on the last lane of a block."""
    for N in (NB, NB + 1, 2 * NB):
        cand = np.full((N,), 7.5, np.float32)
        cand[NB - 1] = -3.25
        mask = np.zeros((N,), bool)
        mask[NB - 1] = True
        got = masked_min(jnp.asarray(cand), jnp.asarray(mask),
                         interpret=True)
        assert float(got) == -3.25, N

