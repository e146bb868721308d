"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth its kernel is tested against
(tests/test_kernels.py sweeps shapes/dtypes and asserts allclose).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_BIG = jnp.float32(3.0e38)


# ---------------------------------------------------------------------------
# maxmin progressive-filling round statistics (paper §3.2.3 hot loop)
# ---------------------------------------------------------------------------

def fill_stats_ref(provider, consumer, r, live, unfrozen, perf):
    """Per-spreader headroom for one progressive-filling round.

    Returns (dp, dc): f32[S] per-spreader increment headroom
    ``max(perf - committed, 0) / count_unfrozen`` (``_BIG`` where no
    unfrozen flow touches the spreader).
    """
    S = perf.shape[0]
    rl = jnp.where(live, r, 0.0)
    uf = unfrozen.astype(jnp.float32)
    committed_p = jax.ops.segment_sum(rl, provider, num_segments=S)
    committed_c = jax.ops.segment_sum(rl, consumer, num_segments=S)
    cnt_p = jax.ops.segment_sum(uf, provider, num_segments=S)
    cnt_c = jax.ops.segment_sum(uf, consumer, num_segments=S)
    avail_p = jnp.maximum(perf - committed_p, 0.0)
    avail_c = jnp.maximum(perf - committed_c, 0.0)
    dp = jnp.where(cnt_p > 0, avail_p / jnp.maximum(cnt_p, 1.0), _BIG)
    dc = jnp.where(cnt_c > 0, avail_c / jnp.maximum(cnt_c, 1.0), _BIG)
    return dp, dc


def maxmin_solve_ref(provider, consumer, p_l, live, perf, *,
                     max_iters: int = 64, rel_eps: float = 1e-5):
    """Full progressive-filling solve (the engine's per-interval max-min
    fair-share problem, paper §3.2.3) — ground truth for the fused
    ``repro.kernels.maxmin.maxmin_solve`` kernel.

    Identical round recurrence to ``repro.core.fairshare.maxmin_rates``
    (without ``flow_caps``) with the pure-jnp fill stats.
    """
    C = provider.shape[0]
    r0 = jnp.zeros((C,), jnp.float32)

    def body(state):
        i, r, unfrozen = state
        dp, dc = fill_stats_ref(provider, consumer, r, live, unfrozen, perf)
        df = jnp.minimum(dp[provider], dc[consumer])
        df = jnp.minimum(df, jnp.maximum(p_l - r, 0.0))
        df = jnp.where(unfrozen, df, _BIG)
        delta = jnp.min(df)
        delta = jnp.where(jnp.isfinite(delta) & (delta < _BIG), delta, 0.0)
        r = jnp.where(unfrozen, r + delta, r)
        tight = df <= delta * (1.0 + rel_eps) + 1e-12
        return i + 1, r, unfrozen & ~tight

    def cond(state):
        i, _r, unfrozen = state
        return jnp.logical_and(i < max_iters, unfrozen.any())

    _, r, _ = jax.lax.while_loop(cond, body, (jnp.int32(0), r0, live))
    return jnp.where(live, r, 0.0)


# ---------------------------------------------------------------------------
# event horizon: masked min over the candidate time-to-event vector
# ---------------------------------------------------------------------------

def masked_min_ref(cand: jax.Array, mask: jax.Array) -> jax.Array:
    """Scalar ``min(cand[mask])`` with ``_BIG`` as the empty-set identity —
    the engine's fused event-horizon reduction (loop/advance.py)."""
    return jnp.min(jnp.where(mask, cand, _BIG))

