"""Low-level scheduling logic of the unified resource sharing model (§3.2.3).

DISSECT-CF ships two sample schedulers:

* a *simple logic* that splits each spreader's capacity equally among its
  consumptions (no bottleneck handling) -> :func:`equal_share_rates`;
* a *max-min fairness* scheduler with progressive filling [Bertsekas-Gallager]
  -> :func:`maxmin_rates`.

Both are expressed over the dense consumption arrays.  ``maxmin_rates`` is the
simulation hot spot (the paper's unified sharing model exists to make exactly
this fast); its inner segmented reductions have a Pallas TPU kernel in
``repro.kernels.maxmin`` selected via ``backend='pallas'``.

Rates are in processing-units per simulated second; a consumption with rate
``r`` finishes after ``p_r / r`` simulated seconds (horizon mode) or drains by
``r * tau`` per tick (tau mode, Eq. 1-2).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from .arrays import Consumptions

_BIG = jnp.float32(3.0e38)


def _segment_sum(data: jax.Array, ids: jax.Array, num_segments: int) -> jax.Array:
    return jax.ops.segment_sum(data, ids, num_segments=num_segments)


# ---------------------------------------------------------------------------
# Simple logic: equal split on both endpoints (paper's demo scheduler)
# ---------------------------------------------------------------------------

def _equal_share_offers(
    provider: jax.Array,
    consumer: jax.Array,
    live: jax.Array,
    perf: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Per-flow (provider-side, consumer-side) equal-split offered rates:
    each spreader splits its capacity evenly among its live consumptions.
    Shared by :func:`equal_share_rates` (horizon mode) and
    :func:`step_tau` (Eq. 1-2 tau mode) — one code path, same semantics."""
    S = perf.shape[0]
    livef = live.astype(jnp.float32)
    cnt_p = _segment_sum(livef, provider, S)
    cnt_c = _segment_sum(livef, consumer, S)
    offer_p = perf[provider] / jnp.maximum(cnt_p[provider], 1.0)
    offer_c = perf[consumer] / jnp.maximum(cnt_c[consumer], 1.0)
    return offer_p, offer_c


def equal_share_rates(
    provider: jax.Array,
    consumer: jax.Array,
    p_l: jax.Array,
    live: jax.Array,
    perf: jax.Array,
    *,
    backend: str = "jnp",    # registry-uniform signature; unused
    max_iters: int = 0,      # registry-uniform signature; unused
    flow_caps: bool = False,  # registry-uniform signature; unused
    owned: jax.Array | None = None,  # registry-uniform signature; unused
) -> jax.Array:
    """rate = min(perf[prov]/n_prov, perf[cons]/n_cons, p_l)."""
    del backend, max_iters, flow_caps, owned
    offer_p, offer_c = _equal_share_offers(provider, consumer, live, perf)
    r = jnp.minimum(jnp.minimum(offer_p, offer_c), p_l)
    return jnp.where(live, r, 0.0)


# ---------------------------------------------------------------------------
# Max-min fairness via progressive filling
# ---------------------------------------------------------------------------

def _share(perf, committed, cnt):
    """A spreader's equal share of its spare capacity among its ``cnt``
    unfrozen flows (``_BIG`` where none is unfrozen)."""
    avail = jnp.maximum(perf - committed, 0.0)
    return jnp.where(cnt > 0, avail / jnp.maximum(cnt, 1.0), _BIG)


def _jnp_fill_stats(provider, consumer, r, live, unfrozen, perf):
    """One progressive-filling round of segmented stats (pure-jnp reference).

    Returns the per-spreader shares ``(dp, dc)`` on the provider and the
    consumer side.
    """
    S = perf.shape[0]
    rl = jnp.where(live, r, 0.0)
    uf = unfrozen.astype(jnp.float32)
    # One scatter-add covers all four segmented stats: provider-side rows
    # land in segments [0, S), consumer-side rows in [S, 2S), and the two
    # data columns carry (committed rate, unfrozen count).  Segments are
    # disjoint and rows keep their index order, so every stat is
    # bit-identical to its standalone segment_sum.
    ids = jnp.concatenate([provider, consumer + S])
    data = jnp.stack([jnp.concatenate([rl, rl]),
                      jnp.concatenate([uf, uf])], axis=-1)
    stats = _segment_sum(data, ids, 2 * S)
    return (_share(perf, stats[:S, 0], stats[:S, 1]),
            _share(perf, stats[S:, 0], stats[S:, 1]))


def _provider_fill_stats(provider, r, live, unfrozen, perf, perf_c):
    """:func:`_jnp_fill_stats` where every live flow's consumer is its own
    spreader: the provider shares ``dp`` from an F-row scatter, and each
    flow's consumer share ``sc`` from its own committed rate and unfrozen
    flag, with ``perf_c = perf[consumer]``.  A consumer segment that only
    one flow names sums that flow's row and exact ``+0.0`` rows, so ``sc``
    equals ``dc[consumer]`` bit for bit (DESIGN.md §7)."""
    rl = jnp.where(live, r, 0.0)
    uf = unfrozen.astype(jnp.float32)
    stats = _segment_sum(jnp.stack([rl, uf], axis=-1), provider,
                         perf.shape[0])
    return _share(perf, stats[:, 0], stats[:, 1]), _share(perf_c, rl, uf)


def maxmin_fill(
    provider: jax.Array,
    consumer: jax.Array,
    p_l: jax.Array,
    live: jax.Array,
    perf: jax.Array,
    *,
    max_iters: int = 64,
    backend: str = "jnp",
    rel_eps: float = 1e-5,
    flow_caps: bool = False,
    owned: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Max-min fair rates by progressive filling, the rounds it took, and
    whether it stopped at ``max_iters`` with a flow still unfrozen.

    All unfrozen flows rise at the same global increment until a constraint
    (provider capacity, consumer capacity, or the flow's own ``p_l``)
    saturates; saturated flows freeze; repeat.  Each round freezes at least
    one flow.

    ``flow_caps`` (static) says that a flow's own cap ``p_l`` may lie below
    the share of its spreaders, as a VM's utilisation cap does (DESIGN.md
    §7).  The round's increment is then the smallest spreader share alone;
    each flow rises by it or up to its cap, whichever is less; every flow
    at its cap freezes, and a flow at a spreader that reached its share
    freezes only where no flow at that spreader stopped below the
    increment (such a spreader still has room).  Rounds are then bounded by
    the spreader levels, not by the number of distinct caps.  Without caps
    below the shares no flow stops early, and the rule is the one without
    ``flow_caps``: the same increments and freezes.

    ``backend='pallas'`` solves the whole progressive filling in one fused
    kernel when the problem fits VMEM (``repro.kernels.maxmin.maxmin_solve``
    — the carried rate/freeze vectors never round-trip HBM between rounds),
    falling back to the round-wise Pallas ``fill_stats`` kernel above that
    size or under ``flow_caps``; ``'jnp'`` uses segment_sum throughout.
    The fused kernel does not report its rounds: it returns a round count
    of 0 and no truncation.

    ``owned`` (a traced bool, jnp backend only) says that no two live
    flows name the same consumer, as in the engine when no image transfer
    or migration is live: the rounds then reduce over the providers alone
    and give the same bits (DESIGN.md §7).  ``None`` runs every round over
    both sides.
    """
    if backend == "pallas":
        from repro.kernels import ops as _kops
        if (not flow_caps
                and _kops.maxmin_solve_fits(provider.shape[0],
                                            perf.shape[0])):
            return _kops.maxmin_solve_pallas(
                provider, consumer, p_l, live, perf,
                max_iters=max_iters, rel_eps=rel_eps), jnp.int32(0), \
                jnp.bool_(False)
        fill_stats = _kops.fill_stats_pallas
        owned = None
    else:
        fill_stats = _jnp_fill_stats

    C = provider.shape[0]
    S = perf.shape[0]
    init = (jnp.int32(0), jnp.zeros((C,), jnp.float32), live)

    def cond(state):
        i, r, unfrozen = state
        return jnp.logical_and(i < max_iters, unfrozen.any())

    def body(perf_c, state):
        """One round; ``perf_c = perf[consumer]`` takes the provider-only
        round (consumers that one flow each names), ``None`` the round
        over both sides."""
        i, r, unfrozen = state
        own = perf_c is not None
        if own:
            dp, sc = _provider_fill_stats(provider, r, live, unfrozen, perf,
                                          perf_c)
            sp = dp[provider]
        else:
            dp, dc = fill_stats(provider, consumer, r, live, unfrozen, perf)
            sp, sc = dp[provider], dc[consumer]
        room = jnp.maximum(p_l - r, 0.0)
        df = jnp.minimum(sp, sc)
        if not flow_caps:
            df = jnp.minimum(df, room)
        df = jnp.where(unfrozen, df, _BIG)
        delta = jnp.min(df)
        delta = jnp.where(jnp.isfinite(delta) & (delta < _BIG), delta, 0.0)
        level = delta * (1.0 + rel_eps) + 1e-12
        if not flow_caps:
            r = jnp.where(unfrozen, r + delta, r)
            # freeze flows whose own constraint bound the round
            tight = df <= level
        else:
            r = jnp.where(unfrozen, r + jnp.minimum(delta, room), r)
            # a spreader where a flow stopped at its cap below the
            # increment kept room: its other flows rise again next round
            stopped = unfrozen & (room < delta)
            stop_f = stopped.astype(jnp.float32)
            if own:
                spare_p = (_segment_sum(stop_f, provider, S) > 0)[provider]
                spare_c = stopped
            else:
                spare = _segment_sum(jnp.concatenate([stop_f, stop_f]),
                                     jnp.concatenate([provider, consumer + S]),
                                     2 * S) > 0
                spare_p, spare_c = spare[provider], spare[S + consumer]
            tight = ((room <= level)
                     | ((sp <= level) & ~spare_p)
                     | ((sc <= level) & ~spare_c))
        unfrozen = unfrozen & ~tight
        return i + 1, r, unfrozen

    def fill(own: bool):
        perf_c = perf[consumer] if own else None
        return jax.lax.while_loop(cond, functools.partial(body, perf_c), init)

    if owned is None:
        rounds, r, unfrozen = fill(False)
    else:
        rounds, r, unfrozen = jax.lax.cond(owned, lambda: fill(True),
                                           lambda: fill(False))
    return jnp.where(live, r, 0.0), rounds, unfrozen.any()


def maxmin_rates(provider, consumer, p_l, live, perf, *, max_iters=64,
                 backend="jnp", rel_eps=1e-5, flow_caps=False) -> jax.Array:
    """Max-min fair rates by progressive filling (:func:`maxmin_fill`
    without the round count)."""
    return maxmin_fill(provider, consumer, p_l, live, perf,
                       max_iters=max_iters, backend=backend,
                       rel_eps=rel_eps, flow_caps=flow_caps)[0]


# Low-level sharing-scheduler registry (paper §3.2.3 pluggable logic).
# Every entry has the uniform signature
# ``fn(provider, consumer, p_l, live, perf, *, backend, max_iters,
# flow_caps, owned) -> (rates, rounds, truncated)`` so the engine, the
# standalone sharing loop, and rates_for all select by name through this
# one table instead of string branches; ``rounds`` (i32) is the solve's
# progressive-filling rounds and ``truncated`` (bool) whether it stopped
# at ``max_iters`` with a flow unfrozen, which the engine counts
# (``LoopCounters.fill_rounds``, ``fill_truncated``); 0 and False for the
# one-shot equal split.
SCHEDULERS: dict[str, Callable] = {
    "equal": lambda *a, **kw: (equal_share_rates(*a, **kw), jnp.int32(0),
                               jnp.bool_(False)),
    "maxmin": maxmin_fill,
}


def rates_for(
    cons: Consumptions,
    t: jax.Array,
    perf: jax.Array,
    *,
    scheduler: str = "maxmin",
    backend: str = "jnp",
) -> tuple[jax.Array, jax.Array]:
    """Convenience: (rates, live mask) for the current instant."""
    from .arrays import live_mask

    live = live_mask(cons, t)
    r, *_ = SCHEDULERS[scheduler](cons.provider, cons.consumer, cons.p_l,
                                 live, perf, backend=backend)
    return r, live


# ---------------------------------------------------------------------------
# Exact tau-stepping semantics (paper Eq. 1-2)
# ---------------------------------------------------------------------------

def step_tau(
    cons: Consumptions,
    t: jax.Array,
    perf: jax.Array,
    tau: float | jax.Array,
    *,
    scheduler: str = "maxmin",
) -> Consumptions:
    """One exact tick of the provider->consumer two-pass update.

    Eq. 1 (provider side): ``p_u* = p_u + min(p_r, p(prov), p_l) * tau``  —
    the provider moves work from *remaining* into the in-flight buffer.
    Eq. 2 (consumer side): the consumer drains ``min(p(cons), p_l) * tau``
    from the buffer.

    Note on the printed Eq. 2: the article's formula for ``p_r(t+tau)`` as
    typeset would make ``p_u + p_r`` invariant (no work would ever complete);
    we use the conservation-consistent reading — ``p_r`` decreases by exactly
    the amount the provider moved into the buffer — which also matches the
    completion criterion ``p_u = 0 and p_r = 0`` given in §3.2.3.
    """
    tau = jnp.asarray(tau, jnp.float32)
    from .arrays import live_mask

    live = live_mask(cons, t)
    # p(c, s, t): per-side offered rates from the scheduling logic.
    if scheduler == "maxmin":
        rate = maxmin_rates(cons.provider, cons.consumer, cons.p_l, live, perf)
        offer_p = offer_c = rate
    else:
        offer_p, offer_c = _equal_share_offers(cons.provider, cons.consumer,
                                               live, perf)

    moved = jnp.minimum(cons.p_r, jnp.minimum(offer_p, cons.p_l) * tau)
    moved = jnp.where(live, moved, 0.0)
    p_u_star = cons.p_u + moved
    drained = jnp.minimum(p_u_star, jnp.minimum(offer_c, cons.p_l) * tau)
    drained = jnp.where(live, drained, 0.0)
    return cons._replace(
        p_u=p_u_star - drained,
        p_r=cons.p_r - moved,
    )
