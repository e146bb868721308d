"""Bare unified-resource-sharing simulation (paper §3.2 as a standalone core).

``run_sharing`` simulates a set of resource consumptions over a set of
spreaders to completion using event-horizon time jumps: rates are
piecewise-constant between events (arrivals / latency releases /
completions), so jumping to the next event and integrating exactly is
equivalent to DISSECT-CF's ``Timed`` time-jump control (§3.1) — no per-tau
ticking.  This is the hot core used by the CPU-sharing and networking
validation experiments (Figs. 7-9) and the pure-sharing performance
benchmarks (Fig. 12/13, Table 3).

The full IaaS engine (engine.py) embeds the same loop with infrastructure
state around it.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .fairshare import SCHEDULERS

_BIG = jnp.float32(3.0e38)


class SharingProblem(NamedTuple):
    """A static description of spreaders + consumptions.

    ``t_start`` doubles as arrival time and latency gate (Eq. 10-11): the
    consumption exists but is non-performing before it.
    """

    perf: jax.Array       # f32[S] spreader capacity (units/s)
    provider: jax.Array   # i32[C]
    consumer: jax.Array   # i32[C]
    amount: jax.Array     # f32[C] total units to process
    limit: jax.Array      # f32[C] per-consumption rate cap (p_l)
    t_start: jax.Array    # f32[C]

    @staticmethod
    def build(perf, provider, consumer, amount, limit=None, t_start=None):
        provider = jnp.asarray(provider, jnp.int32)
        amount = jnp.asarray(amount, jnp.float32)
        C = amount.shape[0]
        if limit is None:
            limit = jnp.full((C,), _BIG)
        if t_start is None:
            t_start = jnp.zeros((C,), jnp.float32)
        return SharingProblem(
            perf=jnp.asarray(perf, jnp.float32),
            provider=provider,
            consumer=jnp.asarray(consumer, jnp.int32),
            amount=amount,
            limit=jnp.asarray(limit, jnp.float32),
            t_start=jnp.asarray(t_start, jnp.float32),
        )


class SharingResult(NamedTuple):
    completion: jax.Array   # f32[C] completion times (inf if never finished)
    t_end: jax.Array        # f32 simulation end time
    n_events: jax.Array     # i32 number of horizon jumps
    ok: jax.Array           # bool — all consumptions completed
    energy: jax.Array       # f32[S] per-spreader energy (J) if power given else 0
    processed: jax.Array    # f32[S] provider-side processed units (util counter)


@functools.partial(
    jax.jit,
    static_argnames=("scheduler", "backend", "max_events", "max_fill_iters"),
)
def run_sharing(
    prob: SharingProblem,
    *,
    scheduler: str = "maxmin",
    backend: str = "jnp",
    max_events: int = 1_000_000,
    max_fill_iters: int = 64,
    p_idle: jax.Array | None = None,
    p_span: jax.Array | None = None,
) -> SharingResult:
    """Simulate to completion; optionally integrate a linear power model
    ``P(s) = p_idle[s] + p_span[s] * utilisation(s)`` per spreader."""
    S = prob.perf.shape[0]
    C = prob.amount.shape[0]
    with_power = p_idle is not None
    if p_idle is None:
        p_idle = jnp.zeros((S,), jnp.float32)
    if p_span is None:
        p_span = jnp.zeros((S,), jnp.float32)

    thresh = 1e-6 * prob.amount + 1e-9
    exists = prob.amount > 0.0

    rate_fn = SCHEDULERS[scheduler]

    def rates_of(p_r, t):
        live = exists & (p_r > thresh) & (t >= prob.t_start)
        r, *_ = rate_fn(prob.provider, prob.consumer, prob.limit, live,
                       prob.perf, backend=backend, max_iters=max_fill_iters)
        return r, live

    class _St(NamedTuple):
        t: jax.Array
        t_c: jax.Array
        p_r: jax.Array
        completion: jax.Array
        n: jax.Array
        energy: jax.Array
        running: jax.Array

    st0 = _St(
        t=jnp.float32(0.0), t_c=jnp.float32(0.0),
        p_r=prob.amount,
        completion=jnp.where(exists, jnp.inf, 0.0).astype(jnp.float32),
        n=jnp.int32(0),
        energy=jnp.zeros((S,), jnp.float32),
        running=jnp.bool_(True),
    )

    def cond(st: _St):
        return st.running & (st.n < max_events)

    def body(st: _St):
        r, live = rates_of(st.p_r, st.t)
        # Event horizon: next completion or next arrival/latency release.
        ttc = jnp.where(live & (r > 0), st.p_r / jnp.maximum(r, 1e-30), _BIG)
        pending_start = exists & (st.p_r > thresh) & (st.t < prob.t_start)
        tta = jnp.where(pending_start, prob.t_start - st.t, _BIG)
        dt = jnp.minimum(jnp.min(ttc), jnp.min(tta))
        running = dt < _BIG
        dt = jnp.where(running, jnp.maximum(dt, 0.0), 0.0)

        if with_power:
            delivered = jax.ops.segment_sum(r, prob.provider, num_segments=S)
            util = delivered / jnp.maximum(prob.perf, 1e-30)
            power = p_idle + p_span * jnp.clip(util, 0.0, 1.0)
            energy = st.energy + power * dt
        else:
            energy = st.energy

        # Kahan-compensated clock.
        y = dt - st.t_c
        t_new = st.t + y
        t_c = (t_new - st.t) - y

        p_r = jnp.where(live, jnp.maximum(st.p_r - r * dt, 0.0), st.p_r)
        newly_done = live & (p_r <= thresh) & jnp.isinf(st.completion)
        completion = jnp.where(newly_done, t_new, st.completion)
        p_r = jnp.where(newly_done, 0.0, p_r)
        return _St(t=t_new, t_c=t_c, p_r=p_r, completion=completion,
                   n=st.n + 1, energy=energy, running=running)

    st = jax.lax.while_loop(cond, body, st0)
    processed = jax.ops.segment_sum(prob.amount - st.p_r, prob.provider,
                                    num_segments=S)
    ok = ~jnp.any(exists & jnp.isinf(st.completion))
    return SharingResult(completion=st.completion, t_end=st.t,
                         n_events=st.n, ok=ok, energy=st.energy,
                         processed=processed)


def run_sharing_tau(
    prob: SharingProblem,
    *,
    tau: float,
    n_steps: int,
    scheduler: str = "maxmin",
) -> jax.Array:
    """Exact Eq. 1-2 tau-stepping over the same problem; returns completion
    times quantised to tau.  Used to validate that horizon mode and the
    paper's per-tick semantics agree (tests/test_core_sharing.py)."""
    from .arrays import Consumptions, empty_consumptions
    from .fairshare import step_tau

    C = prob.amount.shape[0]
    cons = empty_consumptions(C)
    cons = Consumptions(
        p_u=jnp.zeros((C,)), p_r=prob.amount, p_l=prob.limit,
        provider=prob.provider, consumer=prob.consumer,
        active=prob.amount > 0, t_release=prob.t_start,
        kind=cons.kind, ref=cons.ref, total=prob.amount,
    )
    thresh = 1e-6 * prob.amount + 1e-9

    def step(carry, _):
        cons, t, completion = carry
        cons = step_tau(cons, t, prob.perf, tau, scheduler=scheduler)
        t = t + tau
        done = cons.active & (cons.p_r + cons.p_u <= thresh)
        completion = jnp.where(done & jnp.isinf(completion), t, completion)
        cons = cons._replace(active=cons.active & ~done)
        return (cons, t, completion), None

    completion0 = jnp.where(prob.amount > 0, jnp.inf, 0.0).astype(jnp.float32)
    (cons, t, completion), _ = jax.lax.scan(
        step, (cons, jnp.float32(0.0), completion0), None, length=n_steps)
    return completion
