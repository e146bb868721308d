"""The multi-VM evacuation PM policy (``pm_sched="evacuate"``).

Consolidation moves *one* VM per loop iteration, so a donor hosting
several idle-dominated VMs drains over several event horizons — and each
intermediate horizon re-evaluates triggers against a half-empty host.
Evacuation generalises the masked-migration machinery to up to
``CloudSpec.max_migrations`` moves per iteration: when the idle-dominance
trigger fires, the donor's running VMs (smallest first, the cheapest
serialized states) are *all* re-placed in one pass, each onto the
best-fit running host that still has the cores (and memory) free
**after** the moves planned before it — the plan threads cumulative
``free_cores`` and ``free_mem`` through a
scan, and :func:`repro.core.loop.migrate.migrate_many` re-checks the same
invariant while applying, so a K-deep plan can never overcommit a
destination.  The drained donor is powered down by the inherited
on-demand sleep rule on the next horizon.

Source/destination rules are consolidation's (idle-fraction trigger,
destinations at least as loaded as the donor), so single-VM donors behave
exactly like ``consolidate`` and the policy stays ping-pong-free.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.loop.migrate import migrate_many
from repro.core.loop.state import CloudState

from .. import registry
from .baseline import wake_sleep_pass
from .consolidate import MIGRATION_DELTA
from .select import (feasible_destinations, host_load_facts,
                     idle_dominated_donor, mem_of)


def evacuation_step(spec, params, st: CloudState) -> CloudState:
    """Drain one idle-dominated donor: up to ``spec.max_migrations`` masked
    moves planned against cumulative destination capacity."""
    K = max(1, min(int(spec.max_migrations), spec.n_vm))

    running, used, movable, n_movable = host_load_facts(spec, params, st)
    donor, src = idle_dominated_donor(params, st, running, used, n_movable)

    # victims: the donor's K smallest running VMs (cheapest to re-place)
    on_src = movable & (st.vm_host == src)
    order = jnp.argsort(jnp.where(on_src, st.vm_cores, jnp.inf))
    vs = order[:K].astype(jnp.int32)
    valid = on_src[vs]

    # plan destinations sequentially: each move sees the free cores (and
    # memory) left by the moves before it (same best-fit + load-ordering
    # rule as consolidation, against the iteration-start loads)
    def plan(free, v):
        free_c, free_m = free
        need = st.vm_cores[v]
        need_m = mem_of(st, v)
        fit = feasible_destinations(running, used, free_c, src, need,
                                    free_m, need_m)
        dst = jnp.argmin(jnp.where(fit, free_c, jnp.inf)).astype(jnp.int32)
        ok = fit.any()
        free_c = free_c.at[dst].add(jnp.where(ok, -need, 0.0))
        if free_m is not None:
            free_m = free_m.at[dst].add(jnp.where(ok, -need_m, 0.0))
        return (free_c, free_m), (dst, ok)

    _, (dsts, fits) = jax.lax.scan(plan, (st.free_cores, st.free_mem), vs)
    ok = valid & fits & donor.any()
    return migrate_many(spec, params, st, vs, dsts, ok)


def evacuate(spec, params, ctx, st: CloudState) -> CloudState:
    st = wake_sleep_pass(spec, params, ctx.trace, st)
    return evacuation_step(spec, params, st)


registry.register(
    "pm", "evacuate", evacuate, code=4, requires=MIGRATION_DELTA,
    doc="consolidation trigger, but the donor drains in one pass "
        "(up to CloudSpec.max_migrations moves per iteration)")
