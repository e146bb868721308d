"""Stage 5 — ``pm_sched``: the PM state-scheduler policy hook (§3.5.1).

Pure dispatch: the stage reads ``params.pm_sched`` (an integer code —
*data*, so heterogeneous cells batch through one compiled program) and
``lax.switch``es over the branch list of the open policy registry
(:mod:`repro.sched.registry`, DESIGN.md §6).  The core knows no policy by
name — always-on, on-demand, consolidation, defragmentation, evacuation
and any out-of-tree policy are all :mod:`repro.sched.policies` citizens
registered under stable codes.

The hook runs after the power/lifecycle stages of the pipeline with the
fresh ``ctx.view`` / live ``st.meters`` published by ``observe``, which is
what lets policies at this layer react to metering state without leaving
the loop (the paper's cross-layer scheduling pitch, §1/§3.4).

State delta: whatever the selected policy's registered ``requires``
metadata declares (wake/sleep transitions, hidden-consumer flow slots,
migration rewrites of VM/flow state and ``free_cores``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sched import registry

from .state import CloudState, StageCtx


def pm_sched(ctx: StageCtx, st: CloudState):
    code = jnp.asarray(ctx.params.pm_sched, jnp.int32)
    # Event gate (registry trigger, DESIGN.md §7): e.g. always-on is the
    # identity and gates constant-False; on-demand gates on "queue
    # non-empty or a loadless running host exists".  Policies without a
    # declared trigger run unconditionally, exactly as before.
    may = jax.lax.switch(code, registry.trigger_branches("pm", ctx), st)
    st = jax.lax.cond(
        may,
        lambda s: jax.lax.switch(code, registry.stage_branches("pm", ctx), s),
        lambda s: s, st)
    return ctx._replace(pm_gate=may), st
