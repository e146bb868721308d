"""Stage 4 — ``pm_power``: physical-machine power-state transitions.

Finishes PM switching states (paper Table 1/2, Fig. 5): under the complex
model a transition ends when its *hidden consumer* flow drains (the
hidden-consumer suffix of ``ctx.done``); under the simple model it ends at
the ``pstate_end`` deadline.

State delta: ``pstate``, ``pstate_end``, and the hidden-consumer suffix of
``f_active``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..energy import PM_OFF, PM_RUNNING, PM_SWITCHING_OFF, PM_SWITCHING_ON
from .state import CloudState, StageCtx


def pm_power(ctx: StageCtx, st: CloudState):
    # Event gate (DESIGN.md §7): transitions end either on a hidden-flow
    # completion (complex model) or a pstate_end deadline; with neither
    # fired this iteration every write below selects the old value, so
    # skipping the body is bitwise identity.
    spec = ctx.spec
    switching = ((st.pstate == PM_SWITCHING_ON)
                 | (st.pstate == PM_SWITCHING_OFF))
    fired = (ctx.done[spec.n_vm:].any()
             | (switching & (st.pstate_end <= ctx.t_new)).any())
    return ctx._replace(power_gate=fired), jax.lax.cond(
        fired, lambda s: _pm_power_body(ctx, s), lambda s: s, st)


def _pm_power_body(ctx: StageCtx, st: CloudState) -> CloudState:
    spec = ctx.spec
    P, V = spec.n_pm, spec.n_vm
    hid_slot = jnp.arange(P) + V

    # hidden consumer completion ends complex power transitions
    hdone = ctx.done[V:]
    pstate = st.pstate
    pstate_end = st.pstate_end
    if spec.complex_power:
        pstate = jnp.where(hdone & (pstate == PM_SWITCHING_ON),
                           PM_RUNNING, pstate)
        pstate = jnp.where(hdone & (pstate == PM_SWITCHING_OFF),
                           PM_OFF, pstate)
    f_active = st.f_active.at[hid_slot].set(
        jnp.where(hdone, False, st.f_active[hid_slot]))

    # PM simple-model transitions by deadline
    ponend = (pstate == PM_SWITCHING_ON) & (pstate_end <= ctx.t_new)
    poffend = (pstate == PM_SWITCHING_OFF) & (pstate_end <= ctx.t_new)
    pstate = jnp.where(ponend, PM_RUNNING, pstate)
    pstate = jnp.where(poffend, PM_OFF, pstate)
    pstate_end = jnp.where(ponend | poffend, jnp.inf, pstate_end)

    return st._replace(pstate=pstate, pstate_end=pstate_end,
                       f_active=f_active)
