"""The shared masked live-migration primitive (paper Fig. 6:
running -> suspend-transfer/migrating -> resume on the new host).

This is *machinery*, not policy: the one implementation of "begin
live-migrating VM ``v`` to PM ``dst``" that every caller shares —

* the public out-of-loop API (:func:`repro.core.engine.start_migration`
  is a thin shim over :func:`migrate_one`);
* the in-loop PM policies contributed through the scheduler registry
  (:mod:`repro.sched.policies`): consolidation issues one masked move per
  iteration, multi-VM evacuation folds up to ``spec.max_migrations``
  moves through :func:`migrate_many` so a donor drains in one pass.

Cores (and, with a memory dimension, the VM's memory) move src -> dst
immediately (allocation semantics); the VM's flow slot becomes the
serialized memory state moving over the source NIC.
Refused (``ok=False``) lanes are bit-identical no-ops, which is what lets
policy branches stay masked data under ``vmap``/``lax.switch``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import machine as mc
from .state import BIG, KIND_MIGRATE, CloudState


def migrate_one(spec, params, st: CloudState, v, dst, ok) -> CloudState:
    """Begin live-migrating VM slot ``v`` to PM ``dst``, masked by ``ok``.

    Feasibility is re-checked here (the VM must be RUNNING and the
    destination must have the cores free, and its memory where the state
    has a memory dimension), so callers may pass optimistic masks: an
    infeasible move degrades to a bitwise no-op.
    """
    lay = spec.layout
    v = jnp.asarray(v, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    src = st.vm_host[v]
    ok = ok & (st.vstage[v] == mc.VM_RUNNING) & \
        (st.free_cores[dst] >= st.vm_cores[v])
    if st.free_mem is not None:
        ok = ok & (st.free_mem[dst] >= st.vm_mem[v])
        st = st._replace(free_mem=(
            st.free_mem.at[src].add(jnp.where(ok, st.vm_mem[v], 0.0))
            .at[dst].add(jnp.where(ok, -st.vm_mem[v], 0.0))))

    def w(arr, val):
        return arr.at[v].set(jnp.where(ok, val, arr[v]))

    return st._replace(
        vstage=w(st.vstage, mc.VM_MIGRATING),
        vm_mig_dst=w(st.vm_mig_dst, dst),
        vm_saved_pr=w(st.vm_saved_pr, st.f_pr[v]),
        free_cores=(st.free_cores
                    .at[src].add(jnp.where(ok, st.vm_cores[v], 0.0))
                    .at[dst].add(jnp.where(ok, -st.vm_cores[v], 0.0))),
        f_pr=w(st.f_pr, params.vm_mem_mb),
        f_total=w(st.f_total, params.vm_mem_mb),
        f_pl=w(st.f_pl, BIG),
        f_prov=w(st.f_prov, lay.netout0 + src),
        f_cons=w(st.f_cons, lay.netin0 + dst),
        f_active=w(st.f_active, True),
        f_release=w(st.f_release, st.t + params.latency_s),
        f_kind=w(st.f_kind, KIND_MIGRATE),
        running=st.running | ok,
    )


def migrate_many(spec, params, st: CloudState, vs, dsts, ok) -> CloudState:
    """Fold up to ``K = len(vs)`` masked moves through :func:`migrate_one`
    sequentially (a length-``K`` ``lax.scan``), so later moves see the
    ``free_cores`` (and ``free_mem``) earlier moves already committed — K moves into one
    destination cannot overcommit it even if the caller's plan was
    optimistic."""
    vs = jnp.asarray(vs, jnp.int32).reshape(-1)
    dsts = jnp.asarray(dsts, jnp.int32).reshape(-1)
    ok = jnp.asarray(ok, bool).reshape(-1)

    def step(s, move):
        v, d, o = move
        return migrate_one(spec, params, s, v, d, o), None

    st, _ = jax.lax.scan(step, st, (vs, dsts, ok))
    return st
