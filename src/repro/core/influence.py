"""Influence groups (paper §3.2.2) as vectorized connected components.

An influence group is the connected component of the bipartite
provider/consumer graph induced by the live resource consumptions (Eq. 3).
DISSECT-CF maintains groups incrementally (Alg. 1) because recomputation is
expensive on a pointer machine; in the dense formulation we recompute by
min-label propagation — a handful of scatter-min rounds that vectorise and
batch, and whose fixpoint satisfies the paper's self-consistency property
(Eq. 4).  See DESIGN.md §2 for why Alg. 1 itself has no TPU analogue.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .machine import vms_per_pm

_BIG = jnp.int32(2**30)


def influence_labels(
    provider: jax.Array,
    consumer: jax.Array,
    live: jax.Array,
    num_spreaders: int,
    *,
    max_rounds: int = 0,
) -> jax.Array:
    """Return i32[S] group labels (min spreader index in the component).

    Spreaders with no live consumption form singleton groups labelled by
    themselves.  ``max_rounds=0`` auto-bounds by the spreader count (the
    propagation diameter can never exceed it); each round is O(C) scatter-min.
    """
    return influence_labels_rounds(provider, consumer, live, num_spreaders,
                                   max_rounds=max_rounds)[0]


def influence_labels_rounds(
    provider: jax.Array,
    consumer: jax.Array,
    live: jax.Array,
    num_spreaders: int,
    *,
    max_rounds: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """:func:`influence_labels` and the propagation rounds it ran (i32),
    the last of them the round that changed nothing."""
    S = num_spreaders
    if max_rounds <= 0:
        max_rounds = S
    label0 = jnp.arange(S, dtype=jnp.int32)
    prov = jnp.where(live, provider, 0)
    cons = jnp.where(live, consumer, 0)
    # Both endpoints of every live edge receive the same scatter-min, so a
    # single scatter over the concatenated index vector halves the per-round
    # scatter count (min is order-insensitive — the label fixpoint is
    # unchanged).
    ends = jnp.concatenate([prov, cons])

    def body(state):
        i, label, _changed = state
        edge = jnp.minimum(label[prov], label[cons])
        edge = jnp.where(live, edge, _BIG)
        new = label.at[ends].min(jnp.concatenate([edge, edge]))
        return i + 1, new, (new != label).any()

    def cond(state):
        i, _label, changed = state
        return jnp.logical_and(changed, i < max_rounds)

    rounds, label, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), label0, jnp.bool_(True))
    )
    return label, rounds


def group_sizes(labels: jax.Array) -> jax.Array:
    """i32[S] — size of the group each spreader belongs to (``|G(s,t)|``,
    used by the VM power-attribution Eq. 6)."""
    S = labels.shape[0]
    counts = jax.ops.segment_sum(jnp.ones_like(labels), labels, num_segments=S)
    return counts[labels]


def same_group(labels: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    return labels[a] == labels[b]


def coupled_vm_counts(
    labels: jax.Array,    # i32[S] influence labels
    host_cpu: jax.Array,  # i32[V] spreader index of each VM's host CPU
    vm_spreader: jax.Array,  # i32[V] each VM's own spreader index
    vm_host: jax.Array,   # i32[V] hosting PM index
    n_pm: int,
) -> tuple[jax.Array, jax.Array]:
    """Eq. 6 group membership: which VMs sit in their host CPU spreader's
    influence group, and how many such VMs each PM carries.

    The paper defines the VM-power divisor as ``|G(s_vm)| - 1`` — the VM's
    influence group minus the host CPU spreader itself; counting sibling VM
    spreaders of the component directly keeps the engine's hidden consumer
    (complex power model) out of the divisor.  Returns
    ``(in_group bool[V], vms_on_host i32[P])``.
    """
    in_group = same_group(labels, host_cpu, vm_spreader)
    vms_on_host = vms_per_pm(in_group, vm_host, n_pm)
    return in_group, vms_on_host
