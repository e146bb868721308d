"""Bucketed active-set compaction (DESIGN.md §7): per-event cost that
scales with *live* work, not provisioned cloud size.

A cloud provisioned for ``V`` VMs carries ``F = V + P`` flow slots and
``S = 4P + V + 2`` spreaders, but at any instant only the flows of
currently-running VMs (plus at most ``P`` hidden consumers) are active —
for realistic traces a few dozen out of a thousand.  The dense pipeline
still paid O(F + S) vector work per event in the fair-share solve, the
influence propagation, the provider reductions and the horizon scan.

This module gathers the active flows (``f_active``) and the spreaders
they reference into fixed power-of-two buckets:

* ``fidx``  — the bucket's dense flow indices (ascending, so every
  compacted reduction adds the *same terms in the same order* as its
  dense counterpart — the bit-identity argument in DESIGN.md §7);
* ``sidx`` / ``smap`` — the referenced-spreader bucket and its inverse
  map (``smap[s] == SB`` marks an untouched spreader).

The bucket size is a **spec-static watermark** (:func:`compact_bucket`),
so it is part of the jit compile key exactly like the Pallas
``maxmin_solve_fits`` size gate: one compiled program per (spec, bucket).
No sound static bound on the active-flow count exists (it depends on
traced core demands), so compaction is *checked*, never trusted: every
iteration folds ``count <= bucket`` into the loop-carried ``ok`` flag and
the host entry points rerun the scenario with ``compact=0`` when it ever
trips (:func:`repro.core.engine.simulate` and friends) — results are
bit-identical either way, overflow only costs a recompile.

Dropped lanes are exact no-ops in every compacted reduction: a non-live
flow contributes ``+0.0`` to a ``segment_sum`` (and rates are
non-negative, so no ``-0.0`` can flip a sign bit under ``x + 0.0``), a
masked horizon lane contributes the ``BIG`` filler either way, and an
untouched spreader keeps its singleton influence label.  See
``tests/test_compact.py`` for the replay proofs.

**Tiers.**  Under the auto rule a watermark bucket of at least
``4 * SMALL_FLOWS`` gets a second, small tier (:func:`compact_tiers`):
``SMALL_FLOWS`` flows and twice as many spreaders.  Each flow references
two spreaders, so the small tier holds every active set of at most
``SMALL_FLOWS`` flows.  The loop driver picks the tier on every pass from
the active-flow count (:mod:`repro.core.loop.driver`).  Any bucket at
least as large as the active set gives the same bits, so the choice only
moves the cost.  The small tier is built without a dense scatter
(:func:`build_small`).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

_INT_BIG = jnp.int32(2**30)


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    return 1 << max(int(n) - 1, 0).bit_length()


def compact_bucket(spec) -> int:
    """The spec-static flow-bucket watermark: 0 disables compaction.

    ``spec.compact`` semantics: ``-1`` auto, ``0`` off, ``> 0`` an explicit
    bucket size (rounded up to a power of two).  The auto rule sizes the
    bucket to ``next_pow2(4 * n_pm + 32)`` — room for a few concurrent VM
    flows per physical machine plus every hidden consumer — and only
    enables compaction when the bucket is at most half the dense flow
    count, i.e. when the gather/scatter detour can actually pay for
    itself.  The spreader bucket is the same size (checked at runtime
    like the flow bucket; both counts fold into ``Compact.ok``).
    """
    F = spec.n_vm + spec.n_pm
    if spec.compact == 0:
        return 0
    if spec.compact > 0:
        fb = next_pow2(spec.compact)
        return fb if fb < F else 0
    fb = next_pow2(4 * spec.n_pm + 32)
    return fb if 2 * fb <= F else 0


# The small tier: flows and spreaders.  Every gather and scatter of the
# compacted stages walks its bucket element by element on the TPU, so a
# pass whose active set fits here costs a fraction of the watermark's.
SMALL_FLOWS = 64
SMALL_TIER = (SMALL_FLOWS, 2 * SMALL_FLOWS)


def compact_tiers(spec) -> tuple[tuple[int, int], ...]:
    """The ``(flow bucket, spreader bucket)`` tiers the compacted stages
    may run on, smallest first; ``()`` runs them dense.

    The auto rule (``spec.compact == -1``) adds :data:`SMALL_TIER` below a
    watermark of at least ``4 * SMALL_FLOWS``; an explicit bucket pins one
    tier.
    """
    fb = compact_bucket(spec)
    if fb == 0:
        return ()
    if spec.compact == -1 and fb >= 4 * SMALL_FLOWS:
        return (SMALL_TIER, (fb, fb))
    return ((fb, fb),)


def dense_reachable(spec, n_tasks: int) -> bool:
    """Whether a pass under the auto rule can hold more active flows than
    the largest tier: one flow per VM slot that holds a task, at most
    ``min(n_vm, n_tasks)``, and one hidden consumer per PM.  Only then does
    the driver build its in-program dense branch (a spec-static fact, so a
    cell that cannot reach it compiles no such branch)."""
    tiers = compact_tiers(spec)
    return (spec.compact == -1 and bool(tiers)
            and min(spec.n_vm, n_tasks) + spec.n_pm > tiers[-1][0])


class Compact(NamedTuple):
    """One iteration's active-set gather (built by the ``advance`` stage,
    threaded to ``observe`` through ``StageCtx.compact``)."""

    fidx: jax.Array    # i32[FB] bucket -> dense flow index (F = fill)
    fvalid: jax.Array  # bool[FB] lane holds a real active flow
    sidx: jax.Array    # i32[SB] bucket -> dense spreader index (S = fill)
    smap: jax.Array    # i32[S] dense spreader -> bucket slot (SB = none)
    bprov: jax.Array   # i32[FB] provider bucket slots (SB on fill lanes)
    bcons: jax.Array   # i32[FB] consumer bucket slots (SB on fill lanes)
    ok: jax.Array      # bool — both buckets held every active entry


def build_compact(spec, st, fb: int, sb: int) -> Compact:
    """Gather the active flows and their referenced spreaders into buckets
    of ``fb`` flows and ``sb`` spreaders.  ``jnp.nonzero(size=...)``
    returns indices in ascending order, so compacted segment sums reduce
    the surviving terms in exactly the dense index order (bit-identity,
    DESIGN.md §7)."""
    F = spec.n_vm + spec.n_pm
    S = spec.layout.S

    bm = st.f_active
    fidx = jnp.nonzero(bm, size=fb, fill_value=F)[0].astype(jnp.int32)
    fvalid, prov_d, cons_d = _endpoints(st, fidx, F, S)

    mark = jnp.zeros((S,), bool)
    mark = mark.at[prov_d].set(True, mode="drop")
    mark = mark.at[cons_d].set(True, mode="drop")
    sidx = jnp.nonzero(mark, size=sb, fill_value=S)[0].astype(jnp.int32)
    ok = (jnp.sum(bm) <= fb) & (jnp.sum(mark) <= sb)
    return _assemble(fidx, fvalid, sidx, prov_d, cons_d, S, ok)


def build_small(spec, st, fb: int, sb: int) -> Compact:
    """:func:`build_compact` with no scatter over the dense flow or
    spreader axis, for ``sb >= 2 * fb``.

    ``jnp.nonzero`` counts its mask with a scatter of every entry; here
    ``fidx[j]`` is the number of flows whose running active count is at
    most ``j`` — the index of the ``j``-th active flow, ``F`` past the
    last — and the spreaders are the ``2 * fb`` endpoint ids sorted and
    de-duplicated.  Both give ``build_compact``'s ascending indices and
    fill, bit for bit.
    """
    assert sb >= 2 * fb, (fb, sb)
    F = spec.n_vm + spec.n_pm
    S = spec.layout.S

    bm = st.f_active
    rank = jnp.cumsum(bm.astype(jnp.int32))
    j = jnp.arange(fb, dtype=jnp.int32)
    fidx = jnp.sum(rank[None, :] <= j[:, None], axis=1, dtype=jnp.int32)
    fvalid, prov_d, cons_d = _endpoints(st, fidx, F, S)

    ends = jnp.sort(jnp.concatenate([prov_d, cons_d]))
    dup = jnp.concatenate([jnp.zeros((1,), bool), ends[1:] == ends[:-1]])
    sidx = jnp.sort(jnp.where(dup, S, ends))   # S (the fill) sorts last
    sidx = jnp.pad(sidx, (0, sb - 2 * fb), constant_values=S)
    # 2 * fb endpoints always fit sb spreader slots
    ok = jnp.sum(bm) <= fb
    return _assemble(fidx, fvalid, sidx, prov_d, cons_d, S, ok)


def build_tier(spec, st, tier: tuple[int, int]) -> Compact:
    """The gather of one of :func:`compact_tiers`: the small tier without
    a dense scatter, the watermark by ``jnp.nonzero``."""
    build = build_small if tier == SMALL_TIER else build_compact
    return build(spec, st, *tier)


def _endpoints(st, fidx, F: int, S: int):
    """(fvalid, provider ids, consumer ids) of a flow bucket; fill lanes
    point at spreader ``S``."""
    fvalid = fidx < F
    fidx_c = jnp.minimum(fidx, F - 1)
    prov_d = jnp.where(fvalid, st.f_prov[fidx_c], S)
    cons_d = jnp.where(fvalid, st.f_cons[fidx_c], S)
    return fvalid, prov_d, cons_d


def _assemble(fidx, fvalid, sidx, prov_d, cons_d, S: int, ok) -> Compact:
    """The bucket-slot maps of a gathered flow and spreader bucket."""
    sb = sidx.shape[0]
    smap = jnp.full((S,), sb, jnp.int32).at[sidx].set(
        jnp.arange(sb, dtype=jnp.int32), mode="drop")
    bprov = jnp.where(fvalid, jnp.take(smap, prov_d, mode="clip"), sb)
    bcons = jnp.where(fvalid, jnp.take(smap, cons_d, mode="clip"), sb)
    return Compact(fidx=fidx, fvalid=fvalid, sidx=sidx, smap=smap,
                   bprov=bprov, bcons=bcons, ok=ok)


def gather_flows(cp: Compact, arr: jax.Array, fill) -> jax.Array:
    """``arr[fidx]`` with the bucket's fill lanes forced to ``fill``."""
    F = arr.shape[0]
    out = arr[jnp.minimum(cp.fidx, F - 1)]
    return jnp.where(cp.fvalid, out, jnp.asarray(fill, out.dtype))


def scatter_flows(cp: Compact, n_flows: int, vals: jax.Array,
                  fill=0.0) -> jax.Array:
    """Dense flow vector holding ``vals`` at the bucket's indices and
    ``fill`` everywhere else (fill lanes drop)."""
    base = jnp.full((n_flows,), jnp.asarray(fill, vals.dtype))
    return base.at[cp.fidx].set(vals, mode="drop")


def influence_labels_compact(cp: Compact,
                             live_b: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Influence labels over the *compacted* spreader bucket, and the
    propagation rounds it ran.

    Labels are **dense** spreader indices (slot ``j`` starts at
    ``sidx[j]``), so the fixpoint equals the dense
    :func:`repro.core.influence.influence_labels` restricted to the
    marked set: every live edge has both endpoints marked, hence dense
    propagation never moves a label across an unmarked spreader, and an
    unmarked spreader keeps its singleton self-label (realised by
    :func:`label_lookup`).  The round count matches the dense loop too —
    the per-round change set is identical, and both loops exit on the
    first unchanged round.
    """
    SB = cp.sidx.shape[0]
    S = cp.smap.shape[0]
    label0 = jnp.where(cp.sidx < S, cp.sidx, _INT_BIG)
    bprov = jnp.where(live_b, cp.bprov, SB)
    bcons = jnp.where(live_b, cp.bcons, SB)
    ends = jnp.concatenate([bprov, bcons])

    def body(state):
        i, label, _changed = state
        edge = jnp.minimum(jnp.take(label, bprov, mode="clip"),
                           jnp.take(label, bcons, mode="clip"))
        edge = jnp.where(live_b, edge, _INT_BIG)
        new = label.at[ends].min(jnp.concatenate([edge, edge]), mode="drop")
        return i + 1, new, (new != label).any()

    def cond(state):
        i, _label, changed = state
        return jnp.logical_and(changed, i < SB)

    rounds, label, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), label0, jnp.bool_(True)))
    return label, rounds


def label_lookup(cp: Compact, labels_b: jax.Array,
                 dense_idx: jax.Array) -> jax.Array:
    """The dense influence label of arbitrary spreader indices: the
    propagated bucket label when marked, the singleton self-label when
    not — exactly the dense fixpoint (see above)."""
    slot = jnp.take(cp.smap, dense_idx, mode="clip")
    SB = cp.sidx.shape[0]
    return jnp.where(slot < SB,
                     jnp.take(labels_b, jnp.minimum(slot, SB - 1),
                              mode="clip"),
                     dense_idx)
