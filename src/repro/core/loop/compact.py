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


def build_compact(spec, st) -> Compact:
    """Gather the active flows and their referenced spreaders into the
    spec-static buckets.  ``jnp.nonzero(size=...)`` returns indices in
    ascending order, so compacted segment sums reduce the surviving terms
    in exactly the dense index order (bit-identity, DESIGN.md §7)."""
    FB = compact_bucket(spec)
    SB = FB
    lay = spec.layout
    F = spec.n_vm + spec.n_pm
    S = lay.S

    bm = st.f_active
    fidx = jnp.nonzero(bm, size=FB, fill_value=F)[0].astype(jnp.int32)
    fvalid = fidx < F
    fidx_c = jnp.minimum(fidx, F - 1)
    prov_d = jnp.where(fvalid, st.f_prov[fidx_c], S)
    cons_d = jnp.where(fvalid, st.f_cons[fidx_c], S)

    mark = jnp.zeros((S,), bool)
    mark = mark.at[prov_d].set(True, mode="drop")
    mark = mark.at[cons_d].set(True, mode="drop")
    sidx = jnp.nonzero(mark, size=SB, fill_value=S)[0].astype(jnp.int32)
    smap = jnp.full((S,), SB, jnp.int32).at[sidx].set(
        jnp.arange(SB, dtype=jnp.int32), mode="drop")

    bprov = jnp.where(fvalid, jnp.take(smap, prov_d, mode="clip"), SB)
    bcons = jnp.where(fvalid, jnp.take(smap, cons_d, mode="clip"), SB)
    ok = (jnp.sum(bm) <= FB) & (jnp.sum(mark) <= SB)
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
