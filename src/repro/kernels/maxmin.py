"""Pallas TPU kernels for max-min fair sharing by progressive filling.

The max-min fair-share computation (paper §3.2.3) is DISSECT-CF's hot loop:
every scheduling event re-runs a handful of *segmented reductions* over all
live resource consumptions (committed rate and unfrozen count per spreader).
On a pointer machine these are hash-map walks; the TPU-native form is a
**one-hot matmul**: per row of 128 consumptions, a transposed one-hot tile
``[s, l] = (spreader_id[l] == s)`` (built by broadcasting the id row down
the sublanes, no transpose) turns the segmented sum into one MXU
contraction and the per-flow gather into another.  Accumulators are plain
adds into whole rows — Pallas TPU has no scatter.

* :func:`fill_stats` — one round's per-spreader headroom.  Consumptions
  are padded to (8x128) row blocks, spreaders to 128-lane blocks of one
  (1, S_pad) row; grid = (S/SB, C/CB) with the consumption axis innermost
  and a VMEM accumulator carried across it.
* :func:`maxmin_solve` — the whole progressive-filling loop in one kernel,
  its rate and freeze vectors VMEM-resident across rounds; its footprint
  is checked against the v5e scoped-VMEM limit by :func:`solve_fits`.

Validated against :mod:`repro.kernels.ref` in interpret mode on CPU, and
compiled for v5e by ``tests/test_tpu_compile.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BIG = 3.0e38     # python literal: jnp scalars would be captured consts
ROWS = 8          # sublane rows per consumption block
LANES = 128       # lane width
CB = ROWS * LANES  # consumptions per block
SB = 128          # spreaders per block


def _one_hot_t(ids_row, s_ids):
    """(n_s, LANES) transposed one-hot of a (1, LANES) row of spreader ids
    against the (n_s, LANES) sublane iota ``s_ids``: ``[s, l] = ids[l] ==
    s``.  Built by broadcasting the row down the sublanes — no transpose."""
    return (ids_row == s_ids).astype(jnp.float32)


def _nt(lhs, one_hot_t):
    """(M, LANES) x (n_s, LANES)^T -> (M, n_s): a segmented sum of ``lhs``'s
    rows by the one-hot's spreader ids (exact one-hot, f32 accumulate)."""
    return jax.lax.dot_general(
        lhs, one_hot_t, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _gather(row_s, one_hot_t):
    """(1, n_s) x (n_s, LANES) -> (1, LANES): each lane's spreader value
    (exactly one 1 per one-hot column)."""
    return jnp.dot(row_s, one_hot_t, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _pair(first, second):
    """(2, LANES) from two (1, LANES) rows, via a select on a row iota (no
    scatter, no sublane concatenate)."""
    top = jax.lax.broadcasted_iota(jnp.int32, (2, LANES), 0) == 0
    return jnp.where(top, first, second)


def _headroom(perf, committed, count):
    """Per-spreader increment headroom ``max(perf - committed, 0) / count``,
    ``_BIG`` where no unfrozen flow touches the spreader."""
    avail = jnp.maximum(perf - committed, 0.0)
    return jnp.where(count > 0, avail / jnp.maximum(count, 1.0), _BIG)


def _kernel(prov_ref, cons_ref, rl_ref, uf_ref, perf_ref,
            dp_ref, dc_ref, acc_ref, *, n_cb: int):
    sb = pl.program_id(0)
    cb = pl.program_id(1)

    @pl.when(cb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s_ids = sb * SB + jax.lax.broadcasted_iota(jnp.int32, (SB, LANES), 0)
    acc_p = acc_ref[0:2, :]    # rows: committed rate, unfrozen count
    acc_c = acc_ref[2:4, :]
    # one MXU contraction per sublane row and side: (2,LANES) x (SB,LANES)^T
    for row in range(ROWS):
        sl = slice(row, row + 1)
        lhs = _pair(rl_ref[sl, :], uf_ref[sl, :])
        acc_p = acc_p + _nt(lhs, _one_hot_t(prov_ref[sl, :], s_ids))
        acc_c = acc_c + _nt(lhs, _one_hot_t(cons_ref[sl, :], s_ids))
    acc_ref[0:2, :] = acc_p
    acc_ref[2:4, :] = acc_c

    @pl.when(cb == n_cb - 1)
    def _finalize():
        a = acc_ref[...]
        perf = perf_ref[...]            # (1, SB)
        dp_ref[...] = _headroom(perf, a[0:1, :], a[1:2, :])
        dc_ref[...] = _headroom(perf, a[2:3, :], a[3:4, :])


@functools.partial(jax.jit, static_argnames=("interpret",))
def fill_stats(provider, consumer, r, live, unfrozen, perf, *,
               interpret: bool = False):
    """Drop-in replacement for :func:`repro.kernels.ref.fill_stats_ref`."""
    C = provider.shape[0]
    S = perf.shape[0]
    C_pad = max(-(-C // CB) * CB, CB)
    S_pad = max(-(-S // SB) * SB, SB)

    def pad_c(x, fill):
        return jnp.pad(x, (0, C_pad - C), constant_values=fill)

    # padded flows point at the (padded) spreader S_pad-1 with zero weight
    prov2 = pad_c(provider.astype(jnp.int32), S_pad - 1).reshape(-1, LANES)
    cons2 = pad_c(consumer.astype(jnp.int32), S_pad - 1).reshape(-1, LANES)
    rl2 = pad_c(jnp.where(live, r, 0.0).astype(jnp.float32), 0.0
                ).reshape(-1, LANES)
    uf2 = pad_c(unfrozen.astype(jnp.float32), 0.0).reshape(-1, LANES)
    # spreader rows are one (1, S_pad) row blocked along lanes: a (1, SB)
    # block spans the array's whole (unit) sublane axis, which the TPU
    # tiling accepts at any spreader count
    perf2 = jnp.pad(perf.astype(jnp.float32), (0, S_pad - S)
                    ).reshape(1, S_pad)

    n_sb = S_pad // SB
    n_cb = C_pad // CB
    flow_spec = pl.BlockSpec((ROWS, LANES), lambda sb, cb: (cb, 0))
    sprd_spec = pl.BlockSpec((1, SB), lambda sb, cb: (0, sb))
    dp, dc = pl.pallas_call(
        functools.partial(_kernel, n_cb=n_cb),
        grid=(n_sb, n_cb),
        in_specs=[flow_spec, flow_spec, flow_spec, flow_spec, sprd_spec],
        out_specs=[sprd_spec, sprd_spec],
        out_shape=[jax.ShapeDtypeStruct((1, S_pad), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((8, SB), jnp.float32)],
        interpret=interpret,
    )(prov2, cons2, rl2, uf2, perf2)
    return dp.reshape(-1)[:S], dc.reshape(-1)[:S]


# ---------------------------------------------------------------------------
# Fused full solve: the whole progressive-filling while-loop in one kernel
# ---------------------------------------------------------------------------

# VMEM budget of the resident problem.  The fused solve keeps every flow
# row and the per-spreader stats rows in VMEM, and builds one (S_pad, LANES)
# one-hot tile per side and row.  16 MiB is the scoped-VMEM limit the v5e
# compiler enforces on a kernel by default; the per-byte coefficients are
# fitted to the smallest limit each shape compiles under on v5e (e.g. 3.29
# MiB at 256 flows x 4096 spreaders, 14.55 MiB at 256 x 16384, 1.57 MiB at
# 65536 x 128), rounded up.  Above the budget the engine's round-wise
# fill_stats path takes over.
VMEM_LIMIT_BYTES = 16 << 20
_VMEM_PER_SPREADER = 960
_VMEM_PER_FLOW = 28
_VMEM_FIXED = 64 << 10


def _solve_rows(n_flows: int) -> int:
    """Flow rows of the solve kernel: at least two, so the row loops stay
    loops — a one-trip loop is unrolled by the TPU compiler, which then
    keeps every one-hot tile of the round live at once (4-5x the VMEM)."""
    return max(-(-n_flows // LANES), 2)


def solve_vmem_bytes(n_flows: int, n_spreaders: int) -> int:
    """Upper estimate of the fused solve kernel's scoped VMEM on v5e."""
    s_pad = max(-(-n_spreaders // LANES), 1) * LANES
    return (_VMEM_PER_SPREADER * s_pad
            + _VMEM_PER_FLOW * _solve_rows(n_flows) * LANES + _VMEM_FIXED)


def solve_fits(n_flows: int, n_spreaders: int) -> bool:
    """True when the fused solve kernel's VMEM-resident problem fits."""
    return solve_vmem_bytes(n_flows, n_spreaders) <= VMEM_LIMIT_BYTES


def _solve_kernel(prov_ref, cons_ref, pl_ref, live_ref, perf_ref, r_ref,
                  uf_ref, df_ref, *, c_rows: int, s_pad: int, max_iters: int,
                  rel_eps: float):
    # The rate vector lives in the output ref and the unfrozen mask / per-
    # flow headroom in VMEM scratch across rounds; rows are visited by a
    # loop over dynamic row slices, so the program size does not grow with
    # the flow count.
    perf = perf_ref[...]            # (1, s_pad) f32
    s_ids = jax.lax.broadcasted_iota(jnp.int32, (s_pad, LANES), 0)
    r_ref[...] = jnp.zeros_like(r_ref)
    uf_ref[...] = live_ref[...]     # 0/1: unfrozen flows start as the live

    def stats_row(row, acc):
        acc_p, acc_c = acc
        sl = pl.ds(row, 1)
        rl = jnp.where(live_ref[sl, :] > 0, r_ref[sl, :], 0.0)
        lhs = _pair(rl, uf_ref[sl, :])
        return (acc_p + _nt(lhs, _one_hot_t(prov_ref[sl, :], s_ids)),
                acc_c + _nt(lhs, _one_hot_t(cons_ref[sl, :], s_ids)))

    def headroom_row(row, dpc):
        dp, dc = dpc
        sl = pl.ds(row, 1)
        gp = _gather(dp, _one_hot_t(prov_ref[sl, :], s_ids))
        gc = _gather(dc, _one_hot_t(cons_ref[sl, :], s_ids))
        df = jnp.minimum(jnp.minimum(gp, gc),
                         jnp.maximum(pl_ref[sl, :] - r_ref[sl, :], 0.0))
        df_ref[sl, :] = jnp.where(uf_ref[sl, :] > 0, df, _BIG)
        return dpc

    def round_body(carry):
        i, _ = carry
        zero = jnp.zeros((2, s_pad), jnp.float32)
        # pass 1: segmented stats (committed rate, unfrozen count) per side
        acc_p, acc_c = jax.lax.fori_loop(0, c_rows, stats_row, (zero, zero))
        dp = _headroom(perf, acc_p[0:1], acc_p[1:2])
        dc = _headroom(perf, acc_c[0:1], acc_c[1:2])
        # pass 2: per-flow headroom gather
        jax.lax.fori_loop(0, c_rows, headroom_row, (dp, dc))
        df = df_ref[...]
        delta = jnp.min(df)
        delta = jnp.where(delta < _BIG, delta, 0.0)   # NaN/inf/_BIG -> 0
        unfrozen = uf_ref[...] > 0
        r_ref[...] = jnp.where(unfrozen, r_ref[...] + delta, r_ref[...])
        tight = df <= delta * (1.0 + rel_eps) + 1e-12
        unfrozen = unfrozen & ~tight
        uf_ref[...] = unfrozen.astype(jnp.float32)
        return i + 1, jnp.max(uf_ref[...]) > 0

    def cond(carry):
        i, any_unfrozen = carry
        return (i < max_iters) & any_unfrozen

    jax.lax.while_loop(cond, round_body,
                       (jnp.int32(0), jnp.max(live_ref[...]) > 0))
    r_ref[...] = jnp.where(live_ref[...] > 0, r_ref[...], 0.0)


@functools.partial(jax.jit,
                   static_argnames=("max_iters", "rel_eps", "interpret"))
def maxmin_solve(provider, consumer, p_l, live, perf, *,
                 max_iters: int = 64, rel_eps: float = 1e-5,
                 interpret: bool = False):
    """Max-min fair rates by progressive filling, solved in one kernel.

    Same round recurrence as ``repro.core.fairshare.maxmin_rates``
    without ``flow_caps`` / :func:`repro.kernels.ref.maxmin_solve_ref`,
    but the carried rate and freeze vectors stay VMEM-resident across
    rounds instead of round-tripping through HBM per ``while_loop``
    iteration.  Guard call sites with :func:`solve_fits`; where flows' own
    caps may lie below their spreaders' shares, ``maxmin_fill`` runs the
    round-wise :func:`fill_stats` under its ``flow_caps`` rule instead.
    """
    C = provider.shape[0]
    S = perf.shape[0]
    C_pad = _solve_rows(C) * LANES
    S_pad = max(-(-S // LANES) * LANES, LANES)

    def pad_c(x, fill, dtype):
        return jnp.pad(x.astype(dtype), (0, C_pad - C),
                       constant_values=fill).reshape(-1, LANES)

    prov2 = pad_c(provider, S_pad - 1, jnp.int32)
    cons2 = pad_c(consumer, S_pad - 1, jnp.int32)
    pl2 = pad_c(p_l, 0.0, jnp.float32)
    live2 = pad_c(live, 0.0, jnp.float32)   # padded flows are never live
    perf2 = jnp.pad(perf.astype(jnp.float32),
                    (0, S_pad - S)).reshape(1, S_pad)

    c_rows = C_pad // LANES
    r = pl.pallas_call(
        functools.partial(_solve_kernel, c_rows=c_rows, s_pad=S_pad,
                          max_iters=max_iters, rel_eps=rel_eps),
        out_shape=jax.ShapeDtypeStruct((c_rows, LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((c_rows, LANES), jnp.float32)] * 2,
        interpret=interpret,
    )(prov2, cons2, pl2, live2, perf2)
    return r.reshape(-1)[:C]
