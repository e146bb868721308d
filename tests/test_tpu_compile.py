"""Compile rehearsals for a TPU v5e, without the chip.

The TPU compiler is installed even where no TPU is attached: it compiles
for a *described* v5e chip (``jax.experimental.topologies``) and refuses
what the chip's compiler would refuse — a scatter it cannot emit, a Pallas
primitive Mosaic cannot lower, a block not aligned to the (8, 128) tiling,
a kernel over its scoped VMEM.  Interpret-mode kernel tests and CPU engine
tests see none of that.  Nothing runs here, so nothing about results or
times is checked.

The topology is described inside a module fixture (never at import), so
each test worker collects the same tests and only the one that runs this
file loads the TPU library.  The persistent compilation cache is off
around these compiles: an entry compiled for a described chip cannot be
read back without one.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine
from repro.core.trace import synthetic_trace
from repro.kernels import maxmin
from repro.kernels.horizon import masked_min


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this jax installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(x, sharding):
    x = jnp.asarray(x)
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _vec(n, dtype, sharding):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


# Kernel shapes the engine hands over: compacted horizon / fair-share
# buckets (loop/compact.py: next_pow2(4P+32) flows and spreaders) and the
# dense layouts (F = V+P flows, S = 4P+V+2 spreaders), for 5 x 256,
# 100 x 1024 and 500 x 4096 clouds.  S = 300 is not a multiple of 128.

@pytest.mark.parametrize("n", [300, 4600, 9696])
def test_masked_min_compiles_for_v5e(one_chip, n):
    f32 = _vec(n, jnp.float32, one_chip)
    mask = _vec(n, jnp.bool_, one_chip)
    assert "tpu_custom_call" in _compile(masked_min, f32, mask).as_text()


FLOW_SHAPES = [(64, 64), (261, 279), (300, 300), (512, 512), (1124, 1426),
               (2048, 2048)]


@pytest.mark.parametrize("C,S", FLOW_SHAPES)
def test_fill_stats_compiles_for_v5e(one_chip, C, S):
    i32, f32, b = jnp.int32, jnp.float32, jnp.bool_
    args = [_vec(C, i32, one_chip), _vec(C, i32, one_chip),
            _vec(C, f32, one_chip), _vec(C, b, one_chip),
            _vec(C, b, one_chip), _vec(S, f32, one_chip)]
    assert "tpu_custom_call" in _compile(maxmin.fill_stats, *args).as_text()


def _solve_args(C, S, sharding):
    i32, f32 = jnp.int32, jnp.float32
    return [_vec(C, i32, sharding), _vec(C, i32, sharding),
            _vec(C, f32, sharding), _vec(C, jnp.bool_, sharding),
            _vec(S, f32, sharding)]


@pytest.mark.parametrize("C,S", FLOW_SHAPES + [(4596, 6098)])
def test_maxmin_solve_compiles_for_v5e(one_chip, C, S):
    assert maxmin.solve_fits(C, S)
    compiled = _compile(maxmin.maxmin_solve, *_solve_args(C, S, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_maxmin_solve_vmem_budget_matches_compiler(one_chip):
    """``solve_fits`` admits the widest spreader row it can and the v5e
    compiler accepts it; a shape past the real scoped-VMEM limit is
    refused by both."""
    C = 256
    S = max(s for s in range(128, 32768, 128) if maxmin.solve_fits(C, s))
    _compile(maxmin.maxmin_solve, *_solve_args(C, S, one_chip))
    over = 24576
    assert not maxmin.solve_fits(C, over)
    with pytest.raises(Exception, match="vmem"):
        _compile(maxmin.maxmin_solve, *_solve_args(C, over, one_chip))


def _cloud_args(params, trace, sharding):
    return (jax.tree.map(lambda x: _shape(x, sharding), trace),
            jax.tree.map(lambda x: _shape(x, sharding), params),
            _shape(jnp.float32(jnp.inf), sharding))


@pytest.mark.parametrize("compact", [-1, 0])
def test_simulate_compiles_for_v5e_ondemand(one_chip, compact):
    """The on-demand policy's per-PM count once aborted the TPU compiler
    (a scatter whose indices and updates were one constant buffer in the
    pre-loop management pass); the whole engine must compile."""
    spec, params = engine.make_cloud(n_pm=5, n_vm=256, pm_sched="ondemand",
                                     compact=compact)
    trace = synthetic_trace(64, 4, seed=0)
    tr, pp, t_stop = _cloud_args(params, trace, one_chip)
    engine._simulate_jit.lower(spec, tr, pp, None, t_stop).compile()


def test_simulate_batch_compiles_for_v5e(one_chip):
    spec, base = engine.make_cloud(n_pm=5, n_vm=256)
    points = [dataclasses.replace(base, vm_sched=v, pm_sched=p)
              for v in ("firstfit", "nonqueuing")
              for p in ("alwayson", "ondemand", "consolidate")]
    params = engine.stack_params(points)
    trace = synthetic_trace(64, 4, seed=0)
    tr, pp, t_stop = _cloud_args(params, trace, one_chip)
    engine._simulate_batch_jit.lower(spec, tr, pp, t_stop).compile()


def test_tiered_batch_compiles_for_v5e(one_chip):
    """Two compaction tiers under vmap: the tier ``lax.cond`` on the
    lanes' ``pmax`` must compile, and stay a conditional."""
    from repro.core.loop import compact as cpk
    spec, base = engine.make_cloud(n_pm=25, n_vm=512)
    assert len(cpk.compact_tiers(spec)) == 2
    params = engine.stack_params([base, base])
    trace = synthetic_trace(64, 4, seed=0)
    tr, pp, t_stop = _cloud_args(params, trace, one_chip)
    hlo = engine._simulate_batch_jit.lower(spec, tr, pp, t_stop).compile()
    assert "conditional(" in hlo.as_text()


def test_stream_step_compiles_for_v5e(one_chip):
    spec, params = engine.make_cloud(n_pm=5, n_vm=256, pm_sched="ondemand")
    W = 128
    carry = engine.init_stream(spec, engine.default_n_slots(spec, W), params)
    window = synthetic_trace(W, 4, seed=0)._replace(
        gid=jnp.arange(W, dtype=jnp.int32))

    def on(tree):
        return jax.tree.map(lambda x: _shape(x, one_chip), tree)

    t = _shape(jnp.float32(0.0), one_chip)
    engine._stream_step.lower(spec, on(carry), on(window), on(params),
                              t, t, t).compile()


def test_region_program_compiles_for_v5e(one_chip):
    """Memory, utilisation caps and the in-program dense branch (a pass
    whose active set outgrows the watermark runs dense): the whole engine
    must compile, with the tier choice a conditional."""
    from repro.core.loop import compact as cpk
    spec, params = engine.make_cloud(n_pm=8, n_vm=256, pm_mem=256.0)
    trace = synthetic_trace(140, 4, seed=0)
    trace = trace._replace(mem=trace.cores * 4.0,
                           util=jnp.full_like(trace.cores, 0.2))
    assert cpk.dense_reachable(spec, trace.n)
    tr, pp, t_stop = _cloud_args(params, trace, one_chip)
    hlo = engine._simulate_jit.lower(spec, tr, pp, None, t_stop).compile()
    assert "conditional(" in hlo.as_text()
