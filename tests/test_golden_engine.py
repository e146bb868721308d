"""Golden bitwise-equality suite for the optimised engine (DESIGN.md §7).

``tests/golden/engine_golden.npz`` holds every ``CloudResult`` leaf of the
scenario matrix in ``tools/make_golden.py`` (sequential, batched over the
full policy-code matrix, complex power, sampled metering, in-loop
migration, equal-share sharing, ``t_stop`` partial run), captured at the
pre-optimisation engine.  This suite replays the matrix on the live
engine and asserts *bit* equality:

* float leaves must match bit-for-bit (compared through their integer bit
  pattern — ``allclose`` would hide drift that compounds over thousands
  of loop iterations);
* integer/bool leaves must match by value (the storage dtype is allowed
  to narrow — PR 6 moved ``pstate``/``vstage``/``task_state``/``f_kind``
  to int8 — but never the values).

This is the regression harness behind the perf work: buffer donation, the
fused horizon reduction, the batched fill-stats reduction and the
narrowed state dtypes all landed with this suite green.  Re-baseline only
for intentional semantic changes: ``PYTHONPATH=src python
tools/make_golden.py``.
"""
from __future__ import annotations

import importlib.util
import pathlib

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
FIXTURE = ROOT / "tests/golden/engine_golden.npz"

_spec = importlib.util.spec_from_file_location(
    "make_golden", ROOT / "tools/make_golden.py")
make_golden = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(make_golden)


def _bits(a: np.ndarray) -> np.ndarray:
    """Float array -> integer bit pattern of identical width."""
    return a.view({2: np.uint16, 4: np.uint32, 8: np.uint64}[a.itemsize])


@pytest.fixture(scope="module")
def golden():
    assert FIXTURE.exists(), (
        f"{FIXTURE} missing — generate with tools/make_golden.py")
    with np.load(FIXTURE) as z:
        arrays = {k: z[k] for k in z.files}
    pre = make_golden.ENV_PREFIX
    recorded = {k[len(pre):]: str(v) for k, v in arrays.items()
                if k.startswith(pre)}
    live = make_golden.environment()
    assert recorded == live, (
        f"golden fixture was recorded under {recorded or 'unknown versions'}"
        f" but this run uses {live}: float bit patterns are only comparable "
        f"within one jax/jaxlib/backend — re-baseline with "
        f"tools/make_golden.py after checking the drift is numerics only")
    return {k: v for k, v in arrays.items() if not k.startswith(pre)}


@pytest.mark.parametrize("name,fn", make_golden.scenarios())
def test_engine_matches_golden_bitwise(name, fn, golden):
    _spec_, res = fn()
    jax.block_until_ready(res.t_end)
    live = make_golden.flatten_result(name, res)
    want_keys = {k for k in golden if k.startswith(name + ".")
                 or k.startswith(name + "[")}
    assert want_keys == set(live), (
        f"{name}: leaf set changed: only-golden="
        f"{sorted(want_keys - set(live))[:5]} "
        f"only-live={sorted(set(live) - want_keys)[:5]}")
    mismatches = []
    for key in sorted(want_keys):
        want, got = golden[key], live[key]
        assert want.shape == got.shape, f"{key}: shape {got.shape} != {want.shape}"
        if np.issubdtype(want.dtype, np.floating):
            assert got.dtype == want.dtype, (
                f"{key}: float dtype {got.dtype} != {want.dtype}")
            if not (_bits(want) == _bits(got)).all():
                mismatches.append(key)
        else:
            # integer/bool: storage width may narrow, values may not
            if not (want.astype(np.int64) == got.astype(np.int64)).all():
                mismatches.append(key)
    assert not mismatches, f"{name}: bitwise mismatches in {mismatches}"
