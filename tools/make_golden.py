"""Regenerate the golden engine fixture (tests/golden/engine_golden.npz).

The fixture pins the engine's *exact* numerical behaviour: every leaf of
the :class:`~repro.core.engine.CloudResult` for a matrix of small
scenarios — sequential, batched (heterogeneous scheduler codes), complex
power model, sampled metering, and an in-loop migration policy.
``tests/test_golden_engine.py`` asserts the live engine reproduces every
array *bitwise* (float leaves compared by bit pattern, integer leaves by
value), which is the regression harness behind the PR 4-6 "optimise
without changing a single bit" protocol (DESIGN.md §7).

Run it ONLY to re-baseline after an *intentional* semantic change, or
after a jax/jaxlib upgrade (XLA may reorder a reduction, which moves the
low bits of a float):

    PYTHONPATH=src JAX_PLATFORMS=cpu python tools/make_golden.py

and say so in the commit message — a diff in this file's output that is
not accompanied by an intended semantics change or a version bump is a
bug.  Bit patterns are only comparable under one jax/jaxlib/backend, so
the fixture records them (:func:`environment`) and the replay refuses a
mismatch with a message that says so.
"""
from __future__ import annotations

import pathlib
import sys

import jax
import numpy as np

from repro.core import engine
from repro.core.trace import synthetic_trace

OUT = pathlib.Path(__file__).resolve().parent.parent / (
    "tests/golden/engine_golden.npz")


def scenarios():
    """(name, fn) pairs; each fn returns a CloudResult."""
    tr = synthetic_trace(16, 4, spread_s=40.0, length_range=(5.0, 60.0),
                         seed=11)

    def seq():
        spec, params = engine.make_cloud(
            n_pm=3, n_vm=12, pm_cores=4.0, vm_sched="firstfit",
            pm_sched="ondemand")
        return spec, engine.simulate(spec, tr, params=params)

    def batched():
        # 6 points: every PM policy code (incl. defrag/evacuate) and every
        # VM policy code appears at least once — the full lax.switch matrix
        spec, base = engine.make_cloud(n_pm=3, n_vm=12, pm_cores=4.0)
        import dataclasses
        pts = [dataclasses.replace(base, net_bw=float(80.0 + 20.0 * i),
                                   vm_sched=i % len(engine.VM_SCHEDULERS),
                                   pm_sched=i % len(engine.PM_SCHEDULERS))
               for i in range(6)]
        return spec, engine.simulate_batch(spec, tr,
                                           engine.stack_params(pts))

    def complex_power():
        spec, params = engine.make_cloud(
            n_pm=3, n_vm=12, pm_cores=4.0, complex_power=True,
            pm_sched="ondemand")
        return spec, engine.simulate(spec, tr, params=params)

    def sampled():
        spec, params = engine.make_cloud(
            n_pm=3, n_vm=12, pm_cores=4.0, metering_period=0.25,
            pm_sched="alwayson")
        return spec, engine.simulate(spec, tr, params=params)

    def migration_policy():
        spec, params = engine.make_cloud(
            n_pm=4, n_vm=12, pm_cores=4.0, pm_sched="consolidate",
            consolidate_idle_frac=0.3)
        return spec, engine.simulate(spec, tr, params=params)

    def equal_share():
        spec, params = engine.make_cloud(
            n_pm=3, n_vm=12, pm_cores=4.0, scheduler="equal",
            pm_sched="ondemand")
        return spec, engine.simulate(spec, tr, params=params)

    def t_stop_partial():
        spec, params = engine.make_cloud(
            n_pm=3, n_vm=12, pm_cores=4.0, pm_sched="ondemand")
        return spec, engine.simulate(spec, tr, params=params, t_stop=30.0)

    def streaming_windows():
        # windowed replay (DESIGN.md §8): StreamResult leaves pinned over
        # a 4-way chunk of the (time-sorted) scenario trace
        from repro.core.trace import chunk_trace
        order = np.argsort(np.asarray(tr.arrival), kind="stable")
        tr_sorted = engine.Trace(
            arrival=tr.arrival[order], cores=tr.cores[order],
            work=tr.work[order])
        spec, params = engine.make_cloud(
            n_pm=3, n_vm=12, pm_cores=4.0, vm_sched="smallestfirst",
            pm_sched="ondemand", metering_period=0.25)
        wt = chunk_trace(tr_sorted, -(-tr_sorted.n // 4))
        return spec, engine.simulate_stream(spec, wt, params=params)

    # Active-set compaction scenarios (DESIGN.md §7): explicit buckets at
    # two distinct sizes plus a compacted streaming replay.  The spread-out
    # trace keeps the live set inside the bucket, so these goldens pin the
    # *compacted* code path (gather, bucketed solve, scatter-back), not the
    # overflow replay.  Their bits must equal the dense engine's by
    # construction — the point of pinning them is catching a compacted
    # kernel regressing on its own.
    tr_sparse = synthetic_trace(20, 4, spread_s=250.0,
                                length_range=(5.0, 40.0), seed=23)

    def compact8():
        spec, params = engine.make_cloud(
            n_pm=3, n_vm=12, pm_cores=4.0, vm_sched="firstfit",
            pm_sched="ondemand", compact=8)
        return spec, engine.simulate(spec, tr_sparse, params=params)

    def compact16():
        spec, params = engine.make_cloud(
            n_pm=3, n_vm=24, pm_cores=4.0, vm_sched="smallestfirst",
            pm_sched="ondemand", compact=16)
        return spec, engine.simulate(spec, tr_sparse, params=params)

    def streaming_compact():
        from repro.core.trace import chunk_trace
        spec, params = engine.make_cloud(
            n_pm=3, n_vm=12, pm_cores=4.0, vm_sched="firstfit",
            pm_sched="ondemand", metering_period=0.25, compact=8)
        wt = chunk_trace(tr_sparse, -(-tr_sparse.n // 4))
        return spec, engine.simulate_stream(spec, wt, params=params)

    return [("seq", seq), ("batched", batched),
            ("complex_power", complex_power), ("sampled", sampled),
            ("migration_policy", migration_policy),
            ("equal_share", equal_share),
            ("t_stop_partial", t_stop_partial),
            ("streaming_windows", streaming_windows),
            ("compact8", compact8), ("compact16", compact16),
            ("streaming_compact", streaming_compact)]


def flatten_result(name: str, res) -> dict[str, np.ndarray]:
    """Every answer leaf of ``res`` by its path.  The loop's work counts
    (``res.counters``) are not answers and are left out."""
    flat = {}
    leaves = jax.tree_util.tree_flatten_with_path(res)[0]
    for path, leaf in leaves:
        if getattr(path[0], "name", None) == "counters":
            continue
        key = name + jax.tree_util.keystr(path)
        flat[key] = np.asarray(leaf)
    return flat


def environment() -> dict[str, str]:
    """The versions and backend the fixture's bits belong to."""
    import jaxlib
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "backend": jax.default_backend()}


ENV_PREFIX = "_env."


def main() -> int:
    arrays = {ENV_PREFIX + k: np.asarray(v)
              for k, v in environment().items()}
    for name, fn in scenarios():
        _spec, res = fn()
        jax.block_until_ready(res.t_end)
        arrays.update(flatten_result(name, res))
    OUT.parent.mkdir(parents=True, exist_ok=True)
    np.savez_compressed(OUT, **arrays)
    print(f"wrote {OUT} ({OUT.stat().st_size} bytes, "
          f"{len(arrays)} arrays)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
