"""Batched scenario-sweep throughput — the perf trajectory of the
static/dynamic config split.

An 8-point ``CloudParams`` sweep (bandwidth x boot-work grid) over one
GWA-like trace on a 20-machine cloud, run as a single ``simulate_batch``
call: one compile, eight hardware-parallel scenario points.  Reported as
simulated events/second of wall time so successive PRs can track whether
sweep throughput regresses (the driver snapshots this as
``BENCH_sweep.json``)."""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from repro.core import compile_cache, engine
from repro.core.trace import filter_fitting, gwa_like_trace

SWEEP_POINTS = 8


def run(quick=True) -> list[dict]:
    n = 400 if quick else 4000
    trace = filter_fitting(gwa_like_trace("das2", n, seed=21), 64.0)
    spec, base = engine.make_cloud(n_pm=20, n_vm=1024, pm_cores=64.0,
                                   max_events=4_000_000)
    points = [
        dataclasses.replace(base,
                            net_bw=float(60.0 + 30.0 * (i % 4)),
                            boot_work=float(5.0 + 10.0 * (i // 4)))
        for i in range(SWEEP_POINTS)
    ]
    params = engine.stack_params(points)

    # First call: trace + compile + run.  With the persistent XLA cache
    # (on from the engine import, DESIGN.md §7) populated, this is
    # already a disk hit; either way it is what a fresh process pays.
    t0 = time.time()
    res = engine.simulate_batch(spec, trace, params)
    jax.block_until_ready(res.t_end)
    compile_wall = time.time() - t0

    t0 = time.time()
    res = engine.simulate_batch(spec, trace, params)
    jax.block_until_ready(res.t_end)
    wall = time.time() - t0

    # Drop the in-memory executable and re-jit: with the persistent cache
    # this measures the warm-process compile wall (deserialisation only);
    # without it, a full recompile — reporting both separates the compile
    # wall from the event-loop throughput trajectory.
    jax.clear_caches()
    t0 = time.time()
    jax.block_until_ready(engine.simulate_batch(spec, trace, params).t_end)
    warm_compile_wall = time.time() - t0 - wall  # subtract one run

    events = int(np.asarray(res.n_events).sum())
    return [{
        "name": "sweep8_batched",
        "points": SWEEP_POINTS,
        "tasks": int(trace.n),
        "compile_wall_s": round(compile_wall, 4),
        "warm_compile_wall_s": round(max(warm_compile_wall, 0.0), 4),
        "xla_cache_dir": compile_cache.active_dir(),
        "wall_s": round(wall, 4),
        "events": events,
        "events_per_s": round(events / wall, 1),
        "tasks_per_s": round(SWEEP_POINTS * int(trace.n) / wall, 1),
        "per_point_events": [int(x) for x in np.asarray(res.n_events)],
        "per_point_energy_mj": [
            round(float(np.asarray(res.energy[i]).sum()) / 1e6, 3)
            for i in range(SWEEP_POINTS)],
    }]
