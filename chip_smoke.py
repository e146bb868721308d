#!/usr/bin/env python3
"""Smoke run of the cloud engine on one TPU chip.

Drives the simulator's main entry points once at a cluster and trace size
users of a cloud simulator run, and checks what comes out:

  (a) device check: the first JAX device must be a TPU;
  (b) oracle check: ``engine.simulate`` against the sequential reference
      DES (``repro.baseline.PyDESCloud``) on a small cluster;
  (c) sequential run: 500 PMs x 64 cores, 4096 VM slots, a 10 000-task
      GWA-like DAS-2 trace (the largest machine count of the paper's
      Fig. 15 infrastructure-scaling run);
  (d) batched tournament: the 3 x 5 VM x PM registry grid at 100 PMs x
      1024 VM slots with 2000 tasks through ``engine.simulate_batch``;
  (e) streaming replay: ``engine.simulate_stream`` of a 100 000-task
      windowed trace through exactly one compiled window step;
  (f) Pallas kernels compiled for the chip against ``repro.kernels.ref``
      at the engine's shapes.

    python3 chip_smoke.py               # phases (a)-(f), one chip
    python3 chip_smoke.py --four-chips  # only the (d) grid, sharded over
                                        # four chips vs one chip

Each phase prints one JSON line; any failed check raises, so the script
exits non-zero.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Wall times here are one smoke run's, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time
import warnings

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.baseline import PyDESCloud  # noqa: E402
from repro.core import engine  # noqa: E402
from repro.core.trace import filter_fitting, gwa_like_trace  # noqa: E402
from repro.data.pipeline import gwa_window_stream  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.horizon import masked_min  # noqa: E402
from repro.kernels.maxmin import fill_stats, maxmin_solve  # noqa: E402
from repro.sched import registry  # noqa: E402

PM_CORES = 64.0
MAX_EVENTS = 4_000_000


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond, what: str) -> None:
    if not bool(cond):
        raise AssertionError(what)


class CompileClock:
    """Seconds XLA spends compiling while active (``jax.monitoring``'s
    backend-compile events; a persistent-cache hit compiles nothing)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **_):
        if self.on and event == self.EVENT:
            self.seconds += duration

    def __enter__(self):
        self.seconds, self.on = 0.0, True
        return self

    def __exit__(self, *exc):
        self.on = False


def timed(clock: CompileClock, fn, *args, **kw):
    """``(result, wall_s, compile_s, dense_replay)`` of one entry-point call,
    its wall taken around ``block_until_ready``."""
    with warnings.catch_warnings(record=True) as caught, clock:
        warnings.simplefilter("always")
        t0 = time.perf_counter()
        res = fn(*args, **kw)
        jax.block_until_ready(res)
        wall = time.perf_counter() - t0
    replay = any("compaction bucket" in str(w.message) for w in caught)
    return res, wall, clock.seconds, replay


def check_lane(res, readings, n_tasks: int, where: str) -> dict:
    """The invariants every finished scenario must hold."""
    done = np.isfinite(np.asarray(res.completion))
    rejected = np.asarray(res.rejected)
    check((done | rejected).all(),
          f"{where}: {int((~(done | rejected)).sum())} of {n_tasks} tasks "
          f"neither done nor rejected")
    check(not bool(res.overflow), f"{where}: capacity overflow")
    events = int(res.n_events)
    check(events < MAX_EVENTS, f"{where}: hit max_events ({events})")
    total = float(readings["iaas_total"])
    vm_sum = float(np.sum(np.asarray(readings["vm"], np.float64)))
    unattributed = float(readings["vm_unattributed"])
    pm_sum = float(np.sum(np.asarray(readings["pm"], np.float64)))
    check(np.isfinite(total) and total > 0, f"{where}: iaas_total {total}")
    check(np.isclose(total, vm_sum + unattributed, rtol=1e-4),
          f"{where}: iaas_total {total} != vm {vm_sum} + unattributed "
          f"{unattributed}")
    check(np.isclose(total, pm_sum, rtol=1e-4),
          f"{where}: iaas_total {total} != sum of PM meters {pm_sum}")
    return {"events": events, "done": int(done.sum()),
            "rejected": int(rejected.sum()), "iaas_total_j": total}


def phase_device(n_chips: int) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    check(d0.platform == "tpu",
          f"no TPU: jax.devices()[0] is {d0.platform} ({d0.device_kind})")
    check(len(devs) >= n_chips, f"need {n_chips} chips, found {len(devs)}")
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}
    report("a_device", **info)
    return info


def phase_oracle() -> None:
    """tests/test_engine.py's oracle scenario, on the chip."""
    spec, params = engine.make_cloud(
        n_pm=2, n_vm=16, pm_cores=4.0, net_bw=100.0, repo_bw=200.0,
        image_mb=100.0, boot_work=4.0, latency_s=0.0)
    rng = np.random.RandomState(3)
    n = 24
    arrival = np.sort(rng.uniform(0, 30, n)).astype(np.float32)
    cores = rng.choice([1.0, 2.0, 4.0], n, p=[0.6, 0.3, 0.1]
                       ).astype(np.float32)
    runtime = rng.uniform(5, 40, n).astype(np.float32)
    trace = engine.Trace(arrival=jnp.asarray(arrival),
                         cores=jnp.asarray(cores),
                         work=jnp.asarray(runtime * cores))
    res = engine.simulate(spec, trace, params=params)
    oracle = PyDESCloud(n_pm=2, pm_cores=4.0, net_bw=100.0, repo_bw=200.0,
                        image_mb=100.0, boot_work=4.0).run(
        arrival, cores, runtime * cores)
    got = np.asarray(res.completion)
    check(np.isfinite(got).all(), "oracle scenario: unfinished tasks")
    np.testing.assert_allclose(got, oracle["completion"], rtol=2e-3)
    energy = float(np.sum(np.asarray(res.energy, np.float64)))
    np.testing.assert_allclose(energy, oracle["energy"], rtol=2e-3)
    report("b_oracle", tasks=n,
           max_rel_err_completion=float(np.max(
               np.abs(got - oracle["completion"]) / oracle["completion"])),
           rel_err_energy=abs(energy - oracle["energy"]) / oracle["energy"])


def phase_sequential(clock: CompileClock, n_pm=500, n_vm=4096,
                     n_tasks=10_000) -> None:
    trace = filter_fitting(gwa_like_trace("das2", n_tasks, seed=7), PM_CORES)
    spec, params = engine.make_cloud(n_pm=n_pm, n_vm=n_vm, pm_cores=PM_CORES,
                                     max_events=MAX_EVENTS)
    res, wall, compile_s, replay = timed(
        clock, engine.simulate, spec, trace, params=params)
    facts = check_lane(res, res.readings(spec), trace.n, "sequential")
    report("c_sequential", n_pm=n_pm, n_vm=n_vm, tasks=int(trace.n),
           wall_s=wall, compile_s=compile_s, compaction_replay=replay,
           sim_t_end_s=float(res.t_end), **facts)


def tournament(n_pm=100, n_vm=1024, n_tasks=2000):
    """The 3 x 5 VM x PM registry grid as one stacked batch."""
    trace = filter_fitting(gwa_like_trace("das2", n_tasks, seed=7), PM_CORES)
    spec, base = engine.make_cloud(n_pm=n_pm, n_vm=n_vm, pm_cores=PM_CORES,
                                   max_events=MAX_EVENTS)
    cells = [(v, p) for v in registry.names("vm")
             for p in registry.names("pm")]
    params = engine.stack_params(
        [dataclasses.replace(base, vm_sched=v, pm_sched=p) for v, p in cells])
    return spec, trace, params, cells


def check_grid(spec, res, trace, cells, where: str) -> list[dict]:
    readings = res.readings(spec)
    lanes = []
    for b, (v, p) in enumerate(cells):
        lane = jax.tree.map(lambda x: x[b], res)
        lane_rd = {k: x[b] for k, x in readings.items()}
        lanes.append(check_lane(lane, lane_rd, trace.n,
                                f"{where} lane {b} ({v}, {p})"))
    return lanes


def phase_batched(clock: CompileClock) -> None:
    spec, trace, params, cells = tournament()
    res, wall, compile_s, replay = timed(
        clock, engine.simulate_batch, spec, trace, params)
    lanes = check_grid(spec, res, trace, cells, "batched")
    report("d_batched", n_pm=spec.n_pm, n_vm=spec.n_vm, tasks=int(trace.n),
           lanes=len(cells), wall_s=wall, compile_s=compile_s,
           compaction_replay=replay,
           events=[lane["events"] for lane in lanes],
           rejected=[lane["rejected"] for lane in lanes])


def phase_stream(clock: CompileClock, n_tasks=100_000, window=512) -> None:
    """benchmarks/streaming_bench.py's replay at its full length."""
    spec, params = engine.make_cloud(n_pm=20, n_vm=1024, pm_cores=PM_CORES,
                                     max_events=200_000_000)
    engine._stream_step.clear_cache()
    stream = gwa_window_stream("das2", n_tasks, window,
                               max_cores=int(PM_CORES), seed=21)
    res, wall, compile_s, replay = timed(
        clock, engine.simulate_stream, spec, stream, params)
    compiles = int(engine._stream_step._cache_size())
    check(compiles == 1, f"stream: window step compiled {compiles} times")
    check(res.completion.shape[0] == n_tasks,
          f"stream: {res.completion.shape[0]} of {n_tasks} task ids")
    facts = check_lane(res, res.readings(spec), n_tasks, "stream")
    report("e_stream", n_pm=spec.n_pm, n_vm=spec.n_vm, tasks=n_tasks,
           window=window, windows=-(-n_tasks // window), compiles=compiles,
           wall_s=wall, compile_s=compile_s, compaction_replay=replay,
           sim_t_end_s=float(res.t_end), **facts)


def _flows(rng, C, S):
    provider = jnp.asarray(rng.randint(0, S, C), jnp.int32)
    consumer = jnp.asarray(rng.randint(0, S, C), jnp.int32)
    p_l = jnp.asarray((rng.rand(C) * 4 + 0.1).astype(np.float32))
    live = jnp.asarray(rng.rand(C) < 0.8)
    perf = jnp.asarray((rng.rand(S) * 10).astype(np.float32))
    return provider, consumer, p_l, live, perf


def phase_kernels() -> None:
    """Kernels compiled for the chip (no interpret mode) against their
    pure-jnp references, at the shapes the engine hands them: the
    compacted buckets of phases (c)/(d) and the dense 100 x 1024 layout."""
    rng = np.random.RandomState(0)
    widths = {}
    # horizon widths: compacted 500 PM (2*2048 flow lanes + 500 PMs + 4
    # tails), dense 500 x 4096 (2*4596 + 500 + 4), a small bucket
    for n in (4600, 9696, 300):
        cand = jnp.asarray((rng.randn(n) * 100).astype(np.float32))
        mask = jnp.asarray(rng.rand(n) < 0.6)
        got = masked_min(cand, mask)
        want = ref.masked_min_ref(cand, mask)
        check(float(got) == float(want),
              f"masked_min N={n}: {float(got)} != {float(want)}")
        widths.setdefault("masked_min", []).append(n)
    # (flows, spreaders): 500-PM bucket, 100-PM bucket, dense 100 x 1024
    for C, S in ((2048, 2048), (512, 512), (1124, 1426)):
        provider, consumer, p_l, live, perf = _flows(rng, C, S)
        got = maxmin_solve(provider, consumer, p_l, live, perf)
        want = ref.maxmin_solve_ref(provider, consumer, p_l, live, perf)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
        r = jnp.asarray((rng.rand(C) * 2).astype(np.float32))
        unfrozen = live & jnp.asarray(rng.rand(C) < 0.7)
        dp, dc = fill_stats(provider, consumer, r, live, unfrozen, perf)
        dp_ref, dc_ref = ref.fill_stats_ref(provider, consumer, r, live,
                                            unfrozen, perf)
        np.testing.assert_allclose(np.asarray(dp), np.asarray(dp_ref),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(dc), np.asarray(dc_ref),
                                   rtol=1e-5)
        widths.setdefault("maxmin_solve_and_fill_stats", []).append([C, S])
    report("f_kernels", interpret=False, shapes=widths)


def phase_four_chips(clock: CompileClock) -> None:
    """The (d) grid through simulate_batch_sharded over four chips, lane
    by lane against the same grid on devices[0]."""
    spec, trace, params, cells = tournament()
    devs = jax.devices()[:4]
    sharded, wall4, compile4, _ = timed(
        clock, engine.simulate_batch_sharded, spec, trace, params,
        devices=devs)
    homes = {d for leaf in jax.tree.leaves(sharded)
             for d in leaf.devices()}
    check(len(homes) == 4, f"sharded result lives on {len(homes)} devices")
    one = jax.device_put((trace, params), devs[0])
    single, wall1, compile1, _ = timed(
        clock, engine.simulate_batch, spec, *one)
    check_grid(spec, sharded, trace, cells, "four-chip")
    bitwise, worst = True, 0.0
    for a, b in zip(jax.tree.leaves(sharded), jax.tree.leaves(single)):
        a, b = np.asarray(a), np.asarray(b)
        check(a.shape == b.shape, f"shape {a.shape} != {b.shape}")
        if np.issubdtype(a.dtype, np.floating):
            same = np.array_equal(a, b, equal_nan=True)
            both = np.isfinite(a) & np.isfinite(b)
            check((np.isfinite(a) == np.isfinite(b)).all(),
                  "four-chip: finite pattern differs from one chip")
            if both.any():
                rel = np.abs(a[both] - b[both]) / np.maximum(
                    np.abs(b[both]), 1e-30)
                worst = max(worst, float(rel.max()))
        else:
            same = np.array_equal(a, b)
            check(same, "four-chip: integer result differs from one chip")
        bitwise &= bool(same)
    check(worst <= 1e-5, f"four-chip: max rel diff {worst} vs one chip")
    report("four_chips", lanes=len(cells), devices=len(homes),
           bitwise_equal=bitwise, max_rel_diff=worst,
           wall_s_4chips=wall4, compile_s_4chips=compile4,
           wall_s_1chip=wall1, compile_s_1chip=compile1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (d) grid sharded over four chips "
                         "and compare it lane by lane with one chip")
    args = ap.parse_args(argv)
    device = phase_device(4 if args.four_chips else 1)
    clock = CompileClock()
    if args.four_chips:
        phase_four_chips(clock)
    else:
        phase_oracle()
        phase_sequential(clock)
        phase_batched(clock)
        phase_stream(clock)
        phase_kernels()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
