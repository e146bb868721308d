"""One run of one benchmark cell: set-up, the measured window, the traced
slice, the check against the plain reference, and the result line.

Everything that belongs to one configuration, traffic mix, entry driver
or metric lives in a file of its own and is found by the name that
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``   — the deployment (cluster, power,
  policies, the guarantees it states);
* ``bench/traffic/<traffic>.json``  — the traffic mix: which generator and
  entry driver, trace lengths, the seed pool, the lanes of a sweep;
* ``bench/generators/<name>.py``    — trace generators;
* ``bench/drivers/<name>.py``       — one per program entry point;
* ``bench/reference/<name>.py``     — the plain reference a traffic file
  names under ``"reference"`` (``cloud`` when it names none):
  ``cloud(config, lane)`` and ``replay(cloud, trace, ...)``;
* ``bench/metrics/<metric>.py``     — one reader per metric;
* ``bench/checks/<check>.json``     — the limits of the comparison.

Three seams take a new deployment without an edit to the harness:

* the reference module, named by the traffic file as above;
* the program is built from what the files hold: every key of the
  configuration's ``cluster`` goes to ``engine.make_cloud``, and every
  array the generator returns that names a field of ``engine.Trace``
  goes into the trace (``bench/drivers/common.py``);
* a metric reader gets ``ctx`` with the window's ``calls``, whose lane
  answers carry every field of the program's ``res.counters`` under
  ``"counters"``, and in the traced run ``traced``, whose ``"stages"``
  hold the device time per loop iteration of each stage scope and
  ``"idle_by_label"`` the device idle time by the host span it fell in
  (``bench/trace_reduce.py``, ``bench/stage_reduce.py``).
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import time
import warnings

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = BENCH / ".out"

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


def process_start() -> float:
    """Wall-clock time this process started (Linux ``/proc``), so that
    set-up counts the interpreter and the imports too."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(line.split()[1]) for line in f
                         if line.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return time.time()


def load_module(path: pathlib.Path):
    """Import a file of the benchmark by path (names may hold '.', '-')."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("")
                               .parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with its files read."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    checks: dict

    @property
    def driver(self) -> str:
        return self.traffic["driver"]

    @property
    def reference(self) -> str:
        return self.traffic.get("reference", "cloud")


def load_reference(cell: Cell):
    """The plain reference module the cell's traffic names."""
    return load_module(BENCH / "reference" / f"{cell.reference}.py")


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, bench_json: pathlib.Path | None = None) -> Cell:
    bench = json.loads((bench_json or ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; one of {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    checks = json.loads(
        (BENCH / "checks" / f"{traffic['check']}.json").read_text())
    return Cell(
        name=workload, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        checks=checks)


class CompileClock:
    """Counts and times executables built (backend compiles) and loaded
    from the persistent cache, tagged by the run's current phase."""

    def __init__(self):
        import jax
        self.phase = "setup"
        self.seconds: dict = {}
        self.count: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **_):
        if event in (BACKEND_COMPILE, CACHE_RETRIEVAL):
            key = (self.phase, event)
            self.seconds[key] = self.seconds.get(key, 0.0) + duration
            self.count[key] = self.count.get(key, 0) + 1

    def total(self, phase: str) -> tuple[float, int]:
        """``(seconds, executables)`` built or loaded during ``phase``."""
        events = (BACKEND_COMPILE, CACHE_RETRIEVAL)
        return (sum(self.seconds.get((phase, e), 0.0) for e in events),
                sum(self.count.get((phase, e), 0) for e in events))


@dataclasses.dataclass
class Call:
    """One entry-point call of the window, read back to the host."""

    item: int            # index into the workload's input pool
    start: float
    end: float
    tasks: int           # trace tasks replayed, summed over lanes
    lanes: int
    events: list         # n_events per lane
    dense_replays: int   # compaction-overflow replays inside the call
    failed: int          # lanes that overflowed / hit max_events / stuck
    error: str | None
    answers: list        # per-lane host answers (see drivers/common.py)


def device_info(devices) -> dict:
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int | None:
    """The peak device memory of the fullest chip, where the backend
    reports it (the CPU reports none)."""
    peaks = [int(st["peak_bytes_in_use"]) for st in
             (d.memory_stats() or {} for d in devices)
             if "peak_bytes_in_use" in st]
    return max(peaks) if peaks else None


def run_window(wl, clock: CompileClock, seconds: float) -> list[Call]:
    """Calls back to back until ``seconds`` have passed; a call is never
    split, so the window ends with the first call that ends past it."""
    calls = []
    clock.phase = "window"
    t0 = time.perf_counter()
    k = 0
    while True:
        calls.append(wl.call(k))
        k += 1
        if calls[-1].end - t0 >= seconds:
            break
    clock.phase = "after"
    return calls


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        require_platform: str | None = "tpu", trace_dir=None,
        t_process: float | None = None, log=print) -> dict:
    """One run of ``cell``: returns the result line as a dict."""
    t_process = process_start() if t_process is None else t_process
    import jax

    devices = jax.devices()
    if require_platform is not None:
        if devices[0].platform != require_platform:
            raise SystemExit(
                f"no {require_platform.upper()}: jax.devices()[0] is "
                f"{devices[0].platform} ({devices[0].device_kind})")
        if len(devices) < cell.chips:
            raise SystemExit(f"cell {cell.name} needs {cell.chips} chips, "
                             f"found {len(devices)}")
    devices = devices[:cell.chips]
    clock = CompileClock()
    driver = load_module(BENCH / "drivers" / f"{cell.driver}.py")
    wl = driver.Workload(cell, seed, devices)
    wl.setup()
    wl.warm()
    setup_s = time.time() - t_process

    calls = run_window(wl, clock, seconds)
    mem = memory_peak(devices)

    traced = None
    if trace:
        from bench import trace_reduce
        tdir = pathlib.Path(trace_dir) if trace_dir else OUT / "trace"
        shutil.rmtree(tdir, ignore_errors=True)
        clock.phase = "trace"
        traced = trace_reduce.capture(wl.traced_call, tdir)
        clock.phase = "after"
        if not trace_dir:
            shutil.rmtree(tdir, ignore_errors=True)

    jobs = wl.reference_jobs(calls)
    wl.release()
    from bench.reference import compare
    checks = compare.check(cell.checks, wl.reference, jobs, calls)

    ctx = dict(cell=cell, calls=calls, setup_s=setup_s, clock=clock,
               traced=traced)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    for c in calls:
        log(json.dumps({"call": c.item, "wall_s": c.end - c.start,
                        "tasks": c.tasks, "lanes": c.lanes,
                        "events_max": max(c.events), "failed": c.failed,
                        "dense_replays": c.dense_replays,
                        "error": c.error}))
    device = device_info(devices)
    device["memory_peak_bytes"] = mem
    if traced is not None:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
    line = {
        "correct": checks["correct"],
        "attempted": sum(c.lanes for c in calls),
        "failed": sum(c.failed for c in calls),
        "metrics": metrics,
        "device": device,
    }
    if traced is not None:
        line["breakdown"] = traced["breakdown"]
    line["checks"] = checks["numbers"]
    return line


def main(argv=None) -> int:
    t_process = process_start()
    ap = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json on the accelerator.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace in this directory")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        line = run(cell, args.seed, args.seconds, bool(args.trace),
                   trace_dir=args.trace_dir, t_process=t_process)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0
