"""What the entry drivers share: building the program's cloud and the
reference's from a configuration file, one timed call with its host
spans, and the per-lane answers read back from a result."""
from __future__ import annotations

import dataclasses
import time
import warnings

import numpy as np

from bench.harness import Call
from bench.reference.cloud import Cloud

VM_POLICIES = ("firstfit", "nonqueuing", "smallestfirst")
PM_POLICIES = ("alwayson", "ondemand")


@dataclasses.dataclass(frozen=True)
class Lane:
    vm_sched: str
    pm_sched: str
    idle_scale: float = 1.0


def lanes_of(config: dict, traffic: dict) -> list[Lane]:
    """The scenarios one call runs: the configuration's scheduler grid
    times the traffic's idle-draw scales, in that order."""
    vm = config["vm_sched"]
    pm = config["pm_sched"]
    vm = vm if isinstance(vm, list) else [vm]
    pm = pm if isinstance(pm, list) else [pm]
    scales = traffic.get("idle_scales", [1.0])
    return [Lane(v, p, float(s)) for v in vm for p in pm for s in scales]


def power_table(config: dict, scale: float = 1.0):
    """The program's Table 1 power model of ``config``, idle draw scaled
    as ``experiments.pareto.power_scale_grid`` does."""
    from repro.core.energy import PowerStateTable
    from repro.experiments.pareto import power_scale_grid
    pw = config["power"]
    base = PowerStateTable.simple(
        off_w=pw["off_w"], on_w=pw["switching_on_w"], min_w=pw["idle_w"],
        max_w=pw["max_w"], off_w2=pw["switching_off_w"],
        boot_s=pw["boot_s"], shutdown_s=pw["shutdown_s"])
    return power_scale_grid([scale], base=base)[0]


def engine_cloud(config: dict, lanes: list[Lane]):
    """``(spec, params)`` of the program for these lanes; ``params`` is
    stacked along a leading batch axis when there is more than one."""
    from repro.core import engine
    from repro.core.energy import MeterTopology, hvac_spec
    c = config["cluster"]
    spec, base = engine.make_cloud(
        n_pm=c["n_pm"], n_vm=c["n_vm"], pm_cores=float(c["pm_cores"]),
        perf_core=float(c["perf_core"]), net_bw=float(c["net_bw"]),
        repo_bw=float(c["repo_bw"]), image_mb=float(c["image_mb"]),
        boot_work=float(c["boot_work"]), latency_s=float(c["latency_s"]),
        max_events=int(config["max_events"]),
        meters=MeterTopology(indirect=(
            hvac_spec(config["meters"]["hvac_pue_minus_one"]),)))
    points = [dataclasses.replace(base, vm_sched=ln.vm_sched,
                                  pm_sched=ln.pm_sched,
                                  power=power_table(config, ln.idle_scale))
              for ln in lanes]
    if len(points) == 1:
        return spec, points[0]
    return spec, engine.stack_params(points)


def ref_cloud(config: dict, lane: Lane) -> Cloud:
    """The same scenario for the plain reference."""
    c, pw = config["cluster"], config["power"]
    return Cloud(
        n_pm=int(c["n_pm"]), n_vm=int(c["n_vm"]),
        pm_cores=float(c["pm_cores"]), perf_core=float(c["perf_core"]),
        net_bw=float(c["net_bw"]), repo_bw=float(c["repo_bw"]),
        image_mb=float(c["image_mb"]), boot_work=float(c["boot_work"]),
        latency_s=float(c["latency_s"]),
        p_min=(pw["off_w"], pw["switching_on_w"], pw["idle_w"],
               pw["switching_off_w"]),
        p_max=(pw["off_w"], pw["switching_on_w"], pw["max_w"],
               pw["switching_off_w"]),
        boot_s=float(pw["boot_s"]), shutdown_s=float(pw["shutdown_s"]),
        idle_scale=lane.idle_scale,
        pue_minus_one=float(config["meters"]["hvac_pue_minus_one"]),
        vm_sched=lane.vm_sched, pm_sched=lane.pm_sched)


def pick(res):
    """The device arrays a caller reads back from a result."""
    return {"completion": res.completion, "rejected": res.rejected,
            "pm_energy": res.meters.pm.energy_hi,
            "iaas_total": res.meters.total.energy_hi,
            "indirect": res.meters.indirect.energy_hi,
            "t_end": res.t_end, "n_events": res.n_events,
            "overflow": res.overflow}


def split_lanes(host: dict, n_lanes: int, batched: bool) -> list[dict]:
    """Per-lane answers (numpy) from a read-back result."""
    def lane(b):
        g = (lambda x: np.asarray(x)[b]) if batched else np.asarray
        return {"completion": g(host["completion"]),
                "rejected": g(host["rejected"]),
                "pm_energy": g(host["pm_energy"]),
                "iaas_total": float(g(host["iaas_total"])),
                "hvac": float(g(host["indirect"])[0]),
                "t_end": float(g(host["t_end"])),
                "n_events": int(g(host["n_events"])),
                "overflow": bool(g(host["overflow"]))}
    return [lane(b) for b in range(n_lanes)]


def lane_failed(ans: dict, max_events: int) -> bool:
    """A scenario that overflowed, hit the event cap, or left a task
    neither done nor rejected."""
    settled = np.isfinite(ans["completion"]) | ans["rejected"]
    return bool(ans["overflow"] or ans["n_events"] >= max_events
                or not settled.all())


def timed_call(item: int, tasks_per_lane: int, n_lanes: int, batched: bool,
               max_events: int, entry) -> Call:
    """One closed-loop call: dispatch, wait, read back — each under a host
    span the trace reduction labels idle gaps with."""
    import jax
    from jax.profiler import TraceAnnotation

    error, answers = None, []
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        start = time.perf_counter()
        try:
            with TraceAnnotation("bench.call"):
                with TraceAnnotation("bench.dispatch"):
                    res = entry()
                with TraceAnnotation("bench.block"):
                    res = jax.block_until_ready(res)
                with TraceAnnotation("bench.readback"):
                    host = jax.device_get(pick(res))
            answers = split_lanes(host, n_lanes, batched)
        except RuntimeError as e:  # e.g. a stream that cannot replay
            error = f"{type(e).__name__}: {e}"
        end = time.perf_counter()
    replays = sum("compaction bucket" in str(w.message) for w in caught)
    failed = (n_lanes if error else
              sum(lane_failed(a, max_events) for a in answers))
    return Call(item=item, start=start, end=end,
                tasks=tasks_per_lane * n_lanes, lanes=n_lanes,
                events=[a["n_events"] for a in answers] or [0],
                dense_replays=replays, failed=failed, error=error,
                answers=answers)
