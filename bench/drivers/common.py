"""What the entry drivers share: building the program's cloud and trace
from what a configuration file and a generator hold, one timed call with
its host spans, and the per-lane answers read back from a result."""
from __future__ import annotations

import dataclasses
import time
import warnings

import numpy as np

from bench.harness import Call

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


def _as_field(default, value):
    """``value`` in the type of a field's default, so that a JSON ``64``
    reaches a float field as ``64.0`` and a count stays an integer."""
    if isinstance(default, bool) or not isinstance(default, (int, float)):
        return value
    return type(default)(value)


def engine_cloud(config: dict, lanes: list[Lane]):
    """``(spec, params)`` of the program for these lanes, from every key of
    the configuration's ``cluster`` (``engine.make_cloud`` refuses a name
    it does not know); ``params`` is stacked along a leading batch axis
    when there is more than one."""
    from repro.core import engine
    from repro.core.energy import MeterTopology, hvac_spec
    defaults = {f.name: f.default for cls in (engine.CloudSpec,
                                              engine.CloudParams)
                for f in dataclasses.fields(cls)}
    cluster = {k: _as_field(defaults.get(k), v)
               for k, v in config["cluster"].items()}
    spec, base = engine.make_cloud(
        **cluster, max_events=int(config["max_events"]),
        meters=MeterTopology(indirect=(
            hvac_spec(config["meters"]["hvac_pue_minus_one"]),)))
    points = [dataclasses.replace(base, vm_sched=ln.vm_sched,
                                  pm_sched=ln.pm_sched,
                                  power=power_table(config, ln.idle_scale))
              for ln in lanes]
    if len(points) == 1:
        return spec, points[0]
    return spec, engine.stack_params(points)


def trace_of(arrays: dict):
    """The program's ``engine.Trace`` of every array in ``arrays`` (a
    generator's trace or window) that names one of its fields; the fields
    it leaves out keep their defaults (``gid`` is None for a whole
    trace)."""
    import jax.numpy as jnp
    from repro.core import engine
    return engine.Trace(**{k: jnp.asarray(v) for k, v in arrays.items()
                           if k in engine.Trace._fields})


def pick(res):
    """The device arrays a caller reads back from a result, with every
    field of the loop's counters."""
    return {"completion": res.completion, "rejected": res.rejected,
            "pm_energy": res.meters.pm.energy_hi,
            "iaas_total": res.meters.total.energy_hi,
            "indirect": res.meters.indirect.energy_hi,
            "t_end": res.t_end, "n_events": res.n_events,
            "overflow": res.overflow, "counters": res.counters._asdict()}


def split_lanes(host: dict, n_lanes: int, batched: bool) -> list[dict]:
    """Per-lane answers (numpy) from a read-back result; ``counters``
    holds each counter's plain value (an int, or a list of ints)."""
    def lane(b):
        g = (lambda x: np.asarray(x)[b]) if batched else np.asarray
        return {"completion": g(host["completion"]),
                "rejected": g(host["rejected"]),
                "pm_energy": g(host["pm_energy"]),
                "iaas_total": float(g(host["iaas_total"])),
                "hvac": float(g(host["indirect"])[0]),
                "t_end": float(g(host["t_end"])),
                "n_events": int(g(host["n_events"])),
                "overflow": bool(g(host["overflow"])),
                "counters": {k: g(v).tolist()
                             for k, v in host["counters"].items()}}
    return [lane(b) for b in range(n_lanes)]


def counter_per_event(calls, name: str) -> float | None:
    """A loop counter summed over the lanes of the window's calls, over
    their summed ``n_events``; None where no answer carries it."""
    lanes = [a for c in calls if not c.error for a in c.answers]
    events = sum(a["n_events"] for a in lanes)
    if not events or any(name not in a.get("counters", {}) for a in lanes):
        return None
    return sum(a["counters"][name] for a in lanes) / events


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
