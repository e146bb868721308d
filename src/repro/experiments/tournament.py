"""Scheduler tournaments: arbitrary VM x PM policy grids in one batch.

The paper's §4 methodology compares VM schedulers against PM
state-schedulers cell by cell; since scheduler identity is
``CloudParams`` *data* (integer codes into the open policy registry,
DESIGN.md §6), any grid of (``vm_sched``, ``pm_sched``) cells — the
paper's 3x2, or every registered pair at much larger cloud sizes — runs
as a single (sharded) ``simulate_batch`` call and is scored from the
meter stack (DESIGN.md §4).  The default axes come straight from
:func:`repro.sched.registry.names`: registering a policy makes it a
tournament citizen with no further wiring.  This is the one code path
for scheduler comparison, not a demo.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.sched import registry

from . import shard


def scheduler_grid(vm_scheds: Sequence[str | int] | None = None,
                   pm_scheds: Sequence[str | int] | None = None
                   ) -> list[tuple]:
    """The full cross product of VM x PM scheduler cells.  Each axis
    defaults to *every* registered policy of its layer
    (:func:`repro.sched.registry.names`) — the paper's 3x2 matrix plus
    the consolidate/defrag/evacuate PM schedulers, i.e. 3x5, growing
    automatically with out-of-tree registrations."""
    if vm_scheds is None:
        vm_scheds = registry.names("vm")
    if pm_scheds is None:
        pm_scheds = registry.names("pm")
    return [(v, p) for v in vm_scheds for p in pm_scheds]


def _sched_name(value, layer: str) -> str:
    return value if isinstance(value, str) else registry.name_of(layer, value)


class TournamentResult(NamedTuple):
    rows: list[dict]            # one row per (vm_sched, pm_sched) cell
    result: engine.CloudResult  # full batched engine result


def run(spec: engine.CloudSpec, trace: engine.Trace,
        base_params: engine.CloudParams, *,
        schedulers: Sequence[tuple] | None = None,
        sharded: bool = True, devices=None) -> TournamentResult:
    """Score every ``(vm_sched, pm_sched)`` cell of ``schedulers`` (default
    :func:`scheduler_grid`) on one trace, in one batch.

    Each row reports the meter-stack readings — IT energy (whole-IaaS
    aggregate), the job-attributed share (per-VM Eq. 6 meters), the
    unattributed idle waste, facility cooling (HVAC indirect meter, when
    present) — plus makespan, completion and queueing statistics.
    """
    if schedulers is None:
        schedulers = scheduler_grid()
    schedulers = list(schedulers)
    points = [dataclasses.replace(base_params, vm_sched=v, pm_sched=p)
              for v, p in schedulers]
    res = shard.run_batch(spec, trace, engine.stack_params(points),
                          sharded=sharded, devices=devices)
    readings = res.readings(spec)
    n = len(schedulers)
    rows = []
    for b, (vm_sched, pm_sched) in enumerate(schedulers):
        completion = res.completion[b]
        done = jnp.isfinite(completion)
        row = {
            "vm_sched": _sched_name(vm_sched, "vm"),
            "pm_sched": _sched_name(pm_sched, "pm"),
            "energy_kwh": float(readings["iaas_total"][b]) / 3.6e6,
            "makespan_s": float(res.t_end[b]),
            "jobs_done": int(done.sum()),
            "jobs_rejected": int(res.rejected[b].sum()),
            "mean_completion_s": float(
                jnp.where(done, completion, 0.0).sum()
                / jnp.maximum(done.sum(), 1)),
            "events": int(res.n_events[b]),
        }
        if "vm" in readings:
            # per-VM Eq. 6 meters: the share of IT energy the jobs actually
            # drew, vs the idle/overhead waste a better policy could shed
            row["job_kwh"] = float(jnp.sum(readings["vm"][b])) / 3.6e6
            row["idle_kwh"] = float(readings["vm_unattributed"][b]) / 3.6e6
        if "hvac" in readings:
            row["hvac_kwh"] = float(readings["hvac"][b]) / 3.6e6
        rows.append(row)
    return TournamentResult(rows=rows, result=res)
