"""The comparison that decides ``correct``.

Every lane of every call of the window is compared with the plain
reference that the cell names (a traffic file's ``"reference"``,
``bench/reference/<name>.py``, ``cloud`` by default) run once over the
same trace and scenario.  A reference module exposes ``cloud(config,
lane)``, the scenario of one lane, and ``replay(cloud, trace, *,
finish_frac, tie_window)`` over the whole trace dict, returning
``completion``, ``rejected``, ``pm_energy``, ``iaas_total``, ``hvac`` and
``t_end``.  Each number is the worst over all of them:

* ``fate_mismatch`` — tasks whose fate differs: done, rejected, or
  neither (which tasks start and which are rejected);
* ``completion_rel`` — the largest gap between a task's completion time
  and the reference's, over the reference's time (the lifecycle and the
  fair-share rates);
* ``pm_energy_rel`` — the largest gap of one PM's metered energy, over
  the reference's (PM power states and the per-PM meters);
* ``energy_total_rel`` — the largest gap of the IaaS total or of the HVAC
  meter, over the reference's;
* ``clock_rel`` — the gap of the final simulated clock, over the
  reference's.

A lane of a call that raised, or whose answer has the wrong shape, reads
every task as mismatched and every gap as 1.

The program keeps its clock and the remaining work of each flow in
float32, so it cannot order two events closer together than that
precision resolves, and the order can decide a PM wake-up or a placement.
A lane that misses a limit against the reference is therefore compared
again with the reference's readings of such near-simultaneous events
(``VARIANTS``: flows finish only at their own instant, or events within a
few float32 steps of the clock are simultaneous), and keeps the numbers
of the reading that passes.  Each variant moves an event by at most that
window, so a wrong answer of any size beyond it fails all of them.
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("fate_mismatch", "completion_rel", "pm_energy_rel",
           "energy_total_rel", "clock_rel")

# (finish_frac, tie_window) readings of near-simultaneous events; the
# first is the reference's own rule.
STRICT = (1e-6, 0.0)
VARIANTS = ((1e-12, 0.0), (1e-6, 2.0 ** -22), (1e-12, 2.0 ** -22),
            (1e-6, 2.0 ** -19))


def _fate(completion, rejected):
    return np.where(rejected, 2, np.where(np.isfinite(completion), 1, 0))


def _rel(a, b):
    return abs(float(a) - float(b)) / max(abs(float(b)), 1e-30)


def numbers(ans: dict, ref: dict) -> dict:
    """The compared numbers of one lane against its reference."""
    comp = np.asarray(ans["completion"], np.float64)
    rcomp = ref["completion"]
    if comp.shape != rcomp.shape:
        return failed(rcomp.shape[0])
    fate_p = _fate(comp, np.asarray(ans["rejected"], bool))
    fate_r = _fate(rcomp, ref["rejected"])
    both = np.isfinite(comp) & np.isfinite(rcomp)
    gap = np.abs(comp[both] - rcomp[both]) / np.maximum(rcomp[both], 1.0)
    pm = np.asarray(ans["pm_energy"], np.float64)
    pm_gap = np.abs(pm - ref["pm_energy"]) / np.maximum(ref["pm_energy"],
                                                         1.0)
    return {
        "fate_mismatch": int((fate_p != fate_r).sum()),
        "completion_rel": float(gap.max()) if gap.size else 0.0,
        "pm_energy_rel": float(pm_gap.max()),
        "energy_total_rel": max(_rel(ans["iaas_total"], ref["iaas_total"]),
                                _rel(ans["hvac"], ref["hvac"])),
        "clock_rel": _rel(ans["t_end"], ref["t_end"]),
    }


def failed(n_tasks: int) -> dict:
    return {n: n_tasks if n == "fate_mismatch" else 1.0 for n in NUMBERS}


class References:
    """The reference of each job ``key: (cloud, trace)``, run by the
    reference module once per reading and only when asked for."""

    def __init__(self, reference, jobs: dict):
        self.reference, self.jobs, self.done = reference, jobs, {}

    def __call__(self, key, reading=STRICT):
        if (key, reading) not in self.done:
            cloud, tr = self.jobs[key]
            self.done[(key, reading)] = self.reference.replay(
                cloud, tr, finish_frac=reading[0], tie_window=reading[1])
        return self.done[(key, reading)]


def passes(got: dict, limits: dict) -> bool:
    return all(got[n] <= limits[n]["limit"] for n in NUMBERS)


def lane_numbers(ans, key, refs: References, limits: dict) -> dict:
    """The lane's numbers against the reference, or against the first of
    its near-simultaneous readings that passes."""
    got = numbers(ans, refs(key))
    if passes(got, limits):
        return got
    for reading in VARIANTS:
        alt = numbers(ans, refs(key, reading))
        if passes(alt, limits):
            return alt
    return got


def check(limits: dict, reference, jobs: dict, calls) -> dict:
    """``{"correct", "numbers": {name: {"value", "limit"}}}`` over every
    lane of every call, against the reference module ``reference``."""
    refs = References(reference, jobs)
    worst = {n: 0 if n == "fate_mismatch" else 0.0 for n in NUMBERS}
    for c in calls:
        for lane in range(c.lanes):
            key = (c.item, lane)
            got = (lane_numbers(c.answers[lane], key, refs, limits)
                   if not c.error and len(c.answers) == c.lanes
                   else failed(refs(key)["completion"].shape[0]))
            worst = {n: max(worst[n], got[n]) for n in NUMBERS}
    out = {n: {"value": worst[n], "limit": limits[n]["limit"]}
           for n in NUMBERS}
    correct = all(v["value"] <= v["limit"] for v in out.values())
    return {"correct": bool(correct), "numbers": out}
