"""Plain reference of a VM IaaS region: the cloud of ``cloud.py`` with a
memory dimension and VMs that use a share of their cores.

The same scenario semantics as ``bench/reference/cloud.py`` (read its
docstring: the VM life, the schedulers, the power model, the order of
simultaneous events, ``finish_frac``, ``tie_window`` and the bfloat16
control), plus:

* a VM request asks for ``cores`` and ``mem`` GB; a PM accepts it only
  with both free, a request larger than a PM in either is rejected, and
  a finished VM releases both;
* a booted VM's task runs at most at ``util * cores * perf_core``;
* the on-demand PM scheduler still wakes PMs against the queued cores
  alone.

It is written over numpy arrays, so that a region of thousands of live
VMs replays in seconds: the CPU flows of one PM share that PM's CPU as a
single link, each capped by its own limit and its VM's CPU, so their
max-min rates are exact water-filling per PM; the image transfers share
the repository and the PMs' network links, and their rates come from
progressive filling, which takes one round per bottleneck level because
a transfer has no cap of its own.  It imports nothing of the program.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from bench.reference.cloud import (ACTIVE, BIG, DONE, OFF, PENDING,
                                   REJECTED, RUNNING, SWITCHING_OFF,
                                   SWITCHING_ON, Cloud, _bf16_array, _bf16_up)
from bench.reference.cloud import cloud as _cloud

FREE, XFER, BOOT, TASK = -1, 0, 1, 2


@dataclasses.dataclass(frozen=True)
class Region(Cloud):
    """A :class:`~bench.reference.cloud.Cloud` whose PMs have memory."""

    pm_mem: float = 256.0


def transfer_rates(prov, cons, cap_p, cap_c):
    """Max-min rates of uncapped flows between providers ``prov`` and
    consumers ``cons`` (integer ids into ``cap_p`` and ``cap_c``), by
    progressive filling: every unfrozen flow rises by the smallest share
    of a spreader it uses, and the flows at spreaders that reached their
    share freeze."""
    r = np.zeros(prov.shape[0])
    unfrozen = np.ones(prov.shape[0], bool)
    while unfrozen.any():
        def share(ids, cap):
            used = np.bincount(ids, r, cap.size)
            n = np.bincount(ids, unfrozen, cap.size)
            return np.maximum(cap - used, 0.0) / np.maximum(n, 1)
        room = np.minimum(share(prov, cap_p)[prov], share(cons, cap_c)[cons])
        delta = room[unfrozen].min()
        r[unfrozen] += delta
        unfrozen &= room > delta * (1 + 1e-12) + 1e-300
    return r


def water_fill(host, cap, capacity):
    """Max-min rates of flows that share their host's ``capacity`` as one
    link, each at most its own ``cap``: per host the flows below the
    water level get their cap and the rest the level."""
    n = host.shape[0]
    rate = np.zeros(n)
    if not n:
        return rate
    order = np.lexsort((cap, host))
    h, c = host[order], cap[order]
    starts = np.flatnonzero(np.r_[True, h[1:] != h[:-1]])
    size = np.diff(np.r_[starts, n])
    start = np.repeat(starts, size)
    pos = np.arange(n) - start
    k = np.repeat(size, size)
    csum = np.cumsum(c)
    before = csum - c - np.where(start > 0, csum[start - 1], 0.0)
    fits = before + c * (k - pos) <= capacity[h]
    n_capped = np.add.reduceat(fits.astype(np.int64), starts)
    capped = pos < np.repeat(n_capped, size)
    spent = np.add.reduceat(np.where(capped, c, 0.0), starts)
    left = np.maximum(size - n_capped, 1)
    level = np.maximum(capacity[h[starts]] - spent, 0.0) / left
    rate[order] = np.where(capped, c, np.repeat(level, size))
    return rate


def simulate(region: Region, arrival, cores, work, mem, util, *,
             precision="float64", finish_frac=1e-6, tie_window=0.0,
             max_steps=50_000_000):
    """Run one scenario to the end; returns what ``cloud.simulate``
    returns."""
    if precision == "float64":
        def q(x):
            return x

        def q_up(t, x):
            return x
    elif precision == "bfloat16":
        q, q_up = _bf16_array, _bf16_up
    else:
        raise ValueError(f"unknown precision {precision!r}")

    def q1(x):
        return float(q(np.float64(x)))

    arrival = q(np.asarray(arrival, np.float64))
    cores = q(np.asarray(cores, np.float64))
    work = q(np.asarray(work, np.float64))
    mem = q(np.asarray(mem, np.float64))
    limit = q(np.asarray(util, np.float64) * cores * region.perf_core)
    T, P, V = arrival.shape[0], region.n_pm, region.n_vm
    p_min, p_max = region.power_table()
    span = np.where(np.asarray(region.linear), p_max - p_min, 0.0)
    cpu_cap = region.pm_cores * region.perf_core
    if region.vm_sched not in ("firstfit", "nonqueuing", "smallestfirst"):
        raise ValueError(f"unknown VM scheduler {region.vm_sched!r}")
    if region.pm_sched not in ("alwayson", "ondemand"):
        raise ValueError(f"unknown PM scheduler {region.pm_sched!r}")
    smallest_first = region.vm_sched == "smallestfirst"
    reject_unfit = region.vm_sched == "nonqueuing"
    ondemand = region.pm_sched == "ondemand"

    order = np.lexsort((np.arange(T), arrival))
    fate = np.full(T, PENDING)
    t_done = np.full(T, math.inf)
    queue = []
    nxt = 0

    pstate = np.full(P, OFF if ondemand else RUNNING, np.int64)
    pend = np.full(P, math.inf)
    free = np.full(P, float(region.pm_cores))
    free_mem = np.full(P, float(region.pm_mem))
    n_hosted = np.zeros(P, np.int64)
    energy = np.zeros(P)
    total = 0.0
    # VM slots: what the flow carries, host, task, remaining, size, limit,
    # release
    kind = np.full(V, FREE, np.int64)
    host = np.zeros(V, np.int64)
    task = np.zeros(V, np.int64)
    rem = np.zeros(V)
    size = np.zeros(V)
    lim = np.zeros(V)
    release = np.zeros(V)
    overflow = False
    t = now = 0.0

    def admit():
        nonlocal nxt
        while nxt < T and arrival[order[nxt]] <= now:
            queue.append(int(order[nxt]))
            nxt += 1

    def pm_sched():
        if not ondemand:
            return False
        q_cores = float(sum(cores[i] for i in queue))
        soon = (pstate == RUNNING) | (pstate == SWITCHING_ON)
        deficit = q_cores - float(free[soon].sum())
        k = math.ceil(max(deficit, 0.0) / region.pm_cores)
        wake = np.flatnonzero(pstate == OFF)[:k]
        idle = (np.flatnonzero((pstate == RUNNING) & (n_hosted == 0))
                if not queue else np.empty(0, np.int64))
        pstate[wake] = SWITCHING_ON
        pend[wake] = q1(t + region.boot_s)
        pstate[idle] = SWITCHING_OFF
        pend[idle] = q1(t + region.shutdown_s)
        return bool(len(wake) or len(idle))

    def vm_sched():
        nonlocal overflow
        changed = False
        while queue:
            head = (min(queue, key=lambda i: (cores[i], i)) if smallest_first
                    else queue[0])
            c, m = cores[head], mem[head]
            fits = np.flatnonzero((pstate == RUNNING) & (free >= c)
                                  & (free_mem >= m))
            if (c > region.pm_cores or m > region.pm_mem
                    or (reject_unfit and not len(fits))):
                queue.remove(head)
                fate[head] = REJECTED
                changed = True
                continue
            if not len(fits):
                break
            slots = np.flatnonzero(kind == FREE)
            if not len(slots):
                overflow = True
                break
            pm, v = int(fits[0]), int(slots[0])
            queue.remove(head)
            fate[head] = ACTIVE
            free[pm] = q1(free[pm] - c)
            free_mem[pm] = q1(free_mem[pm] - m)
            n_hosted[pm] += 1
            kind[v], host[v], task[v] = XFER, pm, head
            rem[v] = size[v] = q1(region.image_mb)
            lim[v], release[v] = BIG, q1(t + region.latency_s)
            changed = True
        return changed

    def manage():
        admit()
        changed = pm_sched()
        return vm_sched() or changed

    manage()
    steps = 0
    slot_cores = np.zeros(V)
    while steps < max_steps:
        steps += 1
        busy = kind != FREE
        live = busy & (now >= release) & (rem > finish_frac * size + 1e-9)
        rate = np.zeros(V)
        xf = np.flatnonzero(live & (kind == XFER))
        if xf.size:
            net = np.where(pstate != OFF, region.net_bw, 0.0)
            rate[xf] = transfer_rates(np.zeros(xf.size, np.int64), host[xf],
                                      np.array([region.repo_bw]), net)
        cpu = np.flatnonzero(live & (kind != XFER))
        if cpu.size:
            slot_cores[cpu] = cores[task[cpu]]
            cap = np.minimum(lim[cpu], np.maximum(slot_cores[cpu], 1.0)
                             * region.perf_core)
            rate[cpu] = water_fill(host[cpu], cap,
                                   np.where(pstate == RUNNING, cpu_cap, 0.0))
        rate = q(rate)
        # ---- event horizon
        dt = math.inf
        on = live & (rate > 0)
        if on.any():
            dt = float((rem[on] / rate[on]).min())
        gated = busy & (now < release)
        if gated.any():
            dt = min(dt, float(release[gated].min()) - t)
        if nxt < T:
            dt = min(dt, float(arrival[order[nxt]]) - t)
        trans = (pstate == SWITCHING_ON) | (pstate == SWITCHING_OFF)
        if trans.any():
            dt = min(dt, float(pend[trans].min()) - t)
        has_event = dt < math.inf
        dt = max(dt, 0.0) if has_event else 0.0
        t_new = q_up(t, t + dt)
        dt = t_new - t if precision != "float64" else dt
        # ---- energy over [t, t_new]
        util_pm = np.bincount(host[cpu], rate[cpu], P) / cpu_cap
        power = p_min[pstate] + np.clip(util_pm, 0.0, 1.0) * span[pstate]
        if precision == "float64":
            energy += power * dt
            total += float(power.sum()) * dt
        else:
            energy = q(energy + q(q(power) * dt))
            total = q1(total + q1(q1(float(power.sum())) * dt))
        # ---- drain and finish flows
        t = t_new
        now = t + t * tie_window
        rem[live] = q(np.maximum(rem[live] - rate[live] * dt, 0.0))
        done = live & (rem <= finish_frac * size + 1e-9 + rate * (now - t))
        d_x = np.flatnonzero(done & (kind == XFER))
        d_b = np.flatnonzero(done & (kind == BOOT))
        d_t = np.flatnonzero(done & (kind == TASK))
        kind[d_x] = BOOT
        rem[d_x] = size[d_x] = q1(region.boot_work)
        lim[d_x], release[d_x] = BIG, t
        kind[d_b] = TASK
        rem[d_b] = size[d_b] = work[task[d_b]]
        lim[d_b], release[d_b] = limit[task[d_b]], t
        if d_t.size:
            i, h = task[d_t], host[d_t]
            fate[i] = DONE
            t_done[i] = t
            np.add.at(free, h, cores[i])
            np.add.at(free_mem, h, mem[i])
            free[:] = q(free)
            free_mem[:] = q(free_mem)
            np.subtract.at(n_hosted, h, 1)
            kind[d_t] = FREE
        # ---- PM power transitions
        ended = trans & (pend <= now)
        pstate[ended & (pstate == SWITCHING_ON)] = RUNNING
        pstate[ended & (pstate == SWITCHING_OFF)] = OFF
        pend[ended] = math.inf
        # ---- PM then VM scheduler
        changed = manage() or bool(done.any()) or bool(ended.any())
        busy = kind != FREE
        more = (bool((busy & (rem > finish_frac * size + 1e-9)).any())
                or nxt < T or bool(queue)
                or bool(((pstate == SWITCHING_ON)
                         | (pstate == SWITCHING_OFF)).any()))
        if not ((has_event or changed) and more):
            break
    return {
        "completion": t_done,
        "rejected": fate == REJECTED,
        "pm_energy": energy,
        "iaas_total": float(total),
        "hvac": float(region.pue_minus_one * total),
        "t_end": float(t),
        "steps": steps,
        "overflow": overflow,
    }


# ---- the interface the harness calls (bench/reference/compare.py)

def cloud(config: dict, lane) -> Region:
    """The scenario of a configuration file for one lane of a cell."""
    base = _cloud(config, lane)
    return Region(**dataclasses.asdict(base),
                  pm_mem=float(config["cluster"]["pm_mem"]))


def replay(region: Region, trace: dict, *, finish_frac=1e-6, tie_window=0.0,
           precision="float64") -> dict:
    """:func:`simulate` over a whole trace dict (``arrival``, ``cores``,
    ``work``, ``mem``, ``util``)."""
    return simulate(region, trace["arrival"], trace["cores"], trace["work"],
                    trace["mem"], trace["util"], precision=precision,
                    finish_frac=finish_frac, tie_window=tie_window)
