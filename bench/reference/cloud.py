"""Plain sequential reference of the simulated IaaS cloud.

A straightforward discrete-event loop, written from the scenario semantics
of DISSECT-CF (arXiv:1604.06581 §3.1-§3.5) and independent of the code
under test: it imports nothing of the program and uses none of its data.
It started as a copy of the repository's ``baseline/pydes.py`` (first-fit
VM placement on always-on PMs under the Table 1 linear power model) and
covers what the benchmark's cells run:

* VM schedulers: ``firstfit`` (arrival-ordered queue, first running PM
  with the cores free, the queue blocks on its head), ``nonqueuing`` (a
  request that cannot start now is rejected) and ``smallestfirst`` (the
  queue is ordered by requested cores, ties by task index).
* PM schedulers: ``alwayson`` (every PM runs from t = 0) and ``ondemand``
  (every PM starts off; wake the lowest-index OFF PMs to cover the queued
  core deficit against the free cores of running and booting PMs; switch
  a running PM that hosts no VM off when nothing is queued).
* A VM's life: image transfer from the repository over the host's network
  link (after the network latency), boot work on the host CPU, the task,
  then the VM is destroyed and its cores released.  A VM slot limit: a
  dispatch that finds no free slot sets ``overflow`` and blocks the queue.
* Rates: max-min fair sharing by progressive filling over the CPU, the
  PM network links, the repository link and each VM's own CPU.
* Power: per PM power state (off, switching on, running, switching off),
  constant draw except running, which is linear in CPU utilisation
  (paper Table 1), with the PM idle-draw scaled by ``idle_scale``.
  Energy is integrated exactly over every interval; the IaaS total is the
  sum over PMs and the HVAC meter is ``pue_minus_one`` times it.

Events that fall on the same instant are handled in one step, in this
order: flows finish, PM power transitions finish, the PM scheduler, the
VM scheduler.  A flow counts as finished once its remaining work is at
most ``finish_frac`` (``1e-6``) of its size plus ``1e-9``; every time and
amount is a float64.

Two knobs give the readings of that rule that a program keeping its clock
and remaining work in float32 may take at near-simultaneous events, whose
order such a program cannot resolve: ``finish_frac`` (a smaller one
finishes a flow only at its own instant) and ``tie_window`` (events up to
``tie_window`` times the clock after the step's instant are taken as
simultaneous with it).

``simulate(..., precision="bfloat16")`` is the comparison's control: the
same loop with every stored time, amount, rate and energy rounded to
bfloat16 (the clock always moves forward by at least one bfloat16 step).

As a reference module of the harness (a traffic file's ``"reference"``,
this one by default) it exposes ``cloud(config, lane)``, the scenario of
one lane of a cell, and ``replay(cloud, trace, ...)``, which runs it over
the whole trace dict that the cell's generator made.
"""
from __future__ import annotations

import dataclasses
import heapq
import math

import numpy as np

# PM power states and their order in a power table
OFF, SWITCHING_ON, RUNNING, SWITCHING_OFF = 0, 1, 2, 3
# what a VM's one flow carries
XFER, BOOT, TASK = 0, 1, 2
# task fates
PENDING, ACTIVE, DONE, REJECTED = 0, 1, 2, 3

BIG = 3.0e38


@dataclasses.dataclass(frozen=True)
class Cloud:
    """One scenario's cluster and policies (plain numbers only)."""

    n_pm: int
    n_vm: int
    pm_cores: float = 64.0
    perf_core: float = 1.0
    net_bw: float = 125.0
    repo_bw: float = 250.0
    image_mb: float = 100.0
    boot_work: float = 10.0
    latency_s: float = 0.001
    # per power state [off, switching on, running, switching off]
    p_min: tuple = (36.4, 483.1, 368.8, 409.2)
    p_max: tuple = (36.4, 483.1, 722.7, 409.2)
    linear: tuple = (False, False, True, False)
    boot_s: float = 200.0
    shutdown_s: float = 12.0
    idle_scale: float = 1.0
    pue_minus_one: float = 0.58
    vm_sched: str = "firstfit"
    pm_sched: str = "alwayson"

    def power_table(self):
        """``(p_min, p_max)`` per state after the idle-draw scale; the peak
        never falls below the scaled idle draw."""
        p_min = np.asarray(self.p_min, np.float64) * self.idle_scale
        p_max = np.maximum(np.asarray(self.p_max, np.float64), p_min)
        return p_min, p_max


def _bf16(x):
    import ml_dtypes
    return float(np.float32(x).astype(ml_dtypes.bfloat16).astype(np.float64))


def _bf16_array(x):
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def _bf16_up(t, x):
    """``x`` rounded to bfloat16, but strictly above ``t`` when ``x > t``."""
    import ml_dtypes
    y = _bf16(x)
    if x > t and y <= t:
        b = np.array([t], np.float32).astype(ml_dtypes.bfloat16)
        y = float(np.nextafter(b, np.array([np.inf], ml_dtypes.bfloat16))
                  [0].astype(np.float64))
    return y


def maxmin(flows, cap):
    """Max-min fair rates by progressive filling.

    ``flows`` is a list of ``(provider, consumer, limit)``; ``cap`` maps a
    spreader to its capacity.  All unfrozen flows rise together until a
    spreader saturates or a flow reaches its limit; those freeze."""
    n = len(flows)
    r = [0.0] * n
    unfrozen = set(range(n))
    while unfrozen:
        used, count = {}, {}
        for i, (p, c, _) in enumerate(flows):
            used[p] = used.get(p, 0.0) + r[i]
            used[c] = used.get(c, 0.0) + r[i]
            if i in unfrozen:
                count[p] = count.get(p, 0) + 1
                count[c] = count.get(c, 0) + 1
        head = {s: max(cap[s] - used[s], 0.0) / count[s] for s in count}
        room = {i: min(head[flows[i][0]], head[flows[i][1]],
                       max(flows[i][2] - r[i], 0.0)) for i in unfrozen}
        delta = min(room.values())
        for i in unfrozen:
            r[i] += delta
        tight = {i for i in unfrozen
                 if room[i] <= delta * (1 + 1e-12) + 1e-300}
        unfrozen -= tight
    return r


def simulate(cloud: Cloud, arrival, cores, work, *, precision="float64",
             finish_frac=1e-6, tie_window=0.0, max_steps=50_000_000):
    """Run one scenario to the end.

    Returns a dict: ``completion`` (f64[T], inf where not done),
    ``rejected`` (bool[T]), ``pm_energy`` (f64[P] J), ``iaas_total`` (J),
    ``hvac`` (J), ``t_end`` (s), ``steps`` and ``overflow``."""
    if precision == "float64":
        q = float

        def q_up(t, x):
            return x
    elif precision == "bfloat16":
        q, q_up = _bf16, _bf16_up
    else:
        raise ValueError(f"unknown precision {precision!r}")

    arrival = [q(a) for a in np.asarray(arrival, np.float64)]
    cores = [q(c) for c in np.asarray(cores, np.float64)]
    work = [q(w) for w in np.asarray(work, np.float64)]
    T, P, V = len(arrival), cloud.n_pm, cloud.n_vm
    p_min, p_max = cloud.power_table()
    span = np.where(np.asarray(cloud.linear), p_max - p_min, 0.0)
    cpu_cap = cloud.pm_cores * cloud.perf_core
    smallest_first = cloud.vm_sched == "smallestfirst"
    reject_unfit = cloud.vm_sched == "nonqueuing"
    if cloud.vm_sched not in ("firstfit", "nonqueuing", "smallestfirst"):
        raise ValueError(f"unknown VM scheduler {cloud.vm_sched!r}")
    if cloud.pm_sched not in ("alwayson", "ondemand"):
        raise ValueError(f"unknown PM scheduler {cloud.pm_sched!r}")
    ondemand = cloud.pm_sched == "ondemand"

    order = sorted(range(T), key=lambda i: (arrival[i], i))
    fate = [PENDING] * T
    t_done = [math.inf] * T
    queue = []          # arrived, pending task ids in (arrival, id) order
    next_arrival = 0    # position in `order`

    pstate = np.full(P, RUNNING if not ondemand else OFF, np.int64)
    pend = np.full(P, math.inf)
    free = np.full(P, float(cloud.pm_cores))
    n_hosted = np.zeros(P, np.int64)
    energy = np.zeros(P)
    total = 0.0
    free_slots = list(range(V))
    heapq.heapify(free_slots)
    # VM slot -> [kind, host, cores, task, remaining, size, limit, release]
    vms = {}
    overflow = False
    t = now = 0.0   # the clock, and the latest instant taken as simultaneous

    def admit():
        nonlocal next_arrival
        while next_arrival < T and arrival[order[next_arrival]] <= now:
            queue.append(order[next_arrival])
            next_arrival += 1

    def pm_sched():
        if not ondemand:
            return False
        q_cores = sum(cores[i] for i in queue)
        soon = (pstate == RUNNING) | (pstate == SWITCHING_ON)
        deficit = q_cores - float(free[soon].sum())
        k = math.ceil(max(deficit, 0.0) / cloud.pm_cores)
        wake = np.flatnonzero(pstate == OFF)[:k]
        idle = np.flatnonzero((pstate == RUNNING) & (n_hosted == 0)) \
            if not queue else np.empty(0, np.int64)
        pstate[wake] = SWITCHING_ON
        pend[wake] = q(t + cloud.boot_s)
        pstate[idle] = SWITCHING_OFF
        pend[idle] = q(t + cloud.shutdown_s)
        return bool(len(wake) or len(idle))

    def vm_sched():
        nonlocal overflow
        changed = False
        while queue:
            if smallest_first:
                head = min(queue, key=lambda i: (cores[i], i))
            else:
                head = queue[0]
            c = cores[head]
            fits = np.flatnonzero((pstate == RUNNING) & (free >= c))
            if c > cloud.pm_cores or (reject_unfit and not len(fits)):
                queue.remove(head)
                fate[head] = REJECTED
                changed = True
                continue
            if not len(fits):
                break
            if not free_slots:
                overflow = True
                break
            pm = int(fits[0])
            v = heapq.heappop(free_slots)
            queue.remove(head)
            fate[head] = ACTIVE
            free[pm] = q(free[pm] - c)
            n_hosted[pm] += 1
            vms[v] = [XFER, pm, c, head, q(cloud.image_mb),
                      q(cloud.image_mb), BIG, q(t + cloud.latency_s)]
            changed = True
        return changed

    def manage():
        admit()
        changed = pm_sched()
        return vm_sched() or changed

    manage()
    steps = 0
    while steps < max_steps:
        steps += 1
        # ---- rates of the live flows over this interval
        live = [v for v, f in vms.items()
                if now >= f[7] and f[4] > finish_frac * f[5] + 1e-9]
        cap, flows = {}, []
        for v in live:
            kind, host, c = vms[v][0], vms[v][1], vms[v][2]
            if kind == XFER:
                ends = (("repo",), ("net", host))
                cap[("repo",)] = cloud.repo_bw
                cap[("net", host)] = (cloud.net_bw if pstate[host] != OFF
                                      else 0.0)
            else:
                ends = (("cpu", host), ("vm", v))
                cap[("cpu", host)] = (cpu_cap if pstate[host] == RUNNING
                                      else 0.0)
                cap[("vm", v)] = max(c, 1.0) * cloud.perf_core
            flows.append((ends[0], ends[1], vms[v][6]))
        rate = [q(x) for x in maxmin(flows, cap)]
        # ---- event horizon
        dt = math.inf
        for v, r in zip(live, rate):
            if r > 0:
                dt = min(dt, vms[v][4] / r)
        for f in vms.values():
            if now < f[7]:
                dt = min(dt, f[7] - t)
        if next_arrival < T:
            dt = min(dt, arrival[order[next_arrival]] - t)
        trans = (pstate == SWITCHING_ON) | (pstate == SWITCHING_OFF)
        if trans.any():
            dt = min(dt, float(pend[trans].min()) - t)
        has_event = dt < math.inf
        dt = max(dt, 0.0) if has_event else 0.0
        t_new = q_up(t, t + dt)
        dt = t_new - t if precision != "float64" else dt
        # ---- energy over [t, t_new]
        util = np.zeros(P)
        for v, r in zip(live, rate):
            if vms[v][0] != XFER:
                util[vms[v][1]] += r
        util = np.clip(util / cpu_cap, 0.0, 1.0)
        power = p_min[pstate] + util * span[pstate]
        if precision == "float64":
            energy += power * dt
            total += float(power.sum()) * dt
        else:
            energy = _bf16_array(energy + _bf16_array(
                _bf16_array(power) * dt))
            total = q(total + q(q(float(power.sum())) * dt))
        # ---- drain and finish flows
        t = t_new
        now = t + t * tie_window
        done = []
        for v, r in zip(live, rate):
            f = vms[v]
            f[4] = q(max(f[4] - r * dt, 0.0))
            if f[4] <= finish_frac * f[5] + 1e-9 + r * (now - t):
                done.append(v)
        for v in done:
            f = vms[v]
            if f[0] == XFER:
                f[0], f[4], f[5], f[6], f[7] = (
                    BOOT, q(cloud.boot_work), q(cloud.boot_work), BIG, t)
            elif f[0] == BOOT:
                i = f[3]
                f[0], f[4], f[5], f[6], f[7] = (
                    TASK, work[i], work[i], q(cores[i] * cloud.perf_core), t)
            else:
                i = f[3]
                fate[i] = DONE
                t_done[i] = t
                free[f[1]] = q(free[f[1]] + f[2])
                n_hosted[f[1]] -= 1
                del vms[v]
                heapq.heappush(free_slots, v)
        # ---- PM power transitions
        ended = trans & (pend <= now)
        pstate[ended & (pstate == SWITCHING_ON)] = RUNNING
        pstate[ended & (pstate == SWITCHING_OFF)] = OFF
        pend[ended] = math.inf
        # ---- PM then VM scheduler
        changed = manage() or bool(done) or bool(ended.any())
        more = (any(f[4] > finish_frac * f[5] + 1e-9 for f in vms.values())
                or next_arrival < T or bool(queue)
                or bool(((pstate == SWITCHING_ON)
                         | (pstate == SWITCHING_OFF)).any()))
        if not ((has_event or changed) and more):
            break
    hvac = cloud.pue_minus_one * total
    return {
        "completion": np.asarray(t_done, np.float64),
        "rejected": np.asarray([s == REJECTED for s in fate]),
        "pm_energy": energy,
        "iaas_total": float(total),
        "hvac": float(hvac),
        "t_end": float(t),
        "steps": steps,
        "overflow": overflow,
    }


# ---- the interface the harness calls (bench/reference/compare.py)

def cloud(config: dict, lane) -> Cloud:
    """The scenario of a configuration file for one lane of a cell: its
    ``vm_sched``, ``pm_sched`` and ``idle_scale``."""
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


def replay(cloud: Cloud, trace: dict, *, finish_frac=1e-6, tie_window=0.0,
           precision="float64") -> dict:
    """:func:`simulate` over a whole trace dict (``arrival``, ``cores``,
    ``work``)."""
    return simulate(cloud, trace["arrival"], trace["cores"], trace["work"],
                    precision=precision, finish_frac=finish_frac,
                    tie_window=tie_window)
