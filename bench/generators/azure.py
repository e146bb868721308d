"""Seeded VM request traces shaped on the Azure Public Dataset VM traces,
in numpy only.

Cortez et al., "Resource Central: Understanding and Predicting Workloads
for Improved Resource Management in Large Cloud Platforms" (SOSP 2017),
and the ``vmtable`` of github.com/Azure/AzurePublicDataset (V1 2017, V2
2019) give the shape: VMs ask for cores and memory, most are small,
lifetimes are heavy-tailed from minutes to the whole trace, long-running
VMs hold most of the reserved core-hours, average CPU use lies well
below the cores reserved, and creations follow a daily cycle.  The
traces are not in the repository, so each family draws from
distributions whose parameters it states (the configuration file lists
them under ``assumed``).

A trace is a region observed from ``t = 0``: the VMs live at that
instant (length-biased lifetimes, each with a remaining life uniform
within its lifetime) arrive at ``t = 0``, then new VMs arrive as a
Poisson process whose rate follows the day, from its trough.  A VM's
``work`` is its remaining life times ``util * cores * perf_core``, so
that a VM running uncontended at its utilisation ends on its lifetime.

Every function returns plain ``float32`` numpy arrays, arrivals sorted;
the drivers place them on the device.
"""
from __future__ import annotations

import dataclasses
import math
import zlib

import numpy as np

DAY = 86400.0


@dataclasses.dataclass(frozen=True)
class AzureFamily:
    name: str
    cores: dict            # P(cores = k)
    mem_per_core: dict     # P(GB per core = m)
    short_share: float     # mixture weight of the short lifetimes
    short_median_s: float  # lognormal median of the short lifetimes
    short_sigma: float
    long_median_s: float   # lognormal median of the long lifetimes
    long_sigma: float
    lifetime_cap_s: float
    util_beta: tuple       # Beta(a, b) of the average CPU use
    util_min: float
    live: int              # VMs live at t = 0
    diurnal_depth: float   # rate(t) = mean * (1 - depth * cos(2 pi t / day))


FAMILIES = {
    "azure-256pm": AzureFamily(
        name="azure-256pm",
        cores={1: 0.35, 2: 0.35, 4: 0.18, 8: 0.09, 16: 0.03},
        mem_per_core={2: 0.25, 4: 0.50, 8: 0.25},
        short_share=0.6, short_median_s=1800.0, short_sigma=1.2,
        long_median_s=DAY, long_sigma=1.5, lifetime_cap_s=7 * DAY,
        util_beta=(0.6, 2.4), util_min=0.01, live=3072,
        diurnal_depth=0.5),
}


def _rng(family: str, seed: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64(np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFFFFFFFFFF, zlib.crc32(family.encode())])))


def _capped_lognormal_mean(median: float, sigma: float, cap: float) -> float:
    """``E[min(X, cap)]`` for ``X`` lognormal with this median and sigma."""
    mu, lc = math.log(median), math.log(cap)

    def phi(z):
        return 0.5 * (1.0 + math.erf(z / math.sqrt(2.0)))

    return (math.exp(mu + sigma ** 2 / 2) * phi((lc - mu - sigma ** 2) / sigma)
            + cap * (1.0 - phi((lc - mu) / sigma)))


def mean_lifetime(fam: AzureFamily) -> float:
    """The mean of the capped lifetime mixture, in seconds."""
    return (fam.short_share * _capped_lognormal_mean(
                fam.short_median_s, fam.short_sigma, fam.lifetime_cap_s)
            + (1 - fam.short_share) * _capped_lognormal_mean(
                fam.long_median_s, fam.long_sigma, fam.lifetime_cap_s))


def _lifetimes(fam: AzureFamily, rng, n: int) -> np.ndarray:
    short = rng.random(n) < fam.short_share
    median = np.where(short, fam.short_median_s, fam.long_median_s)
    sigma = np.where(short, fam.short_sigma, fam.long_sigma)
    life = median * np.exp(sigma * rng.standard_normal(n))
    return np.minimum(life, fam.lifetime_cap_s)


def _choice(rng, table: dict, n: int) -> np.ndarray:
    keys = np.asarray(list(table), np.float64)
    p = np.asarray(list(table.values()), np.float64)
    return rng.choice(keys, size=n, p=p / p.sum())


def _arrivals(fam: AzureFamily, rng, n: int, rate: float) -> np.ndarray:
    """``n`` arrivals of the Poisson process of rate ``rate * (1 - depth *
    cos(2 pi t / DAY))`` from ``t = 0``, by thinning a process of the peak
    rate."""
    peak = rate * (1.0 + fam.diurnal_depth)
    out, t = [], 0.0
    while len(out) < n:
        cand = t + np.cumsum(rng.exponential(1.0 / peak, 2 * n))
        lam = rate * (1.0 - fam.diurnal_depth * np.cos(2 * np.pi * cand / DAY))
        keep = cand[rng.random(cand.size) * peak < lam]
        out.extend(keep[:n - len(out)].tolist())
        t = float(cand[-1])
    return np.asarray(out, np.float64)


def trace(family: str, n_tasks: int, *, seed: int, max_cores: float,
          perf_core: float = 1.0) -> dict:
    """One region trace of ``n_tasks`` VMs: ``{"arrival", "cores", "work",
    "mem", "util"}``, each f32[n_tasks], arrivals sorted.  The family's
    ``live`` VMs arrive at ``t = 0``, the rest by the daily cycle at the
    rate that keeps that population live on average.  Requests wider than
    ``max_cores`` are cut to it."""
    fam = FAMILIES[family]
    if n_tasks < fam.live:
        raise ValueError(f"{family} needs at least {fam.live} tasks, "
                         f"got {n_tasks}")
    rng = _rng(family, seed)
    n_new = n_tasks - fam.live
    # the live population: lifetimes drawn length-biased (a VM is live at
    # t = 0 in proportion to its lifetime), remaining life uniform within
    pool = _lifetimes(fam, rng, 64 * fam.live)
    life0 = rng.choice(pool, size=fam.live, p=pool / pool.sum())
    remaining = np.concatenate([rng.random(fam.live) * life0,
                                _lifetimes(fam, rng, n_new)])
    arrival = np.concatenate([
        np.zeros(fam.live),
        _arrivals(fam, rng, n_new, fam.live / mean_lifetime(fam))])
    cores = np.minimum(_choice(rng, fam.cores, n_tasks), max_cores)
    mem = cores * _choice(rng, fam.mem_per_core, n_tasks)
    util = np.clip(rng.beta(*fam.util_beta, n_tasks), fam.util_min, 1.0)
    f32 = np.float32
    util = util.astype(f32)
    cores = cores.astype(f32)
    work = (remaining.astype(f32) * util * cores * f32(perf_core)).astype(f32)
    return {"arrival": arrival.astype(f32), "cores": cores, "work": work,
            "mem": mem.astype(f32), "util": util}
