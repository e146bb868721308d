"""Seeded GWA-like task traces, in numpy only.

Copied from the program's ``core/trace.py`` (``GWA_FAMILIES``,
``gwa_like_trace``) and ``data/pipeline.py`` (``gwa_window_stream``) so
that the benchmark's traffic cannot move when the program changes.  The
Grid Workloads Archive is not redistributable, so each archive system is
replaced by a moment-matched synthetic trace (Iosup et al., FGCS 2008):
lognormal runtimes, bursty Weibull interarrivals, power-of-two
parallelism.

Every function returns plain ``float32`` / ``int32`` numpy arrays; the
drivers place them on the device.
"""
from __future__ import annotations

import dataclasses
import zlib

import numpy as np


@dataclasses.dataclass(frozen=True)
class GWAFamily:
    name: str
    runtime_logmean: float     # lognormal ln-seconds
    runtime_logstd: float
    interarrival_scale: float  # Weibull scale (s)
    interarrival_shape: float  # < 1: bursty
    par_probs: tuple           # P(cores = 2**i)
    max_cores: int = 64


FAMILIES = {
    "das2":      GWAFamily("das2", 4.1, 1.9, 35.0, 0.55, (0.35, 0.2, 0.2, 0.15, 0.07, 0.03)),
    "grid5000":  GWAFamily("grid5000", 5.3, 2.2, 50.0, 0.50, (0.5, 0.15, 0.12, 0.1, 0.08, 0.05)),
    "nordugrid": GWAFamily("nordugrid", 7.2, 1.8, 120.0, 0.60, (0.9, 0.06, 0.03, 0.01)),
    "auvergrid": GWAFamily("auvergrid", 6.8, 1.7, 90.0, 0.65, (0.97, 0.02, 0.01)),
    "sharcnet":  GWAFamily("sharcnet", 6.9, 2.4, 25.0, 0.45, (0.55, 0.15, 0.12, 0.1, 0.05, 0.03)),
    "lcg":       GWAFamily("lcg", 5.9, 1.6, 8.0, 0.70, (1.0,)),
}


def _probs(fam: GWAFamily) -> np.ndarray:
    p = np.asarray(fam.par_probs, np.float64)
    return p / p.sum()


def trace(family: str, n_tasks: int, *, seed: int, max_cores: float,
          perf_core: float = 1.0, runtime_cap_s: float = 3.0e5) -> dict:
    """One whole trace: ``{"arrival", "cores", "work"}``, each f32[n_tasks],
    arrivals sorted.  Tasks wider than ``max_cores`` are cut to it (the
    paper filters them; for the families whose widest task fits, the two
    agree)."""
    fam = FAMILIES[family]
    rng = np.random.RandomState(
        (seed ^ zlib.crc32(family.encode()) & 0x7FFFFFFF) & 0xFFFFFFFF)
    inter = fam.interarrival_scale * rng.weibull(fam.interarrival_shape,
                                                 n_tasks)
    arrival = np.cumsum(inter).astype(np.float32)
    runtime = np.exp(rng.normal(fam.runtime_logmean, fam.runtime_logstd,
                                n_tasks))
    runtime = np.minimum(runtime, runtime_cap_s).astype(np.float32)
    probs = _probs(fam)
    cores = (2.0 ** rng.choice(len(probs), size=n_tasks, p=probs)
             ).astype(np.float32)
    cores = np.minimum(cores, np.float32(min(max_cores, fam.max_cores)))
    return {"arrival": arrival, "cores": cores,
            "work": (runtime * cores * np.float32(perf_core))
            .astype(np.float32)}


def windows(family: str, n_tasks: int, window: int, *, seed: int,
            max_cores: float, perf_core: float = 1.0,
            runtime_cap_s: float = 3.0e5) -> list[dict]:
    """A trace cut into fixed-shape windows of ``window`` tasks, each
    ``{"arrival", "cores", "work", "gid"}``; the last window is padded
    (``gid == -1``, ``arrival == inf``).  Window ``k`` draws from a Philox
    stream keyed on ``(seed, family, k)``; only the arrival offset carries
    across windows, so arrivals are sorted over the whole stream.  Copied
    from the program's ``data/pipeline.gwa_window_stream``."""
    fam = FAMILIES[family]
    probs = _probs(fam)
    cap = float(min(max_cores, fam.max_cores))
    fam_key = zlib.crc32(family.encode()) & 0xFFFFFFFF
    out, offset = [], 0.0
    for k, start in enumerate(range(0, n_tasks, window)):
        n = min(window, n_tasks - start)
        key = (seed & 0xFFFFFFFF) << 64 | fam_key << 32 | (k & 0xFFFFFFFF)
        rng = np.random.Generator(np.random.Philox(key=key))
        arrival = offset + np.cumsum(
            fam.interarrival_scale * rng.weibull(fam.interarrival_shape, n))
        offset = float(arrival[-1])
        runtime = np.minimum(
            np.exp(rng.normal(fam.runtime_logmean, fam.runtime_logstd, n)),
            runtime_cap_s)
        cores = np.minimum(2.0 ** rng.choice(len(probs), size=n, p=probs),
                           cap)
        pad = window - n

        def padded(x, fill, dtype):
            return np.concatenate([np.asarray(x, dtype),
                                   np.full((pad,), fill, dtype)])

        out.append({
            "arrival": padded(arrival, np.inf, np.float32),
            "cores": padded(cores, 0.0, np.float32),
            "work": padded(runtime * cores * perf_core, 0.0, np.float32),
            "gid": padded(np.arange(start, start + n), -1, np.int32),
        })
    return out


def flatten(wins: list[dict]) -> dict:
    """The whole trace behind a list of windows (valid entries only, in
    global-id order) — what the reference replays."""
    cat = {k: np.concatenate([w[k] for w in wins]) for k in wins[0]}
    keep = cat["gid"] >= 0
    order = np.argsort(cat["gid"][keep], kind="stable")
    return {k: cat[k][keep][order] for k in ("arrival", "cores", "work")}
