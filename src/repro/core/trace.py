"""Workload traces (paper §4.2): synthetic generator + GWA-like families.

*Synthetic* (Fig. 11 knobs): total task count, max parallel tasks, spread
(window within which a parallel batch starts) and per-task length range.
Batches are separated by a gap long enough for the previous batch to finish
— exactly the paper's generator ("the trace generator will insert a gap long
enough for all the previously generated tasks to finish").

*GWA-like*: the Grid Workloads Archive is not redistributable offline, so
we generate moment-matched synthetic traces per archive system (DAS-2,
Grid'5000, NorduGrid, AuverGrid, SHARCNet, LCG) from published summary
statistics (Iosup et al., FGCS 2008): lognormal runtimes, bursty Weibull
interarrivals, power-of-two parallelism mixes.  DESIGN.md records this as a
deliberate deviation (no network access).
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import NamedTuple

import numpy as np

from .engine import Trace


def synthetic_trace(
    n_tasks: int,
    parallel: int,
    spread_s: float = 10.0,
    length_range: tuple[float, float] = (10.0, 90.0),
    cores: int = 1,
    perf_core: float = 1.0,
    seed: int = 0,
) -> Trace:
    """Paper Fig. 11 synthetic load: batches of ``parallel`` tasks whose
    starts fall within ``spread_s``, lengths uniform in ``length_range``."""
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    lo, hi = length_range
    arrival = np.zeros(n_tasks, np.float32)
    length = rng.uniform(lo, hi, n_tasks).astype(np.float32)
    offs = rng.uniform(0.0, spread_s, n_tasks).astype(np.float32)
    batch = np.arange(n_tasks) // max(parallel, 1)
    # gap long enough for all previously generated tasks to finish
    gap = hi + spread_s
    arrival = batch.astype(np.float32) * gap + offs
    return Trace(
        arrival=jnp.asarray(arrival),
        cores=jnp.full((n_tasks,), float(cores), jnp.float32),
        work=jnp.asarray(length * cores * perf_core),
    )


@dataclasses.dataclass(frozen=True)
class GWAFamily:
    """Moment parameters for one archive system (published marginals)."""

    name: str
    runtime_logmean: float    # lognormal ln-seconds
    runtime_logstd: float
    interarrival_scale: float  # Weibull scale (s)
    interarrival_shape: float  # <1 -> bursty
    par_probs: tuple[float, ...]  # P(cores = 2**i)
    max_cores: int = 64


GWA_FAMILIES: dict[str, GWAFamily] = {
    # parameters approximate the archive's published per-system statistics
    "das2":      GWAFamily("das2", 4.1, 1.9, 35.0, 0.55, (0.35, 0.2, 0.2, 0.15, 0.07, 0.03)),
    "grid5000":  GWAFamily("grid5000", 5.3, 2.2, 50.0, 0.50, (0.5, 0.15, 0.12, 0.1, 0.08, 0.05)),
    "nordugrid": GWAFamily("nordugrid", 7.2, 1.8, 120.0, 0.60, (0.9, 0.06, 0.03, 0.01)),
    "auvergrid": GWAFamily("auvergrid", 6.8, 1.7, 90.0, 0.65, (0.97, 0.02, 0.01)),
    "sharcnet":  GWAFamily("sharcnet", 6.9, 2.4, 25.0, 0.45, (0.55, 0.15, 0.12, 0.1, 0.05, 0.03)),
    "lcg":       GWAFamily("lcg", 5.9, 1.6, 8.0, 0.70, (1.0,)),
}


def gwa_like_trace(
    family: str,
    n_tasks: int,
    *,
    perf_core: float = 1.0,
    max_cores: int | None = None,
    runtime_cap_s: float = 3.0e5,
    seed: int = 0,
) -> Trace:
    """A GWA-moment-matched trace for ``family`` (see GWA_FAMILIES)."""
    import jax.numpy as jnp

    fam = GWA_FAMILIES[family]
    # stable per-family seed: crc32, not hash() — identical traces in every
    # process, no PYTHONHASHSEED pinning needed for golden comparisons
    rng = np.random.RandomState(
        seed ^ zlib.crc32(family.encode()) & 0x7FFFFFFF)
    inter = fam.interarrival_scale * rng.weibull(fam.interarrival_shape, n_tasks)
    arrival = np.cumsum(inter).astype(np.float32)
    runtime = np.exp(rng.normal(fam.runtime_logmean, fam.runtime_logstd,
                                n_tasks))
    runtime = np.minimum(runtime, runtime_cap_s).astype(np.float32)
    probs = np.asarray(fam.par_probs, np.float64)
    probs = probs / probs.sum()
    pow2 = rng.choice(len(probs), size=n_tasks, p=probs)
    cores = (2.0 ** pow2).astype(np.float32)
    cap = float(max_cores if max_cores is not None else fam.max_cores)
    cores = np.minimum(cores, cap)
    return Trace(
        arrival=jnp.asarray(arrival),
        cores=jnp.asarray(cores),
        work=jnp.asarray(runtime * cores * perf_core),
    )


class WindowedTrace(NamedTuple):
    """A trace chunked on the task axis (DESIGN.md §8): ``n_windows``
    windows of one fixed shape ``[W]``, the last one padded (``gid == -1``
    marks a pad entry: ``arrival == inf``, zero cores/work).  The fixed
    window shape is the whole point — :func:`repro.core.engine.simulate_stream`
    compiles once per ``(spec, W, Q)``, never per total trace length."""

    arrival: object  # f32[n_windows, W]
    cores: object    # f32[n_windows, W]
    work: object     # f32[n_windows, W]
    gid: object      # i32[n_windows, W]; -1 = pad
    mem: object = None   # f32[n_windows, W] where the trace has mem
    util: object = None  # f32[n_windows, W] where the trace has util

    @property
    def n_windows(self) -> int:
        return self.arrival.shape[0]

    @property
    def window_size(self) -> int:
        return self.arrival.shape[1]

    @property
    def n_tasks(self) -> int:
        """Number of real (non-pad) tasks across all windows."""
        return int(np.sum(np.asarray(self.gid) >= 0))

    def window(self, k: int) -> Trace:
        """Window ``k`` as a gid-carrying :class:`Trace`."""
        return Trace(*(None if x is None else x[k] for x in self))

    def windows(self):
        """Iterate the windows in stream order (``__iter__`` stays the
        NamedTuple field iteration jax's pytree flattening relies on)."""
        for k in range(self.n_windows):
            yield self.window(k)


def chunk_trace(trace: Trace, window: int) -> WindowedTrace:
    """Chunk a time-sorted :class:`Trace` into fixed-shape windows for
    :func:`repro.core.engine.simulate_stream` (DESIGN.md §8).

    The last window is padded up to ``window`` tasks and masked
    (``gid == -1``, ``arrival == inf``); global ids are the original task
    indices, so a streamed replay's per-task outputs align with the
    monolithic trace axis.  An unsorted trace is stably sorted by arrival
    first (ties keep their original relative order) — the streaming
    sentinel (first arrival of the next window) is only the true horizon
    minimum when arrivals never go back in time, and each task carries
    its *original* index as ``gid``, so per-task outputs still line up
    with the caller's trace axis after the sort.
    """
    W = int(window)
    if W <= 0:
        raise ValueError(f"window must be positive, got {window}")
    arrival = np.asarray(trace.arrival, np.float32)
    T = arrival.shape[0]
    if T == 0:
        raise ValueError("chunk_trace needs a non-empty trace")
    import jax.numpy as jnp

    gid = (np.asarray(trace.gid, np.int32) if trace.gid is not None
           else np.arange(T, dtype=np.int32))
    order = (np.argsort(arrival, kind="stable")
             if np.any(np.diff(arrival) < 0) else np.arange(T))
    n_windows = -(-T // W)
    pad = n_windows * W - T

    def chunk(x, fill, dtype):
        if x is None:
            return None
        x = np.asarray(x, dtype)[order]
        x = np.concatenate([x, np.full((pad,), fill, dtype)])
        return jnp.asarray(x.reshape(n_windows, W))

    return WindowedTrace(
        arrival=chunk(arrival, np.inf, np.float32),
        cores=chunk(trace.cores, 0.0, np.float32),
        work=chunk(trace.work, 0.0, np.float32),
        gid=chunk(gid, -1, np.int32),
        mem=chunk(trace.mem, 0.0, np.float32),
        util=chunk(trace.util, 0.0, np.float32),
    )


def filter_fitting(trace: Trace, pm_cores: float) -> Trace:
    """Drop tasks larger than one PM (paper §4.2.2 scalability experiment:
    'tasks that could not fit … were automatically filtered out, never more
    than 6%')."""
    import jax.numpy as jnp
    import numpy as np2

    keep = np2.asarray(trace.cores) <= pm_cores
    return Trace(*(None if x is None or name == "gid"
                   else jnp.asarray(np2.asarray(x)[keep])
                   for name, x in zip(Trace._fields, trace)))
