"""Jitted public wrappers for the Pallas kernels.

Each op compiles via Mosaic on a TPU and runs the kernel body in interpret
mode on any other backend (the CPU test suite); tests that pin a mode pass
``interpret=`` to the kernel itself.
"""
from __future__ import annotations

import jax


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def fill_stats_pallas(provider, consumer, r, live, unfrozen, perf):
    """Progressive-filling round statistics (see kernels/maxmin.py)."""
    from . import maxmin
    return maxmin.fill_stats(provider, consumer, r, live, unfrozen, perf,
                             interpret=_interpret())


def maxmin_solve_fits(n_flows: int, n_spreaders: int) -> bool:
    """Whether the fused full-solve kernel can take this problem size."""
    from . import maxmin
    return maxmin.solve_fits(n_flows, n_spreaders)


def maxmin_solve_pallas(provider, consumer, p_l, live, perf, *,
                        max_iters=64, rel_eps=1e-5):
    """Whole progressive-filling solve in one kernel (see kernels/maxmin.py)."""
    from . import maxmin
    return maxmin.maxmin_solve(provider, consumer, p_l, live, perf,
                               max_iters=max_iters, rel_eps=rel_eps,
                               interpret=_interpret())


def masked_min_pallas(cand, mask):
    """Masked scalar min — the event-horizon reduction (kernels/horizon.py)."""
    from . import horizon
    return horizon.masked_min(cand, mask, interpret=_interpret())

