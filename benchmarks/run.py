"""Benchmark runner: one module per paper table/figure + the batched
experiment trajectories.  ``python -m benchmarks.run [--full]``."""
from __future__ import annotations

import argparse
import json
import time
import traceback
from pathlib import Path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow on CPU)")
    ap.add_argument("--only", default="",
                    help="comma list of module names to run")
    ap.add_argument("--out", default="experiments/bench")
    args = ap.parse_args(argv)
    quick = not args.full

    from benchmarks import (consolidation_bench, energy_overhead,
                            ensemble_bench, microbench_steps, pareto_bench,
                            scaling, sharing_perf, streaming_bench,
                            sweep_bench, traces_bench, validation)
    modules = {
        "validation": validation,        # Fig 7/8/9/10
        "sharing_perf": sharing_perf,    # Fig 12 / Table 3
        "scaling": scaling,              # Fig 13 / Fig 15
        "traces": traces_bench,          # Fig 14
        "energy_overhead": energy_overhead,  # Fig 16/17
        "sweep": sweep_bench,            # batched 8-point scenario sweep
        "pareto": pareto_bench,          # Pareto-front experiment (sharded)
        "ensemble": ensemble_bench,      # trace-ensemble experiment (sharded)
        "consolidation": consolidation_bench,  # in-loop migration policy
        "streaming": streaming_bench,    # windowed datacenter-year replay
        "microbench_steps": microbench_steps,  # K coalescing tuner (§7)
    }
    if args.only:
        keep = set(args.only.split(","))
        modules = {k: v for k, v in modules.items() if k in keep}

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    failures = 0
    for name, mod in modules.items():
        t0 = time.time()
        try:
            rows = mod.run(quick=quick)
            status = "ok"
        except Exception:
            rows = [{"error": traceback.format_exc()[-2000:]}]
            status = "FAIL"
            failures += 1
        wall = time.time() - t0
        # One canonical artifact per module.  The perf-trajectory modules
        # (batched sweep, scaling grid, sharded experiment kinds, the
        # consolidation tournament, streaming replay) write the
        # ``BENCH_``-prefixed files CI uploads and tools/check_bench.py
        # guards; everything else writes a bare ``{name}.json``.  A failed
        # trajectory run never clobbers its artifact — the traceback goes
        # to ``{name}.error.json`` (and stdout) instead.
        trajectory = name in ("sweep", "scaling", "pareto", "ensemble",
                              "consolidation", "streaming")
        if trajectory and status != "ok":
            (outdir / f"{name}.error.json").write_text(
                json.dumps(rows, indent=1))
        else:
            out_name = f"BENCH_{name}.json" if trajectory else f"{name}.json"
            (outdir / out_name).write_text(json.dumps(rows, indent=1))
        print(f"== {name} [{status}] ({wall:.1f}s) " + "=" * 40)
        for row in rows if isinstance(rows, list) else [rows]:
            print("  " + json.dumps(row)[:240])
    print(f"\nbenchmarks complete, {failures} failures; "
          f"results in {outdir}/")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
