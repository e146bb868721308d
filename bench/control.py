#!/usr/bin/env python3
"""The comparison's control: the plain reference computed in bfloat16
(the cell's reference module's ``replay(..., precision="bfloat16")``),
put in the program's place, for every lane of every input of a cell.

    python3 bench/control.py --workload <cell> --seeds 1 2 3

It needs no accelerator and is not part of a benchmark run.  For each
seed it prints the numbers that ``bench/reference/compare.py`` compares,
each beside its limit, and whether the control was caught (it has to
be: some number over its limit).
"""
import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench.reference import compare  # noqa: E402


def control_numbers(cell, seed: int) -> dict:
    """Worst numbers of the bfloat16 reference against the reference over
    the cell's whole input pool and every lane."""
    driver = harness.load_module(harness.BENCH / "drivers"
                                 / f"{cell.driver}.py")
    wl = driver.Workload(cell, seed, devices=None)
    wl.host = wl.traces()
    ref = wl.reference
    calls = []
    for item, tr in enumerate(wl.host):
        answers = []
        for lane in wl.lane_list:
            got = ref.replay(ref.cloud(cell.config, lane), tr,
                             precision="bfloat16")
            answers.append({**got, "n_events": got["steps"]})
        calls.append(harness.Call(
            item=item, start=0.0, end=0.0, tasks=0, lanes=wl.lanes,
            events=[0], dense_replays=0, failed=0, error=None,
            answers=answers))
    return compare.check(cell.checks, ref, wl.reference_jobs(calls), calls)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    for seed in args.seeds:
        out = control_numbers(cell, seed)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "caught": not out["correct"],
                          "numbers": out["numbers"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
