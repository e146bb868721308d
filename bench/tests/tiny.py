"""Cells of BENCHMARK.json cut to a size a CPU test run holds."""
import copy

from bench import harness


def tiny_cell(workload: str, n_tasks: int = 96) -> harness.Cell:
    cell = harness.load_cell(workload)
    cell.config = copy.deepcopy(cell.config)
    cell.traffic = dict(cell.traffic)
    cell.config["cluster"].update(n_pm=6, n_vm=64)
    cell.traffic.update(n_tasks=n_tasks, pool=2, trace_tasks=10)
    if "window" in cell.traffic:
        cell.traffic["window"] = 32
        cell.config["cluster"]["n_vm"] = 128
    if "idle_scales" in cell.traffic:
        cell.traffic["idle_scales"] = [0.6, 1.2]
    return cell
