"""Entry driver: ``engine.simulate``, one scenario per call.

Each call replays one whole trace of the input pool (cycled in order) to
its end.  The warm-up runs the same compiled program with ``t_stop = 0``,
which loads it without replaying the trace."""
from __future__ import annotations

from bench.drivers import common
from bench.harness import BENCH, load_module, load_reference


class Workload:
    batched = False

    def __init__(self, cell, seed: int, devices):
        self.cell, self.seed, self.devices = cell, seed, devices
        self.lane_list = common.lanes_of(cell.config, cell.traffic)
        self.lanes = len(self.lane_list)
        self.max_events = int(cell.config["max_events"])
        self.reference = load_reference(cell)

    # -- inputs --------------------------------------------------------
    def traces(self) -> list[dict]:
        """The host input pool: one whole trace per pool seed (the run's
        seed, seed + 1, ...)."""
        t, c = self.cell.traffic, self.cell.config["cluster"]
        gen = load_module(BENCH / "generators" / f"{t['generator']}.py")
        return [gen.trace(t["family"], int(t["n_tasks"]),
                          seed=self.seed + j, max_cores=c["pm_cores"],
                          perf_core=c["perf_core"])
                for j in range(int(t["pool"]))]

    def setup(self):
        import jax
        from repro.core import engine
        self.engine = engine
        self.spec, self.params = common.engine_cloud(self.cell.config,
                                                     self.lane_list)
        self.host = self.traces()
        dev = self.devices[0]
        self.pool = [jax.device_put(common.trace_of(h), dev)
                     for h in self.host]
        self.params = jax.device_put(self.params, dev)
        self.n_tasks = int(self.cell.traffic["n_tasks"])

    def entry(self, trace, t_stop):
        return self.engine.simulate(self.spec, trace, self.params,
                                    t_stop=t_stop)

    def warm(self):
        self.call_on(0, 0.0)

    # -- the window ----------------------------------------------------
    def call_on(self, item: int, t_stop: float):
        tr = self.pool[item]
        return common.timed_call(
            item, self.n_tasks, self.lanes, self.batched, self.max_events,
            lambda: self.entry(tr, t_stop))

    def call(self, k: int):
        import math
        return self.call_on(k % len(self.pool), math.inf)

    def traced_call(self) -> dict:
        """A slice of one call for the profiler: the first pool trace cut
        at the arrival of task ``trace_tasks``."""
        n = int(self.cell.traffic["trace_tasks"])
        t_stop = float(self.host[0]["arrival"][n])
        call = self.call_on(0, t_stop)
        return {"iterations": max(call.events), "program_word": None}

    # -- the check -----------------------------------------------------
    def reference_jobs(self, calls) -> dict:
        items = sorted({c.item for c in calls})
        return {(i, b): (self.reference.cloud(self.cell.config, ln),
                         self.host[i])
                for i in items for b, ln in enumerate(self.lane_list)}

    def release(self):
        self.pool = self.params = None
