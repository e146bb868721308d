"""Entry driver: ``engine.simulate_stream``, one windowed replay per call.

Each call replays ``n_tasks`` trace tasks as fixed-shape windows of
``window`` tasks through the program's one compiled window step, from a
fresh carry.  The input pool holds the windows of each pool seed on the
device.  The warm-up runs the same windows with ``t_stop = 0``: every
window step and the final assembly run at their real shapes (as long as
the slot pool holds all the call's tasks), without replaying the trace."""
from __future__ import annotations

from bench.drivers import common, simulate
from bench.harness import BENCH, load_module


class Workload(simulate.Workload):

    def traces(self) -> list[dict]:
        t, c = self.cell.traffic, self.cell.config["cluster"]
        gen = load_module(BENCH / "generators" / f"{t['generator']}.py")
        self.windows_host = [
            gen.windows(t["family"], int(t["n_tasks"]), int(t["window"]),
                        seed=self.seed + j, max_cores=c["pm_cores"],
                        perf_core=c["perf_core"])
            for j in range(int(t["pool"]))]
        return [gen.flatten(w) for w in self.windows_host]

    def setup(self):
        import jax
        from repro.core import engine
        self.engine = engine
        self.spec, self.params = common.engine_cloud(self.cell.config,
                                                     self.lane_list)
        self.host = self.traces()
        dev = self.devices[0]
        self.pool = [[jax.device_put(common.trace_of(w), dev) for w in wins]
                     for wins in self.windows_host]
        self.params = jax.device_put(self.params, dev)
        self.n_tasks = int(self.cell.traffic["n_tasks"])

    def warm(self):
        super().warm()
        # the t = 0 warm-up reaches the real shapes only when the slot pool
        # holds every task of the call; otherwise warm up with a whole call
        q = self.engine.default_n_slots(self.spec,
                                        int(self.cell.traffic["window"]))
        if self.n_tasks > q:
            self.call_on(0, float("inf"))

    def entry(self, windows, t_stop):
        return self.engine.simulate_stream(self.spec, list(windows),
                                           self.params, t_stop=t_stop)

    def traced_call(self) -> dict:
        out = super().traced_call()
        out["program_word"] = "_stream_step"
        return out
