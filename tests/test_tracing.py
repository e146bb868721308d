"""The engine's trace names and loop counters (``repro.core.tracing``,
``LoopCounters``): stage scopes in the lowered programs, host spans in a
profiler trace, and the per-lane round and gate counts of ``res.counters``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, tracing
from repro.core.trace import chunk_trace
from repro.sched import registry

SRC = str(pathlib.Path(__file__).resolve().parent.parent / "src")


def _trace(n=12, seed=3, t0=1.0):
    rng = np.random.default_rng(seed)
    return engine.Trace(
        arrival=jnp.asarray(t0 + np.sort(rng.uniform(0, 200, n)),
                            jnp.float32),
        cores=jnp.asarray(rng.integers(1, 3, n), jnp.float32),
        work=jnp.asarray(rng.uniform(5, 20, n), jnp.float32))


def _cloud(**kw):
    return engine.make_cloud(n_pm=3, n_vm=24, pm_cores=4.0, **kw)


def _grid(params):
    return [dataclasses.replace(params, vm_sched=v, pm_sched=p)
            for v in registry.names("vm") for p in ("alwayson", "ondemand")]


def _counts(c) -> dict:
    return {k: np.asarray(v) for k, v in c._asdict().items()}


# ---------------------------------------------------------------------------
# stage scopes: HLO metadata, present in every program, nothing else
# ---------------------------------------------------------------------------

def _op_paths(lowered) -> str:
    return "\n".join(re.findall(r'loc\("([^"]*)"',
                                lowered.as_text(debug_info=True)))


def _lowered_programs():
    spec, params = _cloud()
    tr = _trace()
    grid = engine.stack_params(_grid(params))
    wt = chunk_trace(tr, 4)
    carry = engine.init_stream(spec, 16, params)
    return {
        "_simulate_jit": engine._simulate_jit.lower(
            spec, tr, params, None, jnp.inf),
        "_simulate_batch_jit": engine._simulate_batch_jit.lower(
            spec, tr, grid, jnp.inf),
        "_stream_step": engine._stream_step.lower(
            spec, carry, wt.window(0), params, jnp.float32(0.0),
            jnp.float32(5.0), jnp.float32(jnp.inf)),
    }


@pytest.mark.parametrize("program", ["_simulate_jit", "_simulate_batch_jit",
                                     "_stream_step"])
def test_lowered_programs_carry_every_stage_scope(program):
    paths = _op_paths(_lowered_programs()[program])
    want = tracing.STAGE_SCOPES + (tracing.TERMINATION,
                                   tracing.MANAGEMENT_PASS)
    if program == "_stream_step":
        want += (tracing.STREAM_INSERT, tracing.STREAM_REPLAY,
                 tracing.STREAM_FLUSH)
    # a transform wraps the scope it is applied in: vmap(management_pass)
    missing = [s for s in want if not re.search(rf"[/(]{s}[)/]", paths)]
    assert not missing, f"{program}: no ops under {missing}"
    # every registered policy body is scoped by its name, under its stage;
    # always-on is the identity and has no op to scope
    for layer in ("pm", "vm"):
        for name in registry.names(layer):
            if name != "alwayson":
                assert re.search(rf"/{layer}_sched/(.*/)?{name}(/|$)",
                                 paths, re.M), name


def test_scopes_leave_the_program_unchanged(monkeypatch):
    """A scope is location metadata: the lowered program without debug
    information is the same text with every scope removed."""
    spec, params = _cloud()
    tr = _trace()

    def lowered():
        return jax.jit(lambda t, p: engine._simulate_impl(
            spec, t, p, None, jnp.float32(jnp.inf))).lower(tr, params)

    scoped = lowered().as_text()
    monkeypatch.setattr(tracing, "scope",
                        lambda name: contextlib.nullcontext())
    plain = lowered()
    assert "/advance/" not in _op_paths(plain)
    assert plain.as_text() == scoped


# ---------------------------------------------------------------------------
# loop counters
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def grid_runs():
    spec, params = _cloud()
    tr = _trace()
    pts = _grid(params)
    seq = [engine.simulate(spec, tr, p) for p in pts]
    batch = engine.simulate_batch(spec, tr, engine.stack_params(pts))
    return tr, seq, batch


def test_counters_bounded_by_iterations(grid_runs):
    tr, seq, _ = grid_runs
    for res in seq:
        c, n = _counts(res.counters), int(res.n_events)
        assert c["gate_opens"].shape == (4,)
        assert (c["gate_opens"] >= 0).all() and (c["gate_opens"] <= n).all()
        # when a task ran, the fair-share solve took rounds; with per-VM
        # meters the label propagation runs at least once per iteration
        if np.isfinite(np.asarray(res.completion)).any():
            assert c["fill_rounds"] >= 1
        assert c["label_rounds"] >= n


def test_serve_rounds_count_every_settled_task(grid_runs):
    """The first arrival is after t = 0, so every task is started or
    rejected inside the loop: one serving round each, plus the last,
    empty round of every call."""
    tr, seq, _ = grid_runs
    for res in seq:
        c = _counts(res.counters)
        settled = int(np.sum(np.isfinite(np.asarray(res.completion))
                             | np.asarray(res.rejected)))
        assert c["serve_rounds"] == settled + c["gate_opens"][3]


def test_no_arrivals_no_rounds():
    spec, params = _cloud()
    tr = engine.Trace(arrival=jnp.full((4,), jnp.inf, jnp.float32),
                      cores=jnp.ones((4,), jnp.float32),
                      work=jnp.ones((4,), jnp.float32))
    c = _counts(engine.simulate(spec, tr, params).counters)
    assert c["serve_rounds"] == 0 and c["fill_rounds"] == 0
    assert (c["gate_opens"] == 0).all()


def test_batched_counters_equal_sequential(grid_runs):
    """Every lane counts what its sequential run counts, but for the
    provider-only solves: under vmap a pass takes them only where no lane
    has a shared consumer live, so a lane counts at most its own."""
    _, seq, batch = grid_runs
    for i, res in enumerate(seq):
        for k, v in _counts(res.counters).items():
            got = _counts(batch.counters)[k][i]
            if k == "provider_only_solves":
                assert got <= v, (i, got, v)
            else:
                np.testing.assert_array_equal(v, got,
                                              err_msg=f"lane {i} {k}")


def test_compaction_keeps_the_counts():
    """The compacted solve and label propagation run the dense rounds."""
    spec, params = _cloud()
    tr = _trace()
    dense = engine.simulate(spec, tr, params)
    packed = engine.simulate(dataclasses.replace(spec, compact=8), tr,
                             params)
    for k, v in _counts(dense.counters).items():
        np.testing.assert_array_equal(v, _counts(packed.counters)[k], k)


def test_stream_counters_accumulate_across_windows():
    spec, params = _cloud()
    tr = _trace()
    mono = engine.simulate(spec, tr, params)
    wt = chunk_trace(tr, 3)
    carry = engine.init_stream(spec, 16, params)
    seen = [_counts(carry.counters)]
    t_prev = jnp.float32(0.0)
    for k in range(wt.n_windows):
        t_next = (engine._first_arrival(wt.window(k + 1))
                  if k + 1 < wt.n_windows else jnp.float32(jnp.inf))
        carry, _ = engine._stream_step(spec, carry, wt.window(k), params,
                                       t_prev, t_next, jnp.float32(jnp.inf))
        seen.append(_counts(carry.counters))
        t_prev = t_next
    for a, b in zip(seen, seen[1:]):
        assert all((b[k] >= a[k]).all() for k in a)
    assert seen[-1]["fill_rounds"] > seen[1]["fill_rounds"] > 0
    streamed = _counts(engine.simulate_stream(spec, wt, params,
                                              n_slots=16).counters)
    for k, v in seen[-1].items():
        np.testing.assert_array_equal(v, streamed[k], k)
    # the iterations are the monolithic ones; only the deferred
    # management passes differ
    for k in ("fill_rounds", "label_rounds"):
        assert streamed[k] == _counts(mono.counters)[k], k


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------

def _spans(tdir) -> list[tuple[int, int, str]]:
    from jax.profiler import ProfileData
    path = sorted(pathlib.Path(tdir).glob("plugins/profile/*/*.xplane.pb"))
    prof = ProfileData.from_file(str(path[-1]))
    plane = prof.find_plane_with_name("/host:CPU")
    return sorted((ev.start_ns, ev.end_ns, ev.name)
                  for ln in plane.lines for ev in ln.events
                  if ev.name.startswith("repro."))


def _inside(spans, child, parent) -> bool:
    """Every ``parent`` span holds a ``child`` span."""
    kids = [(s, e) for s, e, n in spans if n == child]
    outer = [(s, e) for s, e, n in spans if n == parent]
    return bool(outer) and all(any(ps <= s and e <= pe for s, e in kids)
                               for ps, pe in outer)


def test_profiler_records_nested_host_spans(tmp_path):
    spec, params = _cloud()
    tiny = dataclasses.replace(spec, compact=2)
    tr = _trace()
    burst = tr._replace(arrival=jnp.ones_like(tr.arrival))  # overflows tiny
    wt = chunk_trace(tr, 4)
    grid = engine.stack_params(_grid(params)[:2])
    # warm every program first, so the trace holds calls, not compiles
    runs = [lambda: engine.simulate(tiny, burst, params),
            lambda: engine.simulate_batch(spec, tr, grid),
            lambda: engine.simulate_stream(spec, wt, params)]
    with pytest.warns(RuntimeWarning, match="compaction bucket"):
        for run in runs:
            jax.block_until_ready(run())
        with jax.profiler.trace(str(tmp_path)):
            for run in runs:
                jax.block_until_ready(run())
    spans = _spans(tmp_path)
    for child in (tracing.LAUNCH, tracing.COMPACT_CHECK,
                  tracing.DENSE_REPLAY):
        assert _inside(spans, child, "repro.simulate"), child
    assert _inside(spans, tracing.COMPACT_CHECK, "repro.simulate_batch")
    for child in (tracing.STREAM_INIT, tracing.STREAM_WINDOW,
                  tracing.COMPACT_CHECK, tracing.STREAM_ASSEMBLE):
        assert _inside(spans, child, "repro.simulate_stream"), child
    assert _inside(spans, tracing.STREAM_NEXT_WINDOW, tracing.STREAM_WINDOW)
    assert _inside(spans, tracing.LAUNCH, tracing.STREAM_WINDOW)
    assert sum(n == tracing.STREAM_WINDOW for *_, n in spans) == \
        wt.n_windows


def test_sharded_pad_lanes_sliced_off_subprocess():
    """Forced 2-device topology: a 3-lane sweep is padded to 4 lanes; the
    result, counters included, keeps the 3 valid lanes, each equal to its
    sequential run, and the pad spans nest in the entry span."""
    code = """
import dataclasses, pathlib, tempfile
import jax, jax.numpy as jnp, numpy as np
from jax.profiler import ProfileData
from repro.core import engine
from repro.experiments import shard

assert jax.device_count() == 2, jax.devices()
spec, base = engine.make_cloud(n_pm=2, n_vm=8, pm_cores=4.0)
tr = engine.Trace(arrival=jnp.arange(1, 9, dtype=jnp.float32),
                  cores=jnp.ones((8,), jnp.float32),
                  work=jnp.full((8,), 5.0, jnp.float32))
pts = [dataclasses.replace(base, pm_sched=p, net_bw=jnp.float32(60.0 + i))
       for i, p in enumerate(("alwayson", "ondemand", "ondemand"))]
params = engine.stack_params(pts)
shard.simulate_batch_sharded(spec, tr, params)
with tempfile.TemporaryDirectory() as d:
    with jax.profiler.trace(d):
        got = jax.block_until_ready(
            shard.simulate_batch_sharded(spec, tr, params))
    path = sorted(pathlib.Path(d).glob("plugins/profile/*/*.xplane.pb"))
    plane = ProfileData.from_file(str(path[-1])).find_plane_with_name(
        "/host:CPU")
    spans = {ev.name: (ev.start_ns, ev.end_ns) for ln in plane.lines
             for ev in ln.events if ev.name.startswith("repro.")}
s, e = spans["repro.simulate_batch_sharded"]
for child in ("repro.shard.pad", "repro.launch", "repro.compact_check",
              "repro.shard.unpad"):
    cs, ce = spans[child]
    assert s <= cs <= ce <= e, child
assert got.counters.gate_opens.shape == (3, 4), got.counters
for i, p in enumerate(pts):
    one = engine.simulate(spec, tr, p)
    # a shard's lanes take the provider-only solves together
    own = int(got.counters.provider_only_solves[i])
    assert own <= int(one.counters.provider_only_solves)
    for a, b in zip(
            jax.tree.leaves(one.counters._replace(provider_only_solves=None)),
            jax.tree.leaves(got.counters._replace(provider_only_solves=None))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[i])
print("SHARDED_COUNTERS_OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=560)
    assert "SHARDED_COUNTERS_OK" in r.stdout, r.stdout + r.stderr[-2000:]
