"""Reduce a JAX profiler trace to the benchmark's device numbers.

The trace of one sliced call (``capture``) is read with
``jax.profiler.ProfileData`` and reduced (``reduce``) to:

* ``window_s`` — the traced call's host span (``bench.call``);
* ``busy_s`` — per device, the union of the intervals in which an XLA op
  ran (lines ``XLA Ops`` and ``Async XLA Ops``) inside that window,
  averaged over the devices;
* ``device_ops`` — the ten ops of line ``XLA Ops`` with the most device
  self time (an op's time less that of the ops nested in it, as a loop's
  body in the loop), summed by HLO instruction name; an asynchronous copy
  spans the compute it overlaps, so it is counted busy but not ranked;
* ``idle_gaps`` — the ten longest gaps between device ops inside the
  window, each labelled by the innermost benchmark host span (``bench.*``)
  that covers its midpoint;
* ``program_runs`` — ``[start_s, end_s]`` of each execution (line
  ``XLA Modules``) of the program whose name holds the given word.

``reduce_raw`` merges into these what ``bench/stage_reduce.py`` reads from
the same trace's raw bytes: ``stages`` (device seconds per loop iteration
of each of the program's stage scopes), ``entry`` (its entry span, or
None), ``idle_by_label`` (device idle seconds by the host span they fell
in), and the idle gaps labelled with the program's ``repro.*`` spans in
place of the ``bench.*`` ones.

Times are seconds on the profiler's clock; host and device events share
it.
"""
from __future__ import annotations

import pathlib

HOST_PLANE = "/host:CPU"
OP_LINES = ("XLA Ops", "Async XLA Ops")
SPAN_PREFIX = "bench."
CALL_SPAN = "bench.call"


def _device_planes(profile):
    return [p for p in profile.planes
            if p.name.startswith("/device:") and "CPU" not in p.name]


def _line(plane, name):
    return next((ln for ln in plane.lines if ln.name == name), None)


def op_name(event_name: str) -> str:
    """``fusion.354`` from ``%fusion.354 = f32[2048]{...} fusion(...)``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def _self_times(events):
    """``{name: seconds}`` of self time: each op's duration less that of
    the ops that start and end inside it."""
    out, stack = {}, []
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack and e <= stack[-1][1]:
            parent = stack[-1][2]
            out[parent] = out.get(parent, 0.0) - (e - s)
        out[name] = out.get(name, 0.0) + (e - s)
        stack.append((s, e, name))
    return out


def _union(intervals):
    """Merge ``[(start, end)]`` into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _host_spans(profile):
    plane = profile.find_plane_with_name(HOST_PLANE)
    spans = []
    if plane is None:
        return spans
    for ln in plane.lines:
        for ev in ln.events:
            if ev.name.startswith(SPAN_PREFIX):
                spans.append((ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name))
    return spans


def _label(spans, t):
    covering = [(e - s, name) for s, e, name in spans
                if s <= t <= e and name != CALL_SPAN]
    return min(covering)[1] if covering else "outside bench spans"


def reduce(profile, program: str | None = None, top: int = 10) -> dict:
    """The numbers above from a ``ProfileData``; ``None`` when the trace
    holds no device op."""
    spans = _host_spans(profile)
    calls = [(s, e) for s, e, name in spans if name == CALL_SPAN]
    devices = _device_planes(profile)
    per_device, op_time, gaps, runs = [], {}, [], []
    for plane in devices:
        ivals = []
        for line in OP_LINES:
            ops = _line(plane, line)
            events = [] if ops is None else [
                (ev.start_ns * 1e-9, ev.end_ns * 1e-9, op_name(ev.name))
                for ev in ops.events]
            ivals += [(s, e) for s, e, _ in events]
            if line != "XLA Ops":
                continue
            for name, t in _self_times(events).items():
                op_time[name] = op_time.get(name, 0.0) + t
        if not ivals:
            continue
        lo = min(s for s, _ in calls) if calls else min(s for s, _ in ivals)
        hi = max(e for _, e in calls) if calls else max(e for _, e in ivals)
        merged = [[max(s, lo), min(e, hi)] for s, e in _union(ivals)
                  if e > lo and s < hi]
        busy = sum(e - s for s, e in merged)
        per_device.append((busy, hi - lo))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, _label(spans, (a + b) / 2)))
        mods = _line(plane, "XLA Modules")
        if program and mods is not None and not runs:
            runs = [(ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                    for ev in mods.events if program in ev.name]
    if not per_device:
        return None
    busy_s = sum(b for b, _ in per_device) / len(per_device)
    window_s = max(w for _, w in per_device)
    ops_sorted = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    n_dev = len(per_device)
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "n_devices": n_dev,
        "breakdown": {
            "device_ops": [[name, t / n_dev] for name, t in ops_sorted],
            "idle_gaps": [[name, t] for t, name in
                          sorted(gaps, key=lambda g: -g[0])[:top]],
        },
        "program_runs": sorted(runs),
    }


def raw_bytes(path) -> bytes:
    """The serialized ``XSpace`` of an ``.xplane.pb`` file, gzipped or
    not."""
    import gzip
    path = pathlib.Path(path)
    raw = path.read_bytes()
    return gzip.decompress(raw) if path.suffix == ".gz" else raw


def load(path) -> object:
    """A ``ProfileData`` from an ``.xplane.pb`` file, gzipped or not."""
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(raw_bytes(path))


def newest_xplane(tdir) -> pathlib.Path:
    found = sorted(pathlib.Path(tdir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no profiler trace under {tdir}")
    return found[-1]


def reduce_raw(raw: bytes, program: str | None = None,
               iterations: int | None = None) -> dict:
    """``reduce`` of a serialized trace merged with ``stage_reduce``'s
    stages (per iteration when ``iterations`` is given), entry span and
    labelled idle gaps; the reduction of a trace with no device op holds
    ``None`` and empty lists."""
    from jax.profiler import ProfileData

    from bench import stage_reduce
    profile = ProfileData.from_serialized_xspace(raw)
    red = reduce(profile, program=program) or {
        "busy_s": None, "window_s": None, "n_devices": 0,
        "breakdown": {"device_ops": [], "idle_gaps": []},
        "program_runs": []}
    staged = stage_reduce.reduce(profile, stage_reduce.op_paths(raw),
                                 iterations) or {
        "stages": {}, "entry": None, "idle_by_label": {}, "idle_gaps": []}
    red["iterations"] = iterations
    red["stages"] = staged["stages"]
    red["entry"] = staged["entry"]
    red["idle_by_label"] = staged["idle_by_label"]
    red["breakdown"]["idle_gaps"] = staged["idle_gaps"]
    return red


def capture(traced_call, tdir) -> dict:
    """Run ``traced_call`` under the profiler, writing the trace to
    ``tdir``; return ``reduce_raw`` of it with what the call reports:
    ``iterations`` (loop iterations it ran) and ``program_word`` (a word
    of the program whose executions ``program_runs`` lists, or None)."""
    import jax
    jax.profiler.start_trace(str(tdir))
    try:
        out = traced_call()
    finally:
        jax.profiler.stop_trace()
    return reduce_raw(raw_bytes(newest_xplane(tdir)),
                      program=out.get("program_word"),
                      iterations=out["iterations"])
