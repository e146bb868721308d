"""Attribute a profiler trace of the engine to its loop stages and host
spans: what ``bench/trace_reduce.py`` cannot see from op names alone.

The program names its work (``repro.core.tracing``): a ``jax.named_scope``
per loop stage, and ``repro.*`` host spans around its host wrappers.
Scopes reach the trace as each op's ``op_name`` path (the ``tf_op`` stat
of the device plane's event metadata, e.g.
``jit(_simulate_jit)/while/body/advance/add``), which
``jax.profiler.ProfileData`` does not expose; this module reads the raw
``XSpace`` with a schema of the few fields it needs.  From one trace it
reduces:

* ``stages`` — device self time per stage scope (the first scope in an
  op's path; ``pm_sched/<policy>`` and ``vm_sched/<policy>`` one level
  down), in seconds per loop iteration, averaged over the devices; ops
  under no scope are ``unscoped``.  An op the compiler made (a while
  loop, conditional or copy with no path; a copy or rewritten batched
  scatter labelled with its enclosing loop's path) takes the scope of
  the next op that has one: a loop's first body op, the op a copy
  feeds;
* ``idle_gaps`` — the longest gaps between device ops inside the traced
  call, labelled ``in-program:<module>`` when they lie inside an
  execution of a program (the host did not cause them), else by the
  innermost covering ``bench.*`` or ``repro.*`` host span; a trace with
  no ``repro.*`` span (a program without them) keeps the labels of
  ``trace_reduce``;
* ``idle_by_label`` — all of those gaps' device idle time summed by
  label, averaged over the devices;
* ``entry`` — the program's outermost ``repro.<entry>`` span: its
  window, the device idle time inside it, the labels of the idle gaps
  that lie inside it, and the share of device busy time that lies inside
  it.

The scope and span names are copied from the program, not imported: a
benchmark reads the trace, never the code under test.

    python3 bench/stage_reduce.py <trace dir | .xplane.pb[.gz]> \\
        [--iterations N]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import re
import sys
from collections import Counter

if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from bench import trace_reduce  # noqa: E402

STAGES = ("advance", "observe", "vm_lifecycle", "pm_power", "pm_sched",
          "vm_sched", "termination", "management_pass", "stream_insert",
          "stream_replay", "stream_flush")
POLICY_STAGES = ("pm_sched", "vm_sched")
UNSCOPED = "unscoped"
SPAN_PREFIXES = ("bench.", "repro.")
CALL_SPAN = trace_reduce.CALL_SPAN
# the program's entry spans, ``repro.<entry>``; its other spans hold a dot
# after ``repro.<word>`` or are one of these phase names
PHASE_SPANS = ("repro.launch", "repro.compact_check", "repro.dense_replay")
# structural components JAX adds to an op_name path; a transform wraps the
# scope it is applied in (``vmap(management_pass)``)
_STRUCTURAL = re.compile(r"^(while|body|cond|branch_\d+_fun|.*\(.*\))$")
_WRAPPED = re.compile(r"^\w+\((\w+)\)$")


def _unwrap(part: str) -> str:
    m = _WRAPPED.match(part)
    return m.group(1) if m else part


# ---------------------------------------------------------------- XSpace

def _xspace_class():
    """A message class for the fields of ``tsl/profiler/protobuf/
    xplane.proto`` read here (field numbers as there); the parser skips
    the rest."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xspace.proto", package="bench_xspace", syntax="proto3")

    def message(name, *fields):
        m = fdp.message_type.add(name=name)
        for fname, number, ftype, repeated, type_name in fields:
            m.field.add(name=fname, number=number, type=ftype,
                        label=(F.LABEL_REPEATED if repeated
                               else F.LABEL_OPTIONAL),
                        type_name=type_name)

    msg = F.TYPE_MESSAGE
    message("XStat", ("metadata_id", 1, F.TYPE_INT64, False, None),
            ("str_value", 5, F.TYPE_STRING, False, None),
            ("ref_value", 7, F.TYPE_UINT64, False, None))
    message("XEventMetadata", ("id", 1, F.TYPE_INT64, False, None),
            ("name", 2, F.TYPE_STRING, False, None),
            ("stats", 5, msg, True, ".bench_xspace.XStat"))
    message("XStatMetadata", ("id", 1, F.TYPE_INT64, False, None),
            ("name", 2, F.TYPE_STRING, False, None))
    message("EventEntry", ("key", 1, F.TYPE_INT64, False, None),
            ("value", 2, msg, False, ".bench_xspace.XEventMetadata"))
    message("StatEntry", ("key", 1, F.TYPE_INT64, False, None),
            ("value", 2, msg, False, ".bench_xspace.XStatMetadata"))
    message("XPlane", ("name", 2, F.TYPE_STRING, False, None),
            ("event_metadata", 4, msg, True, ".bench_xspace.EventEntry"),
            ("stat_metadata", 5, msg, True, ".bench_xspace.StatEntry"))
    message("XSpace", ("planes", 1, msg, True, ".bench_xspace.XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xspace.XSpace"))


def op_paths(raw: bytes) -> dict:
    """``{(plane name, event name): op_name path}`` of every device event
    whose metadata carries a ``tf_op`` stat."""
    space = _xspace_class()()
    space.ParseFromString(raw)
    out = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        tf_op = [k for k, v in stat_names.items() if v == "tf_op"]
        if not tf_op:
            continue
        for entry in plane.event_metadata:
            for st in entry.value.stats:
                if st.metadata_id != tf_op[0]:
                    continue
                out[(plane.name, entry.value.name)] = (
                    st.str_value or stat_names.get(st.ref_value, ""))
    return out


def read(path) -> tuple[object, dict]:
    """``(ProfileData, op_paths)`` of a trace file, gzipped or not, or of
    the newest trace under a directory written by ``jax.profiler``."""
    from jax.profiler import ProfileData
    path = pathlib.Path(path)
    if path.is_dir():
        path = trace_reduce.newest_xplane(path)
    raw = trace_reduce.raw_bytes(path)
    return ProfileData.from_serialized_xspace(raw), op_paths(raw)


def stage_us(traced: dict | None, stage: str) -> float | None:
    """Device microseconds per loop iteration of one stage scope in a
    reduced trace, its policy bodies (``<stage>/<policy>``) included;
    None where the trace holds no op of that scope."""
    stages = (traced or {}).get("stages") or {}
    times = [t for k, t in stages.items()
             if k == stage or k.startswith(stage + "/")]
    return 1e6 * sum(times) if times else None


# ------------------------------------------------------------ attribution

def stage_of(path: str) -> str:
    """The scope an op's ``op_name`` path belongs to: its first stage
    scope, with the policy one level down under ``pm_sched`` /
    ``vm_sched``; ``unscoped`` when it has none."""
    parts = [_unwrap(p) for p in path.split(":", 1)[0].split("/")]
    for i, part in enumerate(parts):
        if part not in STAGES:
            continue
        if part in POLICY_STAGES:
            # the last component is the op itself, never a scope
            sub = [p for p in parts[i + 1:-1] if not _STRUCTURAL.match(p)]
            if sub:
                return f"{part}/{sub[0]}"
        return part
    return UNSCOPED


def _own_path(paths, plane_name, name) -> str | None:
    """The op's ``op_name`` path, or None for an op the compiler made: it
    has no path, or (a copy, a batched scatter it rewrote) the path of
    the loop it sits in, which only the loop instruction itself owns."""
    path = paths.get((plane_name, name))
    if not path:
        return None
    if (path.split(":", 1)[0].endswith("while")
            and not trace_reduce.op_name(name).startswith("while")):
        return None
    return path


def _stages_by_name(plane_name, events, paths) -> dict:
    """``{op name: scope}`` of a device's ops; an op the compiler made
    takes the scope of the next op (by start) that has one, by majority
    over its executions."""
    out = {}
    for _, _, name in events:
        path = _own_path(paths, plane_name, name)
        if path and name not in out:
            out[name] = stage_of(path)
    votes, nxt = {}, None
    for _, _, name in sorted(events, key=lambda x: (-x[0], x[1])):
        if name in out:
            nxt = out[name]
        elif nxt is not None:
            votes.setdefault(name, Counter())[nxt] += 1
    out.update({n: c.most_common(1)[0][0] for n, c in votes.items()})
    return out


def _spans(profile):
    plane = profile.find_plane_with_name(trace_reduce.HOST_PLANE)
    if plane is None:
        return []
    return [(ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
            for ln in plane.lines for ev in ln.events
            if ev.name.startswith(SPAN_PREFIXES)]


def is_entry_span(name: str) -> bool:
    """``repro.simulate``, ``repro.simulate_stream``, …: the span a host
    entry point opens around one call."""
    return (name.startswith("repro.") and name.count(".") == 1
            and name not in PHASE_SPANS)


def label(spans, modules, t) -> str:
    """What a device idle gap at time ``t`` waited on: a running program
    (``in-program:<module>``), else the innermost covering host span
    other than ``bench.call``."""
    for s, e, name in modules:
        if s <= t <= e:
            return "in-program:" + name.split("(", 1)[0]
    covering = [(e - s, name) for s, e, name in spans
                if s <= t <= e and name != CALL_SPAN]
    return min(covering)[1] if covering else "outside bench spans"


def reduce(profile, paths: dict, iterations: int | None = None,
           top: int = 10) -> dict | None:
    """The numbers above from a ``ProfileData`` and its ``op_paths``;
    ``stages`` are per iteration when ``iterations`` is given, else
    totals.  ``None`` when the trace holds no device op."""
    spans = _spans(profile)
    calls = [(s, e) for s, e, n in spans if n == CALL_SPAN]
    entries = [(s, e, n) for s, e, n in spans if is_entry_span(n)]
    # the outermost entry span: the one the traced call opened
    entry = max(entries, key=lambda x: x[1] - x[0]) if entries else None
    stage_t, gaps, per_device, entry_labels = {}, [], [], set()
    for plane in trace_reduce._device_planes(profile):
        ops = trace_reduce._line(plane, "XLA Ops")
        if ops is None:
            continue
        events = [(ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
                  for ev in ops.events]
        if not events:
            continue
        scope_of = _stages_by_name(plane.name, events, paths)
        for name, t in trace_reduce._self_times(events).items():
            key = scope_of.get(name, UNSCOPED)
            stage_t[key] = stage_t.get(key, 0.0) + t
        ivals = [(s, e) for s, e, _ in events]
        asyn = trace_reduce._line(plane, "Async XLA Ops")
        if asyn is not None:
            ivals += [(ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                      for ev in asyn.events]
        lo = min(s for s, _ in calls) if calls else min(s for s, _ in ivals)
        hi = max(e for _, e in calls) if calls else max(e for _, e in ivals)
        merged = [[max(s, lo), min(e, hi)]
                  for s, e in trace_reduce._union(ivals)
                  if e > lo and s < hi]
        mods = trace_reduce._line(plane, "XLA Modules")
        modules = [] if mods is None or entry is None else [
            (ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
            for ev in mods.events]
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        gaps += [(b - a, label(spans, modules, (a + b) / 2)) for a, b in idle]
        busy = sum(e - s for s, e in merged)
        if entry is None:
            per_device.append((busy, None, None))
            continue
        es, ee = entry[0], entry[1]
        busy_in = sum(max(0.0, min(e, ee) - max(s, es)) for s, e in merged)
        idle_in = sum(max(0.0, min(b, ee) - max(a, es)) for a, b in idle)
        per_device.append((busy, busy_in, idle_in))
        entry_labels |= {label(spans, modules, (a + b) / 2)
                         for a, b in idle if es <= a and b <= ee}
    if not per_device:
        return None
    n_dev = len(per_device)
    div = n_dev * (iterations or 1)
    by_label = {}
    for t, name in gaps:
        by_label[name] = by_label.get(name, 0.0) + t / n_dev
    out = {
        "n_devices": n_dev,
        "stages": {k: v / div for k, v in
                   sorted(stage_t.items(), key=lambda kv: -kv[1])},
        "idle_gaps": [[name, t] for t, name in
                      sorted(gaps, key=lambda g: -g[0])[:top]],
        "idle_by_label": by_label,
        "entry": None,
    }
    if entry is not None:
        busy = sum(b for b, _, _ in per_device)
        out["entry"] = {
            "name": entry[2], "start_s": entry[0], "end_s": entry[1],
            "idle_s": sum(i for _, _, i in per_device) / n_dev,
            "gap_labels": sorted(entry_labels),
            "busy_inside_share": (sum(b for _, b, _ in per_device) / busy
                                  if busy else None),
        }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Per-stage device time and labelled idle gaps of a "
                    "profiler trace of the engine.")
    ap.add_argument("trace", help="trace directory or .xplane.pb[.gz]")
    ap.add_argument("--iterations", type=int, default=None,
                    help="loop iterations of the traced call (stages are "
                         "then seconds per iteration)")
    args = ap.parse_args(argv)
    profile, paths = read(args.trace)
    print(json.dumps(reduce(profile, paths, args.iterations)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
