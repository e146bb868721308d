"""Stage attribution and program-span labels (``bench/stage_reduce.py``)
on two small profiler traces recorded on one TPU v5e, each of one sliced
call of das2-500pm.trace1k: ``small.xplane.pb.gz`` from a program without
stage scopes or host spans, ``small_scoped.xplane.pb.gz`` from one with
them."""
import pathlib

import pytest

from bench import stage_reduce, trace_reduce

DATA = pathlib.Path(__file__).parent / "data"
STAGES = ("advance", "observe", "vm_lifecycle", "pm_power", "pm_sched",
          "vm_sched")


@pytest.fixture(scope="module")
def unscoped():
    return stage_reduce.read(DATA / "small.xplane.pb.gz")


@pytest.fixture(scope="module")
def scoped():
    return stage_reduce.read(DATA / "small_scoped.xplane.pb.gz")


def test_op_paths_read_from_event_metadata(unscoped):
    _, paths = unscoped
    # the engine's loop ops, and eager set-up ops with bare op names
    assert any(p.startswith("jit(_simulate_jit)/while/body/")
               for p in paths.values())


def test_unscoped_program_keeps_the_old_labels(unscoped):
    """Without the program's spans and scopes every op is ``unscoped`` and
    the gaps read as ``trace_reduce`` labels them."""
    profile, paths = unscoped
    old = trace_reduce.reduce(profile)
    new = stage_reduce.reduce(profile, paths)
    assert set(new["stages"]) == {stage_reduce.UNSCOPED}
    assert new["stages"]["unscoped"] == pytest.approx(old["busy_s"],
                                                      rel=1e-3)
    assert new["idle_gaps"] == old["breakdown"]["idle_gaps"]
    assert new["entry"] is None


def test_scoped_trace_attributes_every_stage(scoped):
    profile, paths = scoped
    red = stage_reduce.reduce(profile, paths)
    busy = trace_reduce.reduce(profile)["busy_s"]
    stages = red["stages"]
    assert set(STAGES) <= set(stages)
    total = sum(stages.values())
    assert total == pytest.approx(busy, rel=0.05)
    assert stages.get(stage_reduce.UNSCOPED, 0.0) <= 0.10 * total
    per_iter = stage_reduce.reduce(profile, paths, iterations=10)["stages"]
    assert per_iter["advance"] == pytest.approx(stages["advance"] / 10)


def test_scoped_trace_entry_span_and_labels(scoped):
    profile, paths = scoped
    red = stage_reduce.reduce(profile, paths)
    entry = red["entry"]
    assert entry["name"] == "repro.simulate"
    assert entry["busy_inside_share"] >= 0.99
    assert 0 <= entry["idle_s"] < entry["end_s"] - entry["start_s"]
    # a gap inside the entry span is the program's, never the benchmark's
    assert entry["gap_labels"]
    assert all(n.startswith(("repro.", "in-program:"))
               for n in entry["gap_labels"])


@pytest.mark.parametrize("path,stage", [
    ("jit(_simulate_jit)/while/body/advance/add", "advance"),
    ("jit(_simulate_jit)/while/body/observe/while/body/min:", "observe"),
    ("jit(_simulate_jit)/while/body/pm_sched/cond/branch_1_fun/cond/"
     "branch_1_fun/ondemand/and", "pm_sched/ondemand"),
    ("jit(_simulate_batch_jit)/vmap(while)/body/vm_sched/firstfit/while/"
     "body/lt", "vm_sched/firstfit"),
    ("jit(_simulate_jit)/while/body/vm_sched/clamp", "vm_sched"),
    ("jit(_simulate_batch_jit)/vmap(management_pass)/pm_sched/defrag/sub",
     "management_pass"),
    ("jit(_stream_step)/stream_replay/management_pass/vm_sched/eq",
     "stream_replay"),
    ("jit(_simulate_jit)/while", "unscoped"),
    ("", "unscoped"),
])
def test_stage_of(path, stage):
    assert stage_reduce.stage_of(path) == stage


def test_compiler_made_ops_take_the_next_scope():
    """A loop, conditional or copy the compiler made has no op_name, and a
    batched scatter it rewrote carries its loop's: each takes the scope
    of the next op with one of its own.  The loop instruction keeps its
    path."""
    dev = "/device:TPU:0"
    events = [(0, 20, "%while.0"), (0, 10, "%while.1"), (1, 2, "%fusion.1"),
              (3, 4, "%copy.1"), (4, 5, "%fusion.2"), (6, 7, "%fusion.3"),
              (8, 9, "%fusion.4"), (11, 12, "%copy.2")]
    paths = {(dev, "%while.0"): "jit(f)/vmap()/while:",
             (dev, "%fusion.1"): "jit(f)/while/body/advance/add",
             (dev, "%fusion.2"): "jit(f)/while/body/observe/mul",
             (dev, "%fusion.3"): "jit(f)/vmap()/while:",
             (dev, "%fusion.4"): "jit(f)/while/body/vm_lifecycle/eq"}
    assert stage_reduce._stages_by_name(dev, events, paths) == {
        "%while.0": "unscoped", "%fusion.1": "advance",
        "%fusion.2": "observe", "%fusion.4": "vm_lifecycle",
        "%while.1": "advance", "%copy.1": "observe",
        "%fusion.3": "vm_lifecycle"}


def test_labels_and_entry_spans():
    spans = [(0.0, 10.0, "bench.call"), (0.0, 9.0, "bench.dispatch"),
             (1.0, 8.0, "repro.simulate"), (1.0, 2.0, "repro.launch"),
             (2.0, 8.0, "repro.compact_check")]
    modules = [(2.5, 7.5, "jit__simulate_jit(123)")]
    assert stage_reduce.label(spans, modules, 1.5) == "repro.launch"
    assert stage_reduce.label(spans, modules, 5.0) == \
        "in-program:jit__simulate_jit"
    assert stage_reduce.label(spans, modules, 7.8) == "repro.compact_check"
    assert stage_reduce.label(spans, modules, 9.5) == "outside bench spans"
    assert [n for *_, n in spans if stage_reduce.is_entry_span(n)] == \
        ["repro.simulate"]
    assert not stage_reduce.is_entry_span("repro.stream.window")
