"""The ``provider_only_share`` reader on a hand-built window: the
program's ``provider_only_solves`` counter over the lanes' summed
``n_events``, in percent, and nothing from a program without it."""
import pytest

from bench import harness


def _ctx(counters=True):
    def answer(n, **c):
        return {"n_events": n, "counters": c if counters else {}}
    calls = [harness.Call(
        item=0, start=0.0, end=1.0, tasks=40, lanes=2, events=[10, 30],
        dense_replays=0, failed=0, error=None,
        answers=[answer(10, provider_only_solves=8),
                 answer(30, provider_only_solves=24)])]
    return {"calls": calls}


def test_provider_only_share_reader():
    reader = harness.load_module(harness.BENCH / "metrics"
                                 / "provider_only_share.py")
    assert reader.read(_ctx()) == pytest.approx(80.0, rel=1e-12)
    assert reader.read(_ctx(counters=False)) is None
