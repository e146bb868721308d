"""The benchmark's copied trace generators are deterministic per seed,
differ between seeds, and still match the program's originals."""
import numpy as np
import pytest

from bench.generators import gwa


def _same(a, b):
    return all(np.array_equal(a[k], b[k]) for k in a)


@pytest.mark.parametrize("seed", [0, 12, 2**31 + 5])
def test_trace_deterministic_per_seed(seed):
    a = gwa.trace("das2", 300, seed=seed, max_cores=64)
    b = gwa.trace("das2", 300, seed=seed, max_cores=64)
    c = gwa.trace("das2", 300, seed=seed + 1, max_cores=64)
    assert _same(a, b) and not _same(a, c)
    assert np.all(np.diff(a["arrival"]) >= 0)


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_windows_deterministic_per_seed(seed):
    a = gwa.windows("das2", 700, 256, seed=seed, max_cores=64)
    b = gwa.windows("das2", 700, 256, seed=seed, max_cores=64)
    c = gwa.windows("das2", 700, 256, seed=seed + 1, max_cores=64)
    assert all(_same(x, y) for x, y in zip(a, b))
    assert not _same(a[0], c[0])
    assert (a[-1]["gid"][700 - 512:] == -1).all()
    flat = gwa.flatten(a)
    assert flat["arrival"].shape == (700,)
    assert np.all(np.diff(flat["arrival"]) >= 0)


def test_copies_match_the_program():
    from repro.core.trace import gwa_like_trace
    from repro.data.pipeline import gwa_window_stream
    mine = gwa.trace("das2", 200, seed=7, max_cores=64)
    orig = gwa_like_trace("das2", 200, seed=7)
    for k in ("arrival", "cores", "work"):
        np.testing.assert_array_equal(mine[k], np.asarray(getattr(orig, k)))
    mine_w = gwa.windows("das2", 300, 128, seed=9, max_cores=64)
    for a, b in zip(mine_w, gwa_window_stream("das2", 300, 128,
                                              max_cores=64, seed=9)):
        for k in ("arrival", "cores", "work", "gid"):
            np.testing.assert_array_equal(a[k], np.asarray(getattr(b, k)))
