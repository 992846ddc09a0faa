"""The plain reference against NumPy float64 at a tiny size, and the
controls' lower precisions against it."""

import numpy as np
import pytest

from bench.harness import data, reference


@pytest.fixture(scope="module")
def corpus():
    x = np.asarray(data.lowrank(2 ** 34 + 3, 3000, 32, n_clusters=8,
                                rank=4))
    q, c = x[:200], x[200:]
    exact = ((q[:, None, :].astype(np.float64) - c[None]) ** 2).sum(-1)
    return q, c, exact


def test_exact_topk_matches_float64(corpus, monkeypatch):
    q, c, exact = corpus
    monkeypatch.setattr(reference, "CORPUS_BLOCK", 512)   # several blocks
    ids, d2 = (np.asarray(a) for a in reference.exact_topk(q, c, 10))
    want = np.sort(exact, axis=1)[:, :10]
    assert np.abs(d2 - want).max() < 1e-5
    got = np.take_along_axis(exact, ids, axis=1)
    assert np.abs(got - want).max() < 1e-5


def test_valid_rows_only(corpus):
    q, c, exact = corpus
    valid = np.arange(len(c)) % 3 != 0
    ids, d2 = (np.asarray(a) for a in reference.exact_topk(
        q, c, 10, valid=valid))
    assert valid[ids].all()
    want = np.sort(np.where(valid, exact, np.inf), axis=1)[:, :10]
    assert np.abs(d2 - want).max() < 1e-5


def test_too_few_valid_rows_pad():
    c = np.eye(4, dtype=np.float32)
    ids, d2 = (np.asarray(a) for a in reference.exact_topk(
        c[:1], c, 3, valid=np.array([True, False, True, False])))
    assert ids.tolist() == [[0, 2, -1]] and np.isinf(d2[0, 2])


def test_pair_d2(corpus):
    q, c, exact = corpus
    ids = np.array([[0, 5, -1]] * len(q))
    got = reference.pair_d2(q, c, ids)
    assert np.abs(got[:, :2] - exact[:, [0, 5]]).max() < 1e-5
    assert np.isnan(got[:, 2]).all()


@pytest.mark.parametrize("precision,lo,hi", [("highest", 0, 2e-6),
                                             ("high", 2e-6, 1e-4),
                                             ("bf16", 1e-4, 1e-1)])
def test_lower_precisions_are_lower(corpus, precision, lo, hi):
    q, c, exact = corpus
    ids, d2 = (np.asarray(a) for a in reference.exact_topk(
        q, c, 10, precision=precision))
    gap = np.abs(d2 - np.take_along_axis(exact, ids, axis=1)).max()
    assert lo <= gap < hi


def test_quantised_keeps_cells_and_loses_precision(corpus):
    _, c, _ = corpus
    two = np.asarray(reference.quantised(c, c, bits=2))
    assert all(len(np.unique(two[:, j])) <= 4 for j in range(c.shape[1]))
    four = np.asarray(reference.quantised(c, c, bits=4))
    assert np.abs(four - c).mean() < np.abs(two - c).mean()
