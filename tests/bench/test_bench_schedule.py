"""The open-loop schedule is fixed by the seed, gives every seed the same
work, and its lateness is reported."""

import numpy as np
import pytest

from bench.harness import schedule

SHARES = {"search": 0.855, "read_latest": 0.095, "insert": 0.05}


def test_same_seed_same_schedule():
    a = schedule.open_loop(2 ** 35 + 1, rate=80, seconds=30, shares=SHARES)
    b = schedule.open_loop(2 ** 35 + 1, rate=80, seconds=30, shares=SHARES)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


@pytest.mark.parametrize("seeds", [(1, 2), (7, 2 ** 33 + 9)])
def test_seeds_share_the_work_in_another_order(seeds):
    (da, ka), (db, kb) = (schedule.open_loop(s, rate=80, seconds=30,
                                             shares=SHARES) for s in seeds)
    assert len(da) == len(db) == 2400
    assert not np.array_equal(da, db)
    assert sorted(np.diff(da)) == pytest.approx(sorted(np.diff(db)),
                                                abs=0.5)
    for kind, share in SHARES.items():
        assert (ka == kind).sum() == (kb == kind).sum() == round(
            share * 2400)
    assert da[0] == 0 and np.all(np.diff(da) > 0)
    assert da[-1] == pytest.approx(30, rel=0.05)


def test_bad_mix_is_refused():
    with pytest.raises(ValueError):
        schedule.open_loop(1, rate=10, seconds=1, shares={"search": 0.5})
    with pytest.raises(ValueError):
        schedule.open_loop(1, rate=0, seconds=1, shares={"search": 1.0})


def test_lateness_reported():
    due = np.array([0.0, 0.1, 0.2, 0.3])
    sent = due + np.array([0.001, 0.002, 0.003, 0.050])
    late = schedule.lateness_ms(due, sent)
    assert late["max"] == pytest.approx(50.0)
    assert late["p50"] == pytest.approx(2.5)
    assert late["p95"] > late["p50"]
