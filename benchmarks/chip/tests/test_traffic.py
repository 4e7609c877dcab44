"""Traffic and bookkeeping that every run depends on."""
import numpy as np

import pytest

import datagen
import reference
from generator import Mix


def _mix(profile, rows=(1,), batches=()):
    return Mix(profile=tuple(profile), rows=tuple(rows), reader="app",
               query_pool=100, batches=tuple(batches))


def test_arrivals_same_gaps_other_order():
    mix = _mix([(1.0, 0.9)])
    a = mix.arrivals(200.0, 30.0, 2 ** 31 + 5)
    b = mix.arrivals(200.0, 30.0, 7)
    assert len(a) == len(b) == 5400
    ga, gb = np.diff(np.append(a, 30.0)), np.diff(np.append(b, 30.0))
    assert np.allclose(np.sort(ga), np.sort(gb))
    assert not np.allclose(ga, gb)
    assert a[0] == 0.0 and a[-1] < 30.0


def test_burst_profile_follows_its_phases():
    mix = _mix([(0.5, 3.0), (1.5, 0.2)])
    t = mix.arrivals(100.0, 51.0, 11)
    assert mix.mean_load == (0.5 * 3.0 + 1.5 * 0.2) / 2.0
    assert len(t) == round(26 * 0.5 * 300 + (25 * 1.5 + 0.5) * 20)
    on = (t % 2.0) < 0.5
    # each phase gets its own rate: 300/s for 0.5 s, 20/s for 1.5 s
    assert abs(on.sum() / (26 * 0.5) - 300) < 15
    assert abs((~on).sum() / (25 * 1.5 + 0.5) - 20) < 3
    assert np.all(np.diff(t) >= 0) and t[-1] < 51.0


def test_requests_and_batches_same_multiset_other_order():
    mix = _mix([(1.0, 1.0)], rows=(1, 4), batches=(512, 64, 8))
    s1, p1 = mix.requests(10, 3)
    s2, p2 = mix.requests(10, 2 ** 40 + 3)
    assert sorted(s1) == sorted(s2) == [1] * 5 + [4] * 5
    assert p1.max() < 100 and not np.array_equal(s1, s2)
    it = mix.writer_batches(5)
    first = [next(it) for _ in range(6)]
    assert sorted(first[:3]) == [8, 64, 512] and first[3:] == first[:3]
    assert mix.tile_rows(4) == [1, 2, 3, 4]
    assert _mix([(1.0, 1.0)], rows=(1, 9)).tile_rows(4) == [1, 2, 3, 4, 9]


def test_mix_file_refuses_what_cannot_run():
    base = {"query_pool": 8, "searches": {
        "tenant": "app", "rows": [1], "profile": [{"seconds": 1, "load": 1}]}}
    Mix.from_json(base, 1024)
    for bad in ({"writer": {"tenant": "w", "batches": [2048]}},
                {"searches": {**base["searches"], "profile": [
                    {"seconds": 1, "load": 0}]}},
                {"searches": {**base["searches"], "rows": [0]}}):
        with pytest.raises(ValueError):
            Mix.from_json({**base, **bad}, 1024)


def test_ledger_epochs_and_wrapped_ids():
    led = reference.Ledger(n_max=8)
    led.added(0, 6, epoch=1)
    led.removed(0, 4, epoch=3)
    led.added(6, 12, epoch=2)          # serials 8..11 reuse ids 0..3
    assert led.live_at(1).sum() == 6
    assert led.live_at(2).sum() == 12
    assert led.live_at(3).sum() == 8
    labels = np.array([[0, 9, 4, -1], [0, 1, 6, 7]])
    got = led.serial_of(labels, np.array([1, 3]))
    # epoch 1: id 0 is serial 0; id 9 is out of range; epoch 3: id 0 is
    # serial 8 (serial 0 removed), ids 6 and 7 are serials 6 and 7
    assert got.tolist() == [[0, -1, 4, -1], [8, 9, 6, 7]]


def test_rows_made_again_from_the_seed():
    m1 = datagen.Mixture(2 ** 33 + 1, 16, 4, 0.3, 64)
    m2 = datagen.Mixture(2 ** 33 + 1, 16, 4, 0.3, 64)
    x = np.asarray(m1.take(50, 64))
    assert np.array_equal(x, np.asarray(m2.take(50, 64)))
    assert np.array_equal(x[14:], np.asarray(m1.batch(1))[:50])
