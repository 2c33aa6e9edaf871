"""The traffic generator: the same seed gives the same inputs, for seeds
past 32 bits too, and the check dispatch keeps its first rounds' rows
out of its later rounds."""
import numpy as np
import pytest

from bench.data import Traffic, record, round_key


@pytest.mark.parametrize("seed", [2**31 + 11, 2**33 + 5])
def test_same_seed_same_inputs(seed):
    def draw(s):
        t = Traffic(s, 6, [100 + i for i in range(6)], 8, 2, 64)
        seq = t.check_schedule(8, 3)
        return seq, t.batches_for(seq), t.schedule(8), round_key(s, 1)

    a, b, c = draw(seed), draw(seed), draw(seed + 1)
    for x, y in zip(a, b):
        if isinstance(x, dict):
            for k in x:
                np.testing.assert_array_equal(x[k], y[k])
        else:
            np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[1]["tokens"], c[1]["tokens"])
    np.testing.assert_array_equal(a[1]["labels"],
                                  np.roll(a[1]["tokens"], -1, axis=-1))


def test_check_dispatch_first_rows_are_untouched_later():
    t = Traffic(2**31 + 1, 16, [10_000] * 16, 4, 4, 50)
    seq = t.check_schedule(8, 3)
    assert len(set(seq[:3].tolist())) == 3
    assert not set(seq[3:].tolist()) & set(seq[:3].tolist())


def test_records_come_from_seed_owner_and_index():
    r = record(2**31 + 2, 3, 17, 32, 50304)
    assert r.dtype == np.int32 and r.shape == (32,)
    assert r.min() >= 0 and r.max() < 50304
    np.testing.assert_array_equal(r, record(2**31 + 2, 3, 17, 32, 50304))
    assert not np.array_equal(r, record(2**31 + 2, 3, 18, 32, 50304))
