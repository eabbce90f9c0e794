"""The traffic generator: reproducible per seed, inside its windows."""
import numpy as np
import pytest

from harness import traffic
from harness.registry import Registry

GAS = {"publishTask": 1, "submitLocalModel": 2,
       "calculateObjectiveRep": 3, "calculateSubjectiveRep": 4}


def _gen(seed, n=60, **upd):
    reg = Registry()
    mix = dict(reg.traffic("table1-uniform-1k"), **upd)
    return traffic.generate(mix, seed, reg, n_windows=n,
                            n_accounts=1 << 12, l1_gas=GAS)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**40 + 3, -9])
def test_same_seed_same_inputs(seed):
    a, b = _gen(seed), _gen(seed)
    for x, y in ((a.t, b.t), (a.fn, b.fn), (a.sender, b.sender),
                 (a.offsets, b.offsets)):
        np.testing.assert_array_equal(x, y)


def test_seeds_differ_and_windows_hold_their_txs():
    a, b = _gen(1), _gen(2)
    assert not np.array_equal(a.sender[:100], b.sender[:100])
    for w in range(len(a)):
        t, gas, fn, sender = a.window(w)
        assert len(t) > 850
        assert t.min() >= w and t.max() < w + 1
        assert np.all(np.diff(t) >= 0)
        np.testing.assert_array_equal(gas, np.array([1, 2, 3, 4])[fn])
        assert sender.min() >= 0 and sender.max() < 1 << 12
    # the mix's shares hold over 60k txs
    share = np.bincount(a.fn, minlength=4) / len(a.fn)
    np.testing.assert_allclose(share, [0.02, 0.55, 0.28, 0.15], atol=0.01)


def test_zipf_senders_are_skewed_and_reproducible():
    law = {"law": "zipf", "theta": 0.99}
    a, b = _gen(3, senders=law), _gen(3, senders=law)
    np.testing.assert_array_equal(a.sender, b.sender)
    counts = np.sort(np.bincount(a.sender, minlength=1 << 12))[::-1]
    # theta 0.99 over 4096 accounts: the hottest account takes about
    # 1/H(4096, 0.99) ~ 11% of the txs, the hottest 1% about 45%
    assert 0.08 < counts[0] / counts.sum() < 0.14
    assert 0.35 < counts[:41].sum() / counts.sum() < 0.55
    assert a.sender.min() >= 0 and a.sender.max() < 1 << 12
    # the function mix and the windows are the uniform mix's
    u = _gen(3)
    np.testing.assert_array_equal(a.offsets, u.offsets)
    np.testing.assert_array_equal(a.fn, u.fn)
