"""The traffic generator repeats exactly for one seed."""
import numpy as np
import pytest

from chipbench import harness
from chipbench.zipf import ScrambledZipf

GEN = harness.load_file(harness.HERE / "generators" / "ycsb_closed.py")


def _mix(name):
    import json
    return json.loads((harness.HERE / "mixes" / f"{name}.json").read_text())


def _ticks(mix, seed, stream="window", n=5):
    return GEN.Ticks(mix, 1000, 4096, seed).stream(stream).next(n)


def _same(a, b):
    for ta, tb in zip(a, b):
        assert ta.keys() == tb.keys()
        for k in ta:
            assert np.array_equal(ta[k][0], tb[k][0])
            assert (ta[k][1] is None) == (tb[k][1] is None)
            if ta[k][1] is not None:
                assert np.array_equal(ta[k][1], tb[k][1])


@pytest.mark.parametrize("mix", ["ycsb-a", "ycsb-b", "ycsb-c"])
def test_same_seed_same_ticks(mix):
    m = _mix(mix)
    seed = 2 ** 31 + 12345          # larger than 32 signed bits hold
    _same(_ticks(m, seed), _ticks(m, seed))
    a, b = _ticks(m, seed), _ticks(m, seed + 1)
    assert any(not np.array_equal(a[0][k][0], b[0][k][0]) for k in a[0])


def test_window_stream_is_apart_from_warmup():
    m = _mix("ycsb-a")
    t = GEN.Ticks(m, 1000, 4096, 7)
    w = t.stream("warmup")
    w.next(3)
    w.distinct_keys(50)
    _same(t.stream("window").next(4), _ticks(m, 7, n=4))


@pytest.mark.parametrize("shares,clients,want", [
    ({"get": 0.5, "update": 0.5}, 128, {"get": 64, "update": 64}),
    ({"get": 0.95, "update": 0.05}, 128, {"get": 122, "update": 6}),
    ({"get": 1.0}, 128, {"get": 128, "update": 0}),
    ({"get": 0.95, "update": 0.05}, 1024, {"get": 973, "update": 51}),
])
def test_op_counts_fixed_per_tick(shares, clients, want):
    assert GEN.op_counts(shares, clients) == want


def test_keys_in_range_and_hot_records_scrambled():
    rng = np.random.default_rng(3)
    z = ScrambledZipf(1000, 0.99, rng)
    keys = z.keys(rng, 20000)
    assert keys.min() >= 1 and keys.max() <= 1000
    assert np.bincount(keys).argmax() == z.key_of_rank[0]
    assert sorted(z.key_of_rank.tolist()) == list(range(1, 1001))
    # rank 0 is drawn about as often as zipf(0.99) says
    p0 = 1.0 / (np.arange(1, 1001) ** -0.99).sum()
    assert abs((keys == z.key_of_rank[0]).mean() - p0) < 0.02
