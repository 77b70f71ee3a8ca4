"""The kernels' byte counts match a hand count and ignore the index size."""
from chipbench import harness

RACE = harness.load_file(harness.HERE / "costs" / "race_lookup.py")
EPOCH = harness.load_file(harness.HERE / "costs" / "snapshot_epoch.py")
POOL = {"n_pages": 4096, "n_buckets": 1024, "slots_per_bucket": 8,
        "replicas": 3, "chunk_pages": 64, "n_shards": 4}


def test_race_lookup_bytes_by_hand():
    # 10 keys: key in 4 B + 2 buckets x 8 slots x 4 B + ptr 4 B + flag 1 B
    assert RACE.bytes_needed(10, POOL) == 10 * (4 + 64 + 4 + 1) == 730
    assert RACE.bytes_needed(10, {**POOL, "slots_per_bucket": 4}) == \
        10 * (4 + 32 + 4 + 1)


def test_snapshot_epoch_bytes_by_hand():
    # 6 writers, r = 3: 3 reads + 3 writes of 4 B, 3 words in, 1+4+4 B out
    assert EPOCH.bytes_needed(6, POOL) == 6 * (24 + 12 + 9) == 270
    assert EPOCH.bytes_needed(6, {**POOL, "replicas": 1}) == 6 * (8 + 21)


def test_counts_do_not_grow_with_the_index_or_the_pool():
    big = {**POOL, "n_buckets": 1 << 20, "n_pages": 1 << 21}
    assert RACE.bytes_needed(973, big) == RACE.bytes_needed(973, POOL)
    assert EPOCH.bytes_needed(51, big) == EPOCH.bytes_needed(51, POOL)
