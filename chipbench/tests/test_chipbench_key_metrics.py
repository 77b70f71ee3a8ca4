"""The full-key check's byte count, and the readers of ``key_check_roofline``
and ``key_pack_us_per_op`` on a small synthetic trace (times in ns)."""
import copy
import json
from pathlib import Path

import pytest

from chipbench import harness
from chipbench.spans import Spans
from chipbench.trace import Reduction

COST = harness.load_file(harness.HERE / "costs" / "key_check.py")
POOL = {"n_pages": 4096, "n_buckets": 1024, "slots_per_bucket": 8,
        "replicas": 3, "chunk_pages": 64, "n_shards": 4}

BASE = json.loads((Path(__file__).parent / "fixtures" /
                   "trace_small.json").read_text())


def _trace(with_program: bool) -> dict:
    """``trace_small.json``, and where ``with_program`` the key checks'
    modules inside the window (900 ns in all, one after it) and two
    ``kv.backend.keys`` spans (300 ns)."""
    t = copy.deepcopy(BASE)
    if with_program:
        t["devices"][0]["modules"] += [["jit__search_check(4)", 1500, 600],
                                       ["jit__search_all_check(5)", 3500,
                                        300],
                                       ["jit__search_check(4)", 11200, 500]]
        t["host"] += [["kv.backend.keys", 1050, 100],
                      ["kv.backend.keys", 6050, 200]]
    return t


def _readings(with_program=True, sizes=(10, 10), shadowed=1):
    cell = harness.Cell("t", {"pool": POOL}, {}, 1, [], [])
    spans = Spans()
    spans.sizes["cb.pool.search"] = list(sizes)
    return harness.Readings(
        cell=cell, ops={"get": 20, "update": 5}, calls={"get": 2, "update": 1},
        frontend_s=0.0, spans=spans, epoch_writers=[], compiles=0,
        trace=Reduction(_trace(with_program)),
        peaks={"hbm_bytes_per_s": 1e9},
        counters={"pool.search.shadowed": shadowed})


def _read(name, r):
    return harness.load_file(harness.HERE / "layer_metrics" /
                             f"{name}.py").read(r)


def test_key_check_bytes_by_hand():
    # 10 keys: record 24 B + page record 24 B + ptr 4 B + flags 1 B
    assert COST.bytes_needed(10, POOL) == 10 * (24 + 24 + 4 + 1) == 530
    # a shadowed key: 16 slot words, 16 page records, its own record
    assert COST.bytes_needed(10, POOL, 2) == 530 + 2 * (64 + 384 + 24)
    assert COST.bytes_needed(10, {**POOL, "slots_per_bucket": 4}, 1) == \
        530 + 32 + 192 + 24


def test_key_check_bytes_do_not_grow_with_the_pool():
    big = {**POOL, "n_buckets": 1 << 20, "n_pages": 1 << 21}
    assert COST.bytes_needed(973, big, 7) == COST.bytes_needed(973, POOL, 7)


def test_key_check_roofline_reads_both_modules():
    # 20 keys and 1 shadowed: 1060 + 472 B at 1 GB/s, over 900 ns
    assert _read("key_check_roofline", _readings()) == pytest.approx(
        100 * 1532 / 900)


def test_key_pack_reads_the_span_per_op():
    # 300 ns over 25 ops, in us
    assert _read("key_pack_us_per_op", _readings()) == pytest.approx(
        300e-3 / 25)


@pytest.mark.parametrize("name", ["key_check_roofline",
                                  "key_pack_us_per_op"])
def test_readers_say_nothing_of_a_program_without_the_check(name):
    """A program that has no key check and no ``kv.backend.keys`` span
    (one older than both) reads as nothing."""
    assert _read(name, _readings(with_program=False)) is None


def test_key_check_roofline_says_nothing_without_a_get():
    assert _read("key_check_roofline", _readings(sizes=())) is None
