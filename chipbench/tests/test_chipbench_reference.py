"""The replay reference flags every kind of wrong answer it exists for."""
import numpy as np

from chipbench import reference

VALUES = [bytes([i]) * 4 for i in range(8)]


def _loaded():
    r = reference.Replay(VALUES, 5)
    keys = np.arange(1, 6)
    r.apply("insert", keys, np.arange(5), ["OK"] * 5,
            [VALUES[i] for i in range(5)])
    return r


def test_sound_log_has_no_wrong_answer():
    r = _loaded()
    # duplicates in one batch: the last writer's value wins for both ops
    r.apply("update", np.array([2, 2, 3]), np.array([6, 7, 5]),
            ["OK"] * 3, [VALUES[7], VALUES[7], VALUES[5]])
    r.apply("get", np.array([1, 2, 3]), None, ["OK"] * 3,
            [VALUES[0], VALUES[7], VALUES[5]])
    assert r.wrong == 0 and r.full == 0
    assert r.ref[1:].tolist() == [0, 7, 5, 3, 4]


def test_corrupted_answer_is_flagged():
    r = _loaded()
    bad = bytearray(VALUES[1])
    bad[0] ^= 1
    assert r.apply("get", np.array([2]), None, ["OK"], [bytes(bad)]) == 1


def test_stale_value_after_update_is_flagged():
    r = _loaded()
    r.apply("update", np.array([4]), np.array([7]), ["OK"], [VALUES[7]])
    assert r.apply("get", np.array([4]), None, ["OK"], [VALUES[3]]) == 1


def test_first_writer_instead_of_last_is_flagged():
    r = _loaded()
    n = r.apply("update", np.array([2, 2]), np.array([6, 7]), ["OK"] * 2,
                [VALUES[6], VALUES[6]])
    assert n == 2


def test_missing_record_and_wrong_status_are_flagged():
    r = _loaded()
    assert r.apply("get", np.array([5]), None, ["NOT_FOUND"], [None]) == 1
    r2 = reference.Replay(VALUES, 5)
    assert r2.apply("get", np.array([5]), None, ["NOT_FOUND"], [None]) == 0
    assert r2.apply("get", np.array([5]), None, ["OK"], [VALUES[0]]) == 1


def test_full_answer_is_counted_and_not_applied():
    r = _loaded()
    r.apply("update", np.array([1]), np.array([7]), ["FULL"], [None])
    assert r.full == 1 and r.wrong == 0
    assert r.apply("get", np.array([1]), None, ["OK"], [VALUES[0]]) == 0


def test_unconverged_replica_is_flagged():
    index = np.zeros((3, 16), np.int32)
    index[:, 3] = 77
    assert reference.unconverged_slots(index) == 0
    index[2, 5] = 9                   # a write that missed two replicas
    index[0, 5] = 9
    index[1, 7] = 4                   # a backup that diverged alone
    assert reference.unconverged_slots(index) == 2
    assert reference.unconverged_slots(index[:1]) == 0
