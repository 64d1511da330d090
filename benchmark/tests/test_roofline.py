"""The roofline count on a shape worked by hand, and the peaks table."""

import pytest

from roofline.count import least_time, peaks, placement_work


def test_count_by_hand():
    # T=16 tasks, G=2 gangs, R=2 dims, N=4 nodes, J=2 jobs
    ops, nbytes = placement_work(16, 2, 2, 4, 2)
    assert ops == 2 * 4 * (9 * 2 + 34)              # 416
    node = 4 * 2 * 4 * 3 + 4 * 4 * 2                # 128
    gang = 2 * 2 * 4 + 2 * 4 * 1 + 2 * 4 * 4        # 56
    task = 16 * 4 * 4 + 2 * 4 * 5                   # 296
    out = 16 * 5 + 2 * 2                            # 84
    assert nbytes == node + gang + task + out == 564


def test_memory_bounds_the_placement():
    pk = peaks("TPU v5 lite")
    t, bound = least_time({"T": 50176, "G": 6256, "R": 2, "N": 2560,
                           "J": 6272}, pk)
    assert bound == "memory"
    assert t == pytest.approx(placement_work(50176, 6256, 2, 2560, 6272)[1]
                              / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks("cpu")
