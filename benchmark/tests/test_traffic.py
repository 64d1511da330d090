"""The generator: the same seed gives the same traffic, another seed
another order of the same set of sizes."""

import json
from pathlib import Path

import pytest

from traffic.generator import Traffic, quantity

BENCH = Path(__file__).resolve().parents[1]


def _load(config, traffic):
    c = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    t = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    return c, t


def _draw(config, traffic, seed):
    c, t = _load(config, traffic)
    tr = Traffic(c, t, seed)
    res, lead = tr.residents()
    submit, warm, window = tr.initial(30.0, lead)
    jobs = [j for j, _ in res] + submit + \
        [j for _, _, js in warm + window for j in js]
    return [(j.name, j.queue, j.shape, round(j.duration, 9), j.due,
             j.complete_at) for j in jobs]


@pytest.mark.parametrize("config,traffic", [("fleet-10k", "burst"),
                                            ("gpu-5k", "backlog")])
def test_seed_decides_the_traffic(config, traffic):
    big = 2 ** 31 + 12345
    a = _draw(config, traffic, big)
    assert a == _draw(config, traffic, big)
    b = _draw(config, traffic, 7)
    assert a != b
    # stratified draws: the same multiset of shapes and queues per block
    n = min(len(a), len(b), 1000)
    for k in (1, 2):     # queue, shape
        assert sorted(x[k] for x in a[:n]) == sorted(x[k] for x in b[:n])


def test_quantity():
    assert quantity("cpu", "2") == 2000
    assert quantity("cpu", "500m") == 500
    assert quantity("memory", "4Gi") == 4 * 2 ** 30
    assert quantity("nvidia.com/gpu", "8") == 8
