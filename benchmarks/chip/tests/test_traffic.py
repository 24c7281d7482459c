"""Each traffic driver draws the same work from the same seeds."""
import json

import numpy as np

from chip import harness as H
from chip.drivers import fleet, train_stream


def _cfg(name):
    return H.load_json(H.BENCH / "configs" / f"{name}.json")


def test_fleet_tasks_stagger_and_repeat():
    order = fleet.tasks(10, 4, 3)
    assert len(order) == 40 and all(order.count(i) == 4 for i in range(10))
    first = [order.index(i) for i in range(10)]
    assert first == sorted(first)


def test_fleet_pool_order_is_seeded():
    a = fleet.shuffled_blocks(25, 10, 2**31 + 7)
    assert a == fleet.shuffled_blocks(25, 10, 2**31 + 7)
    assert a != fleet.shuffled_blocks(25, 10, 5)
    assert sorted(a) == list(range(25))
    assert sorted(a[:10]) == list(range(10))
    assert sorted(a[10:20]) == list(range(10, 20))


def test_train_corpus_is_seeded():
    cfg = dict(_cfg("costmodel-100m"), vocab_size=256, max_seq=128)
    tr = dict(H.load_json(H.BENCH / "traffic" / "train_unrolled.json"),
              programs=5)
    a = train_stream.corpus(cfg, tr)
    b = train_stream.corpus(cfg, tr)
    assert a[0] == b[0] and json.dumps(a[1]) == json.dumps(b[1])
    assert all(np.array_equal(a[2][k], b[2][k]) for k in a[2])
    assert len(a[0]) == 5 * len(tr["unroll_factors"])
