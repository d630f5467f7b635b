"""The check must fail a run whose timed path is broken underneath.

Each test drives the rest of a run of the CPU-sized cell (no chip look)
with the engine's batched search broken in one way, and sees
``correct`` come out false: half of each batch left out (those requests
get another request's answer), and one answer altered where it is
produced.  A serving cell has no training step, and one chip has no
exchange between chips, so those faults do not apply.
"""
import chipbench_testcell as tc
import numpy as np
import pytest

from repro.serving import engine


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tc.copy(tmp_path_factory.mktemp("bench"))
    tc.add_cell(root)
    return root


def _broken(monkeypatch, corrupt):
    real = engine.BatchedSearcher.search_batch

    def search_batch(self, queries):
        res = real(self, queries)
        corrupt(res)
        return res
    monkeypatch.setattr(engine.BatchedSearcher, "search_batch",
                        search_batch)


def test_half_of_each_batch_left_out(root, monkeypatch):
    def corrupt(res):
        b = res.ids.shape[0]
        if b == 1:          # a batch of one: its only answer is left out
            res.ids[0] = -1
            return
        for i in range(b // 2, b):
            res.ids[i] = res.ids[0]
            res.dists[i] = res.dists[0]
    _broken(monkeypatch, corrupt)
    line = tc.run(root, seed=11)
    assert line["correct"] is False
    assert line["checks"]["rank_gap"]["value"] > \
        line["checks"]["rank_gap"]["limit"]


def test_answer_altered_where_produced(root, monkeypatch):
    def corrupt(res):
        n = 512
        res.ids[:, 0] = (res.ids[:, 0] + 1) % n
    _broken(monkeypatch, corrupt)
    line = tc.run(root, seed=12)
    assert line["correct"] is False
    assert line["checks"]["pair_gap"]["value"] > \
        line["checks"]["pair_gap"]["limit"]


def test_sound_run_is_correct(root):
    line = tc.run(root, seed=13)
    assert line["correct"] is True
    assert np.isfinite(line["checks"]["rank_gap"]["value"])
