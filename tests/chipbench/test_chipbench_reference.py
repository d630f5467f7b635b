"""The plain reference, and the control that must fail the check.

The reference is checked on its own (its DTW against a brute-force
dynamic program written out cell by cell) and against the program at a
CPU size (equal signatures; equal top-k).  The control is the reference
computed in bfloat16, one step below the configuration's float32: its
answers must fail the cell's limits.
"""
import chipbench_testcell as tc
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import checks, data, spec
from chipbench.reference import Reference


def brute_dtw(q, x, band):
    m = len(q)
    d = np.full((m + 1, m + 1), np.inf)
    d[0, 0] = 0.0
    for i in range(1, m + 1):
        for j in range(max(1, i - band), min(m, i + band) + 1):
            d[i, j] = (q[i - 1] - x[j - 1]) ** 2 + min(
                d[i - 1, j - 1], d[i - 1, j], d[i, j - 1])
    return d[m, m]


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tc.copy(tmp_path_factory.mktemp("bench"))
    tc.add_cell(root)
    return spec.load_cell(tc.CELL, root=root)


@pytest.fixture(scope="module")
def world(cell):
    import jax
    ds = cell.config["dataset"]
    stream = data.make_stream(ds, 16, seed=21)
    dev = jax.device_put(stream)
    archive = data.device_windows(dev, data.archive_starts(ds), 128)
    queries = np.asarray(data.device_windows(
        dev, data.query_starts(ds, 16), 128))
    return archive, queries


def test_reference_dtw_is_the_plain_recurrence():
    rng = np.random.default_rng(0)
    q = rng.normal(size=(6, 40)).astype(np.float32)
    x = rng.normal(size=(6, 40)).astype(np.float32)
    ref = Reference({"window": 8, "step": 2, "ngram": 4, "num_hashes": 4,
                     "seed": 1},
                    {"topk": 2, "top_c": 4, "band": 3})
    got = ref.dtw(q, jnp.asarray(x))
    want = [brute_dtw(a.astype(np.float64), b.astype(np.float64), 3)
            for a, b in zip(q, x)]
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_reference_agrees_with_the_program(cell, world):
    from chipbench import harness
    from repro.db import TimeSeriesDB
    archive, queries = world
    cfg = cell.config
    scfg = harness.search_config(cfg).replace(searcher="batched")
    db = TimeSeriesDB.build(archive, spec=harness.index_spec(cfg),
                            config=scfg)
    ref = Reference(cfg["encoder"], cfg["search"])
    sigs = ref.signatures(archive)
    assert np.array_equal(np.asarray(sigs), np.asarray(db.index.signatures))
    served = db.search_batch(jnp.asarray(queries[:8]))
    got = checks.reference_readings(
        ref, archive, sigs, queries[:8], [r.ids for r in served],
        [r.dists for r in served])
    assert got["rank_gap"] < 1e-5 and got["pair_gap"] < 1e-5


def test_bf16_control_fails_the_limits(cell, world):
    archive, queries = world
    cfg = cell.config
    ref = Reference(cfg["encoder"], cfg["search"])
    ctl = Reference(cfg["encoder"], cfg["search"], dtype=jnp.bfloat16)
    sigs, ctl_sigs = ref.signatures(archive), ctl.signatures(archive)
    ids, dists = ctl.search(archive, ctl_sigs, queries[:8])
    got = checks.reference_readings(ref, archive, sigs, queries[:8],
                                    list(ids), list(dists))
    limits = cfg["check"]["limits"]
    assert not checks.passed(checks.verdict(got, 0, limits))
    assert got["pair_gap"] > 10 * limits["pair_gap"]


def _served_by_rule(ref, archive, sigs, queries, pick):
    """Answers of an SSH search whose top-C cut keeps the tied rows that
    ``pick(tied, places)`` names: (ids, dists, tied groups, cuts)."""
    q_sigs = jnp.stack([ref.query_signatures(q) for q in queries])
    counts = np.asarray(checks_counts(sigs, q_sigs))
    n = counts.shape[1]
    c = min(ref.top_c, n)
    out_ids, out_d, ties, cuts = [], [], [], []
    for q, cnt in zip(queries, counts):
        cut = int(np.sort(cnt)[::-1][c - 1])
        above = np.nonzero(cnt > cut)[0]
        tied = np.nonzero(cnt == cut)[0]
        cand = (np.concatenate([above, pick(tied, c - len(above))])
                if cut else np.nonzero(cnt > 0)[0])
        d = ref.dtw(np.repeat(q[None], len(cand), 0),
                    archive[jnp.asarray(cand)])
        pos = np.argsort(d, kind="stable")[:ref.topk]
        out_ids.append(cand[pos])
        out_d.append(d[pos])
        ties.append(tied)
        cuts.append((cut, cnt))
    return out_ids, out_d, ties, cuts


def checks_counts(sigs, q_sigs):
    from chipbench.reference import _counts
    return _counts(sigs, q_sigs)


def test_check_takes_any_resolution_of_the_tie_at_the_cut(cell, world):
    archive, queries = world
    cfg = cell.config
    ref = Reference(cfg["encoder"], cfg["search"])
    sigs = ref.signatures(archive)
    ids, dists, ties, _ = _served_by_rule(
        ref, archive, sigs, queries[:8], lambda t, k: t[::-1][:k])
    # the lowest-id resolution answers otherwise on some query
    _, low = ref.search(archive, sigs, queries[:8])
    assert max(float(checks.rel_gap(d, w).max())
               for d, w in zip(dists, low)) > 1e-2
    got = checks.reference_readings(ref, archive, sigs, queries[:8], ids,
                                    dists)
    assert got["rank_gap"] < 1e-5 and got["pair_gap"] < 1e-5
    assert got["foreign"] == 0


def test_check_fails_a_row_above_the_cut_left_out(cell, world):
    archive, queries = world
    cfg = cell.config
    ref = Reference(cfg["encoder"], cfg["search"])
    sigs = ref.signatures(archive)
    ids, dists, _, cuts = _served_by_rule(
        ref, archive, sigs, queries[:8], lambda t, k: t[:k])
    # drop the best answer of each query and move the rest up a rank
    got = checks.reference_readings(
        ref, archive, sigs, queries[:8], [i[1:] for i in ids],
        [d[1:] for d in dists])
    assert got["rank_gap"] > 1e-2
    # a row under the cut served in place of the last answer
    cut, cnt = cuts[0]
    under = int(np.nonzero(cnt < cut)[0][0])
    ids[0] = np.concatenate([ids[0][:-1], [under]])
    got = checks.reference_readings(ref, archive, sigs, queries[:8], ids,
                                    dists)
    assert got["foreign"] >= 1
