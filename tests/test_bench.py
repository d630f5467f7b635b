"""repro.bench: BENCH_*.json schema round-trips, regression detection on
synthetic trajectories, and stage-timing sanity on the instrumented hot
path (DESIGN.md §8).  All tests carry the ``bench`` marker (CI runs them
as a dedicated job step)."""
import os
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.bench import (STAGES, BenchCase, BenchReport, BenchResult,
                         BenchRunner, SchemaError, StageTimer,
                         compare_reports, failures,
                         has_full_stage_breakdown, load_report,
                         validate_report)
from repro.core import SSHParams, SSHIndex, ssh_search
from repro.data.timeseries import extract_subsequences, synthetic_ecg
from repro.db import SearchConfig
from repro.serving import ssh_search_batch

pytestmark = pytest.mark.bench

PARAMS = SSHParams(window=24, step=3, ngram=8, num_hashes=40, num_tables=20)


@pytest.fixture(scope="module")
def db():
    stream = synthetic_ecg(4200, seed=5)
    return jnp.asarray(extract_subsequences(stream, 128, stride=4,
                                            znorm=True))


@pytest.fixture(scope="module")
def index(db):
    return SSHIndex.build(db, PARAMS.to_spec())


def _result(name="table3/ecg/len128", us=1500.0, **kw):
    base = dict(
        name=name, us_per_query=us, us_p50=us, us_p95=us * 1.2,
        stage_us={"encode": us * 0.1, "probe": us * 0.2, "lb": us * 0.3,
                  "lb_improved": us * 0.1, "dtw": us * 0.3},
        lb_pruned_frac=0.9, precision_at_k=0.8, build_s=1.0,
        case=BenchCase(dataset="ecg", length=128, n_database=1000,
                       spec=PARAMS.to_spec().to_dict(),
                       config=SearchConfig(band=8).to_dict()))
    base.update(kw)
    return BenchResult(**base)


def _report(results=None, **kw):
    base = dict(name="table3_query_time", scale="smoke", git_sha="abc123",
                results=results if results is not None else [_result()],
                host={"platform": "test"}, created_unix=1.0)
    base.update(kw)
    return BenchReport(**base)


# ---------------------------------------------------------------------------
# schema
# ---------------------------------------------------------------------------

class TestSchema:
    def test_round_trip(self, tmp_path):
        report = _report()
        path = tmp_path / "BENCH_table3_query_time.json"
        from repro.bench import dump_report
        dump_report(report, path)
        back = load_report(path)
        assert back.to_dict() == report.to_dict()
        r = back.results[0]
        assert r.case.dataset == "ecg"
        assert r.stage_us["lb"] == pytest.approx(450.0)
        assert r.stage_us["lb_improved"] == pytest.approx(150.0)

    def test_validate_accepts_minimal(self):
        validate_report(_report(results=[BenchResult(
            name="x", us_per_query=0.0)]).to_dict())

    @pytest.mark.parametrize("mutate", [
        lambda d: d.update(schema_version=99),
        lambda d: d.update(scale="galactic"),
        lambda d: d.update(name=""),
        lambda d: d.update(results=[]),
        lambda d: d["results"][0].update(us_per_query=-1.0),
        lambda d: d["results"][0].update(us_per_query=float("nan")),
        lambda d: d["results"][0].update(stage_us={"warp": 1.0}),
        lambda d: d["results"][0].update(name=""),
    ])
    def test_validate_rejects(self, mutate):
        doc = _report().to_dict()
        mutate(doc)
        with pytest.raises(SchemaError):
            validate_report(doc)

    def test_rejects_duplicate_entry_names(self):
        doc = _report(results=[_result(), _result()]).to_dict()
        with pytest.raises(SchemaError, match="duplicate"):
            validate_report(doc)

    def test_full_stage_breakdown_detection(self):
        assert has_full_stage_breakdown(_report().to_dict())
        partial = _result(stage_us={"dtw": 1.0})
        assert not has_full_stage_breakdown(
            _report(results=[partial]).to_dict())


# ---------------------------------------------------------------------------
# regression detection on synthetic trajectories
# ---------------------------------------------------------------------------

class TestRegression:
    def _pair(self, base_us, cur_us, **result_kw):
        return (_report(results=[_result(us=cur_us, **result_kw)]),
                _report(results=[_result(us=base_us, **result_kw)]))

    def test_regression_detected(self):
        cur, base = self._pair(1000.0, 3000.0)
        found = compare_reports(cur, base, rel_threshold=1.0)
        assert [f.kind for f in found] == ["regression"]
        assert failures(found)
        assert found[0].metric == "us_per_query"

    def test_improvement_is_not_failure(self):
        cur, base = self._pair(3000.0, 1000.0)
        found = compare_reports(cur, base, rel_threshold=1.0)
        assert [f.kind for f in found] == ["improvement"]
        assert not failures(found)

    def test_within_noise_passes(self):
        cur, base = self._pair(1000.0, 1800.0)
        assert compare_reports(cur, base, rel_threshold=1.0) == []

    def test_sub_noise_floor_timings_ignored(self):
        # 10x slower but under min_us on the baseline side: not compared
        cur, base = self._pair(100.0, 1000.0)
        assert compare_reports(cur, base, rel_threshold=1.0,
                               min_us=200.0) == []

    def test_precision_drop_is_regression(self):
        cur, base = self._pair(1000.0, 1000.0)
        cur.results[0].precision_at_k = 0.5
        base.results[0].precision_at_k = 0.9
        found = compare_reports(cur, base, precision_tol=0.15)
        assert [(f.kind, f.metric) for f in found] == \
            [("regression", "precision_at_k")]

    def test_missing_entry_fails_new_entry_does_not(self):
        cur = _report(results=[_result(name="a"), _result(name="c")])
        base = _report(results=[_result(name="a"), _result(name="b")])
        found = compare_reports(cur, base)
        kinds = {f.entry: f.kind for f in found}
        assert kinds == {"b": "missing", "c": "new"}
        assert [f.entry for f in failures(found)] == ["b"]

    def test_scale_mismatch_fails_instead_of_bogus_regressions(self):
        cur, base = self._pair(1000.0, 1000.0)
        cur.scale = "small"          # same entries, incomparable workload
        found = compare_reports(cur, base)
        assert [(f.kind, f.metric) for f in found] == \
            [("mismatch", "scale")]
        assert failures(found)


# ---------------------------------------------------------------------------
# runner + gate plumbing
# ---------------------------------------------------------------------------

class TestRunner:
    def test_runner_writes_validated_report(self, tmp_path):
        runner = BenchRunner(scale="smoke", out_dir=tmp_path, sha="deadbeef")
        runner.start_module("table3_query_time")
        runner.record(_result())
        path = runner.finish_module()
        assert path == tmp_path / "BENCH_table3_query_time.json"
        report = load_report(path)
        assert report.git_sha == "deadbeef"
        assert report.scale == "smoke"
        assert report.results[0].name == "table3/ecg/len128"

    def test_empty_module_writes_nothing(self, tmp_path):
        runner = BenchRunner(scale="smoke", out_dir=tmp_path, sha="")
        runner.start_module("empty")
        assert runner.finish_module() is None
        assert list(tmp_path.iterdir()) == []

    def test_compare_dirs_module_filter_and_missing(self, tmp_path):
        from repro.bench import compare_dirs, dump_report
        base_dir, cur_dir = tmp_path / "base", tmp_path / "cur"
        for mod, us in (("m1", 1000.0), ("m2", 1000.0)):
            dump_report(_report(name=mod, results=[_result(us=us)]),
                        base_dir / f"BENCH_{mod}.json")
        # m1 regressed 5x; m2 never emitted by the current run
        dump_report(_report(name="m1", results=[_result(us=5000.0)]),
                    cur_dir / "BENCH_m1.json")
        found, missing = compare_dirs(cur_dir, base_dir, rel_threshold=1.0)
        assert [f.kind for f in found] == ["regression"]
        assert missing == ["BENCH_m2.json"]
        # a partial run (--only m1) must not flag the unran m2
        found, missing = compare_dirs(cur_dir, base_dir, modules=["m1"],
                                      rel_threshold=1.0)
        assert missing == []
        # a module with no baseline yet is skipped, not failed
        _, missing = compare_dirs(cur_dir, base_dir,
                                  modules=["brand_new"])
        assert missing == []

    def test_run_py_only_unmatched_errors(self, capsys):
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
        try:
            from benchmarks.run import main
        finally:
            sys.path.pop(0)
        assert main(["--only", "no_such_bench"]) == 2
        err = capsys.readouterr().err
        assert "matches no benchmark module" in err
        assert "table3_query_time" in err   # lists the valid names


# ---------------------------------------------------------------------------
# stage-timing sanity on the real hot path
# ---------------------------------------------------------------------------

class TestStageTiming:
    CFG = SearchConfig(topk=10, top_c=128, band=8, searcher="local")

    def test_timer_accumulates_and_disables(self):
        t = StageTimer(enabled=True, prefill=STAGES)
        with t.stage("dtw") as sync:
            assert sync(jnp.ones(3)).shape == (3,)
        with t.stage("dtw"):
            pass
        assert set(t.timings) == set(STAGES)
        assert t.timings["dtw"] > 0 and t.timings["encode"] == 0.0
        off = StageTimer(enabled=False)
        with off.stage("dtw") as sync:
            assert sync("x") == "x"
        assert off.timings == {}

    def test_spans_and_fetches_carry_their_stats(self, monkeypatch):
        import jax
        from repro.bench import to_host
        seen = []

        class Annotation:
            def __init__(self, name, **stats):
                seen.append((name, stats))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
        off = StageTimer(enabled=False)
        with off.stage("dtw", pairs=5) as sync:
            assert sync("x") == "x"
        with StageTimer.span("pairs", pairs=2, union=1):
            pass
        host = to_host(np.arange(3))               # already on the host
        dev = to_host(jnp.arange(4, dtype=jnp.float32))
        assert isinstance(dev, np.ndarray) and dev.tolist() == [0, 1, 2, 3]
        assert host.tolist() == [0, 1, 2]
        assert seen == [("ssh.dtw", {"pairs": 5}),
                        ("ssh.pairs", {"pairs": 2, "union": 1}),
                        ("ssh.fetch", {"bytes": 16})]

    def test_sequential_all_stages_present_sum_le_total(self, db, index):
        res = ssh_search(db[3], index, config=self.CFG)
        assert res.stats.stage_seconds is not None
        assert set(res.stats.stage_seconds) == set(STAGES)
        assert all(v >= 0.0 for v in res.stats.stage_seconds.values())
        assert sum(res.stats.stage_seconds.values()) <= res.wall_seconds
        assert res.stats.stage_us["dtw"] == pytest.approx(
            res.stats.stage_seconds["dtw"] * 1e6)

    def test_batched_all_stages_present_sum_le_total(self, db, index):
        res = ssh_search_batch(db[jnp.asarray([3, 9, 14])], index,
                               config=self.CFG.replace(searcher="batched"))
        assert set(res.stats.stage_seconds) == set(STAGES)
        assert sum(res.stats.stage_seconds.values()) <= res.wall_seconds

    def test_disabled_timings_do_not_change_results(self, db, index):
        on = ssh_search(db[7], index, config=self.CFG)
        off = ssh_search(db[7], index,
                         config=self.CFG.replace(stage_timings=False))
        assert off.stats.stage_seconds is None
        np.testing.assert_array_equal(on.ids, off.ids)
        np.testing.assert_allclose(on.dists, off.dists)

    @pytest.mark.parametrize("timings", [False, True])
    def test_served_batch_emits_spans_and_syncs_only_when_timed(
            self, db, index, monkeypatch, timings):
        """A batch served by the engine's worker opens every ``ssh.*``
        span whether or not it is timed; untimed it never waits on the
        device (the timed control does), and answers the same."""
        import jax
        from jax._src import array as jax_array
        from repro.db import BatchPolicy
        from repro.serving import ServingEngine
        cfg = self.CFG.replace(searcher="batched", stage_timings=timings,
                               batch_policy=BatchPolicy(max_batch=4,
                                                        max_wait_ms=50.0))
        engine = ServingEngine(index, cfg)
        queries = [3, 9, 14]
        want = engine.search_batch(db[jnp.asarray(queries)])   # compiles
        names, syncs, batches = [], [], []

        class Annotation:
            def __init__(self, name, **stats):
                names.append(name)
                if name == "ssh.batch":
                    batches.append(stats)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        real_sync = jax.block_until_ready
        real_method = jax_array.ArrayImpl.block_until_ready

        def sync(x):
            syncs.append("jax.block_until_ready")
            return real_sync(x)

        def method(self):
            syncs.append("Array.block_until_ready")
            return real_method(self)

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
        monkeypatch.setattr(jax, "block_until_ready", sync)
        monkeypatch.setattr(jax_array.ArrayImpl, "block_until_ready",
                            method)
        futs = [engine.submit(db[q]) for q in queries]
        with engine:
            got = [f.result(timeout=120) for f in futs]
            # with the queue empty again the worker waits for a request
            deadline = time.monotonic() + 60.0
            while ("ssh.engine.wait" not in names
                   and time.monotonic() < deadline):
                time.sleep(0.01)
        assert {"ssh.engine.collect", "ssh.batch", "ssh.encode",
                "ssh.probe", "ssh.lb", "ssh.pairs", "ssh.lb_improved",
                "ssh.dtw", "ssh.fetch", "ssh.engine.deliver",
                "ssh.engine.wait"} <= set(names)
        assert names.count("ssh.batch") == 1
        assert batches[0]["size"] == 3 and batches[0]["bucket"] == 4
        assert batches[0]["head_wait_us"] >= 0
        assert (len(syncs) > 0) == timings
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a.ids, b.ids)
            np.testing.assert_array_equal(a.dists, b.dists)

    def test_engine_metrics_surface_stage_means(self, db, index):
        from repro.serving import ServingEngine
        engine = ServingEngine(index, self.CFG.replace(searcher="batched"))
        engine.search_batch(db[jnp.asarray([3, 9])])
        snap = engine.metrics.snapshot()
        for s in STAGES:
            assert snap[f"stage_{s}_us_per_batch_mean"] >= 0.0
        assert 0.0 <= snap["dtw_abandoned_frac_mean"] <= 1.0


# ---------------------------------------------------------------------------
# counter consistency on the instrumented hot path
# ---------------------------------------------------------------------------

class TestCounterConsistency:
    """The pruning/abandon counters must stay a partition of the
    candidate block and the derived fractions must stay probabilities —
    a regression here means a stage miscounts (e.g. double-attributing a
    candidate to two bounds, or counting an abandoned lane twice)."""

    CFG = SearchConfig(topk=10, top_c=128, band=8, searcher="local")

    def _stats(self, db, index, **cfg):
        return ssh_search(db[3], index,
                          config=self.CFG.replace(**cfg)).stats

    def test_counters_partition_and_fracs_bounded(self, db, index):
        st = self._stats(db, index)
        assert st.n_in == (st.pruned_kim + st.pruned_keogh
                           + st.pruned_keogh2 + st.pruned_improved
                           + st.n_dtw)
        assert 0.0 <= st.lb_pruned_frac <= 1.0
        assert 0.0 <= st.dtw_abandoned_frac <= 1.0
        assert 0 <= st.dtw_abandoned <= st.n_dtw
        # the lb_improved stage is timed whenever the cascade ran
        assert st.stage_seconds["lb_improved"] >= 0.0

    def test_abandon_off_zeroes_counter_only(self, db, index):
        on = self._stats(db, index, early_abandon=True)
        off = self._stats(db, index, early_abandon=False)
        assert off.dtw_abandoned == 0 and off.dtw_abandoned_frac == 0.0
        # pruning decisions happen before the DTW stage: identical
        for f in ("n_in", "pruned_kim", "pruned_keogh", "pruned_keogh2",
                  "pruned_improved", "n_dtw", "forced_kept"):
            assert getattr(on, f) == getattr(off, f)

    def test_batched_counters_partition(self, db, index):
        res = ssh_search_batch(db[jnp.asarray([3, 9, 14, 21])], index,
                               config=self.CFG.replace(searcher="batched"))
        st = res.stats
        assert st.n_in == st.lb_pruned + st.n_dtw
        assert 0 <= st.dtw_abandoned <= st.n_dtw
        assert 0.0 <= st.lb_pruned_frac <= 1.0
        assert 0.0 <= st.dtw_abandoned_frac <= 1.0

    def test_disabled_telemetry_counters_identical(self, db, index):
        on = self._stats(db, index, stage_timings=True)
        off = self._stats(db, index, stage_timings=False)
        assert off.stage_seconds is None
        for f in ("n_in", "pruned_improved", "n_dtw", "dtw_abandoned",
                  "forced_kept"):
            assert getattr(on, f) == getattr(off, f)
