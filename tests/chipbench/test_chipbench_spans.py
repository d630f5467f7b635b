"""The program's ``ssh.*`` spans reduced by ``chipbench/spans.py``: on a
synthetic trace, on a traced CPU run of a tiny cell, and on a trace
recorded on the chip."""
import gzip
from pathlib import Path

import chipbench_testcell as tc
import pytest
from test_chipbench_yardstick import _xspace

from chipbench import harness, reduce_trace, spans, spec

STAGE_SPANS = {"ssh.encode", "ssh.probe", "ssh.lb", "ssh.pairs",
               "ssh.lb_improved", "ssh.dtw"}
READINGS = {"batch_device_idle_pct", "engine_wait_pct",
            "host_fetches_per_batch", "encode_ms_per_batch",
            "probe_ms_per_batch", "lb_ms_per_batch", "dtw_ms_per_batch",
            "batch_fill_pct", "head_wait_ms_p50", "head_wait_ms_p95",
            "lb_pairs_per_batch", "union_rows_per_batch",
            "dtw_pairs_per_batch"}


def _synthetic():
    # times in ns.  Device ops at 1,300..1,400, 3,000..5,000 and
    # 6,000..7,000 leave idle 0..1,300, 1,400..3,000, 5,000..6,000 and
    # 7,000..10,000 (6,900 ns).  The wait opens before the window and is
    # clipped to it; a JAX runtime span inside ssh.dtw owns nothing.
    return _xspace(
        {"modules": [("jit_a(1)", 1300, 100), ("jit_b(2)", 3000, 2000),
                     ("jit_c(3)", 6000, 1000)],
         "ops": [("fusion.1", 1300, 100), ("fusion.2", 3000, 2000),
                 ("fusion.3", 6000, 1000)]},
        [(reduce_trace.WINDOW, 0, 10000),
         ("ssh.engine.wait", -500, 1500),
         ("ssh.batch", 1000, 8000),
         ("ssh.encode", 1200, 800),
         ("ssh.fetch", 1500, 400),
         ("ssh.dtw", 3000, 5000),
         ("np.asarray(jax.Array)", 5000, 1000),
         ("ssh.engine.deliver", 8500, 500)])


def test_spans_nest_and_own_every_idle_piece():
    s = spans.summarize_profile(_synthetic())
    assert s["window_s"] == pytest.approx(10e-6)
    assert s["idle_s"] == pytest.approx(6.9e-6)
    n = s["names"]
    assert n["ssh.engine.wait"]["total_s"] == pytest.approx(1e-6)
    assert n["ssh.batch"]["total_s"] == pytest.approx(8e-6)
    # self time: less the encode, DTW and deliver spans nested in it
    assert n["ssh.batch"]["self_s"] == pytest.approx(1.7e-6)
    assert n["ssh.encode"]["self_s"] == pytest.approx(0.4e-6)
    assert n["ssh.batch"]["idle_s"] == pytest.approx(4.9e-6)
    assert n["ssh.batch"]["busy_s"] == pytest.approx(3.1e-6)
    # the gap under np.asarray(jax.Array) inside ssh.dtw is ssh.dtw's
    assert s["own_idle"]["ssh.dtw"] == pytest.approx(2e-6)
    assert "np.asarray(jax.Array)" not in s["own_idle"]
    assert s["own_idle"]["ssh.fetch"] == pytest.approx(0.4e-6)
    assert s["own_idle"]["ssh.encode"] == pytest.approx(0.3e-6)
    assert s["own_idle"]["ssh.batch"] == pytest.approx(1.7e-6)
    assert s["own_idle"]["ssh.engine.wait"] == pytest.approx(1e-6)
    assert s["own_idle"]["ssh.engine.deliver"] == pytest.approx(0.5e-6)
    assert s["own_idle"][spans.NO_SPAN] == pytest.approx(1e-6)
    assert sum(s["own_idle"].values()) == pytest.approx(s["idle_s"])
    # the fetch lies in ssh.encode inside the batch
    assert s["batches"] == 1 and s["batch_fetches"] == 1
    assert s["stray"] == 0
    assert s["fetch_parents"] == {"ssh.encode": 1}
    assert n["ssh.batch"]["fetches"] == n["ssh.encode"]["fetches"] == 1
    assert n["ssh.dtw"]["fetches"] == 0
    r = spans.readings(s)
    assert set(r) == READINGS
    assert r["batch_device_idle_pct"] == pytest.approx(61.25)
    assert r["engine_wait_pct"] == pytest.approx(10.0)
    assert r["host_fetches_per_batch"] == 1.0
    assert r["encode_ms_per_batch"] == pytest.approx(0.8e-3)
    assert r["dtw_ms_per_batch"] == pytest.approx(5e-3)
    assert r["lb_ms_per_batch"] == 0.0
    # spans that carry no stats read zero
    assert r["batch_fill_pct"] == r["head_wait_ms_p95"] == 0.0
    assert r["lb_pairs_per_batch"] == r["dtw_pairs_per_batch"] == 0.0
    assert len(spans.table(s)) == len(n) + 1 + len(s["fetch_sizes"])


def test_idle_matches_the_trace_reduction_and_no_spans_read_nothing():
    # a trace from before the program had spans: all idle owned by none
    raw = gzip.decompress((Path(spec.HERE) / "testdata"
                           / "ecg2048-poisson.xplane.pb.gz").read_bytes())
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(raw)
    s = spans.summarize_profile(pd)
    old = reduce_trace.summarize_profile(pd)
    assert s["window_s"] == pytest.approx(old["window_s"])
    assert s["busy_s"] == pytest.approx(old["busy_s"])
    assert s["batches"] == 0 and spans.readings(s) == {}
    assert s["own_idle"] == {spans.NO_SPAN: pytest.approx(s["idle_s"])}


@pytest.fixture(scope="module")
def traced_cpu_run(tmp_path_factory):
    """A traced window of the tiny cell on the CPU, reduced."""
    tmp = tmp_path_factory.mktemp("spans")
    root = tc.copy(tmp)
    tc.add_cell(root)
    cell = spec.load_cell(tc.CELL, root=root)
    setup = harness.Setup(cell, 2 ** 33 + 11)
    trace_dir = tmp / "trace"
    try:
        res, counters = harness.run_window(setup, 1.0, 2 ** 33 + 11,
                                           trace_dir)
    finally:
        setup.close()
    return res, counters, spans.summarize(
        reduce_trace.find_xplane(trace_dir))


def test_traced_cpu_run_reads_every_metric(traced_cpu_run):
    res, counters, s = traced_cpu_run
    assert res.failed == 0
    # the batch open when tracing stopped may lack its ssh.batch span
    assert s["batches"] > 0
    assert abs(s["batches"] - counters["batches"]) <= 1
    r = spans.readings(s)
    assert set(r) == READINGS
    assert all(v is not None and v >= 0.0 for v in r.values())
    assert r["host_fetches_per_batch"] > 0
    # every recorded batch carries its size, bucket and head wait; the
    # re-rank narrows its pairs, over no more rows than pairs
    batch = s["names"]["ssh.batch"]
    assert len(s["head_wait_us"]) == s["batches"]
    assert s["batches"] <= batch["stats"]["size"] <= len(res.answered)
    assert 0.0 < r["batch_fill_pct"] <= 100.0
    assert 0.0 <= r["head_wait_ms_p50"] <= r["head_wait_ms_p95"]
    assert 0 < r["dtw_pairs_per_batch"] <= r["lb_pairs_per_batch"]
    assert 0 < r["union_rows_per_batch"] <= r["lb_pairs_per_batch"]
    for name in STAGE_SPANS | {"ssh.batch", "ssh.fetch",
                               "ssh.engine.collect", "ssh.engine.deliver"}:
        assert s["names"][name]["count"] > 0, name
    # every fetch lies inside a stage span inside a batch, but for a
    # batch the trace's end cut
    assert set(s["fetch_parents"]) <= STAGE_SPANS
    assert s["stray_between_batches"] == 0
    assert s["batch_fetches"] > 0.9 * s["names"]["ssh.fetch"]["count"]
    assert sum(s["own_idle"].values()) == pytest.approx(s["idle_s"])


def test_spans_on_a_trace_recorded_on_the_chip():
    # 5.5 s of ecg2048-poisson's window on one TPU v5 lite, traced with
    # the program's spans
    from jax.profiler import ProfileData
    raw = gzip.decompress((Path(spec.HERE) / "testdata"
                           / "ecg2048-poisson-spans.xplane.pb.gz")
                          .read_bytes())
    pd = ProfileData.from_serialized_xspace(raw)
    s = spans.summarize_profile(pd)
    old = reduce_trace.summarize_profile(pd)
    assert s["window_s"] == pytest.approx(5.535, abs=0.001)
    for name in STAGE_SPANS | {"ssh.batch", "ssh.fetch", "ssh.engine.wait",
                               "ssh.engine.collect", "ssh.engine.deliver"}:
        assert s["names"][name]["count"] > 0, name
    assert s["batches"] == 31 and s["batch_fetches"] == 633
    r = spans.readings(s)
    assert set(r) == READINGS and r["host_fetches_per_batch"] > 0
    # the host views of the series and the queries are no fetch
    assert "ssh.pairs" not in s["fetch_parents"]
    # the span stats: 40 requests in 43 compiled rows; the pairs the
    # cascade, the union table and LB_Improved leave
    assert len(s["head_wait_us"]) == 31
    assert r["batch_fill_pct"] == pytest.approx(100.0 * 40 / 43)
    assert r["head_wait_ms_p50"] == pytest.approx(2.904)
    assert r["head_wait_ms_p50"] < r["head_wait_ms_p95"] < 567.468
    assert r["lb_pairs_per_batch"] == pytest.approx(21752 / 31)
    assert r["union_rows_per_batch"] == pytest.approx(20189 / 31)
    assert r["dtw_pairs_per_batch"] == pytest.approx(21526 / 31)
    # every idle piece is owned once, and the idle is the reduction's
    assert sum(s["own_idle"].values()) == pytest.approx(s["idle_s"],
                                                        rel=1e-9)
    assert s["idle_s"] == pytest.approx(old["window_s"] - old["busy_s"],
                                        rel=1e-9)
    # the idle inside a batch goes to its stages, not to ssh.batch
    batch = s["names"]["ssh.batch"]
    assert batch["own_idle_s"] <= 0.1 * batch["idle_s"]
    assert set(s["fetch_parents"]) <= STAGE_SPANS
    assert s["stray_between_batches"] == 0
