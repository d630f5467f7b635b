"""Traffic, work counts and trace reduction: the pieces of the yardstick
that later PRs cannot change."""
import numpy as np
import pytest

from chipbench import reduce_trace, spec, traffic

POISSON = {"loop": "open", "process": "poisson", "rate_qps": 10.0,
           "shape_seed": 3, "pool": 64}


def test_schedule_same_seed_same_times():
    a = traffic.schedule(POISSON, 30.0, 2 ** 35 + 1)
    b = traffic.schedule(POISSON, 30.0, 2 ** 35 + 1)
    assert np.array_equal(a, b)


@pytest.mark.parametrize("seed", [0, 7, 2 ** 33 + 9])
def test_every_seed_gets_the_same_work(seed):
    base = traffic.schedule(POISSON, 30.0, 1)
    got = traffic.schedule(POISSON, 30.0, seed)
    assert len(got) == len(base) == 300
    assert got[-1] < 30.0
    np.testing.assert_allclose(np.sort(np.diff(got, prepend=0.0)),
                               np.sort(np.diff(base, prepend=0.0)))


@pytest.mark.parametrize("process", ["mmpp", "diurnal"])
def test_other_processes_schedule(process):
    got = traffic.schedule(dict(POISSON, process=process), 10.0, 5)
    assert len(got) == 100 and np.all(np.diff(got) >= 0)


def _dispersion(times: np.ndarray, bin_s: float) -> float:
    counts = np.bincount((times // bin_s).astype(int))
    return float(counts.var() / counts.mean())


@pytest.mark.parametrize("process,args", [
    ("poisson", {}), ("mmpp", {"burst_factor": 4.0, "dwell_s": 0.25}),
    ("diurnal", {"period_s": 20.0, "depth": 0.8})])
def test_processes_keep_their_mean_rate(process, args):
    t = traffic.PROCESSES[process](50.0, 20000, 9, **args)
    assert 20000 / t[-1] == pytest.approx(50.0, rel=0.05)


def test_mmpp_bursts_and_diurnal_follows_its_period():
    pois = traffic.poisson_arrivals(50.0, 20000, 4)
    mmpp = traffic.mmpp_arrivals(50.0, 20000, 4, burst_factor=4.0,
                                 dwell_s=0.25)
    assert _dispersion(pois, 0.25) < 1.3
    assert _dispersion(mmpp, 0.25) > 3.0
    # the rate peaks in the first half of each period, dips in the second
    di = traffic.diurnal_arrivals(50.0, 20000, 4, period_s=20.0, depth=0.8)
    phase = (di % 20.0) < 10.0
    assert phase.sum() > 2.5 * (~phase).sum()


def test_query_order_spends_the_pool_before_repeating():
    order = traffic.query_order(64, 200, 2 ** 34)
    assert sorted(order[:64]) == list(range(64))
    assert sorted(order[64:128]) == list(range(64))
    assert np.array_equal(order, traffic.query_order(64, 200, 2 ** 34))


@pytest.mark.parametrize("m,band", [(8, 2), (16, 15), (40, 3), (2048, 102)])
def test_dtw_cells_counts_the_band(m, band):
    w = spec.work_module("dtw_wavefront_pairs")
    i, j = np.meshgrid(np.arange(m), np.arange(m), indexing="ij")
    assert w.cells(m, band) == int(np.sum(np.abs(i - j) <= band))
    ops, nbytes = w.work(3, m, band)
    assert ops == 3 * w.cells(m, band) * 5
    assert nbytes == 3 * (2 * m + 1) * 4


def test_probe_bytes_are_the_stored_signatures():
    w = spec.work_module("collision_count_batch")
    assert w.work(2, 1 << 18, 40) == (0, 2 * (1 << 18) * 40 * 4)


def test_union_and_clip():
    iv = np.asarray([[0, 2], [1, 3], [5, 6], [6, 8]], float)
    assert reduce_trace._union(iv).tolist() == [[0, 3], [5, 8]]
    assert reduce_trace._clip(iv, 1.5, 5.5).tolist() == [
        [1.5, 2], [1.5, 3], [5, 5.5]]


def test_gaps_take_the_tightest_host_span():
    gaps = np.asarray([[10, 20], [30, 31]], float)
    names = ["outer", "inner", "far"]
    spans = np.asarray([[0, 100], [9, 21], [200, 300]], float)
    got = reduce_trace._label_gaps(gaps, names, spans)
    assert dict(got) == {"inner": [10e-9, 1], "outer": [1e-9, 1]}


class _FakeServer:
    """Answers each query after ``delay_s`` on a timer thread."""

    def __init__(self, delay_s: float):
        self.delay_s = delay_s
        self.seen = []

    def submit(self, query):
        import threading
        from concurrent.futures import Future
        fut = Future()
        self.seen.append(float(query[0]))
        threading.Timer(self.delay_s, fut.set_result,
                        args=(float(query[0]),)).start()
        return fut


def test_open_loop_times_from_the_intended_send():
    pool = np.arange(8, dtype=np.float32)[:, None]
    times = np.asarray([0.0, 0.01, 0.02, 0.03])
    srv = _FakeServer(0.02)
    res = traffic.run_open(srv.submit, pool, times, np.asarray([3, 1, 2, 0]),
                           0.2, grace_s=5.0)
    assert srv.seen == [3.0, 1.0, 2.0, 0.0]
    assert res.failed == 0 and res.completed_in_window() == 4
    lat = res.latencies_ms()
    assert np.all(lat >= 20.0) and np.all(lat < 200.0)


def test_open_loop_counts_a_lost_request_as_failed():
    from concurrent.futures import Future
    pool = np.zeros((2, 1), np.float32)
    res = traffic.run_open(lambda q: Future(), pool, np.asarray([0.0]),
                           np.asarray([0]), 0.05, grace_s=0.05)
    assert res.failed == 1 and res.answered == []


def test_closed_loop_keeps_each_client_busy():
    pool = np.arange(16, dtype=np.float32)[:, None]
    srv = _FakeServer(0.01)
    res = traffic.run_closed(srv.submit, pool, 2, np.arange(16), 0.3,
                             grace_s=5.0)
    assert res.failed == 0
    # two clients, each waiting ~10 ms an answer, over 0.3 s
    assert 20 <= res.attempted <= 62
    assert res.completed_in_window() >= res.attempted - 2


def _xspace(device_events, host_events):
    """A trace in the profiler's XSpace text form: a TPU plane with
    "XLA Modules" and "XLA Ops" lines, and a host plane."""
    meta, ids = [], {}

    def mid(name):
        if name not in ids:
            ids[name] = len(ids) + 1
            meta.append(f'event_metadata {{ key: {ids[name]} value {{ '
                        f'id: {ids[name]} name: "{name}" }} }}')
        return ids[name]

    def line(i, name, evs):
        body = " ".join(f"events {{ metadata_id: {mid(n)} offset_ps: "
                        f"{int(s * 1000)} duration_ps: {int(d * 1000)} }}"
                        for n, s, d in evs)
        return f'lines {{ id: {i} name: "{name}" timestamp_ns: 0 {body} }}'

    dev_lines = [line(1, "XLA Modules", device_events["modules"]),
                 line(2, "XLA Ops", device_events["ops"])]
    dev = ('planes { id: 1 name: "/device:TPU:0" ' + " ".join(dev_lines)
           + " " + " ".join(meta) + " }")
    meta.clear()
    ids.clear()
    host = ('planes { id: 2 name: "/host:CPU" '
            + line(1, "python", host_events) + " " + " ".join(meta) + " }")
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(dev + "\n" + host)


def test_trace_reduction_on_a_synthetic_tpu_trace():
    # times in ns: window 0..10,000; DTW program 1,000..4,000 with two
    # ops, probe program 6,000..7,000 with one op; an op before the
    # window is clipped away
    pd = _xspace(
        {"modules": [("jit_dtw_wavefront_pairs(3)", 1000, 3000),
                     ("jit_collision_count_batch(9)", 6000, 1000),
                     ("jit_other(1)", -500, 400)],
         "ops": [("custom-call.1", 1000, 1500), ("fusion.2", 2500, 1500),
                 ("custom-call.4", 6000, 1000), ("copy.1", -500, 400)]},
        [(reduce_trace.WINDOW, 0, 10000),
         ("engine.search_batch", 500, 9000),
         ("PjitFunction(dtw_wavefront_pairs)", 900, 100),
         ("np.asarray", 4100, 1800)])
    s = reduce_trace.summarize_profile(pd)
    assert s["devices"] == 1
    assert s["window_s"] == pytest.approx(10e-6)
    assert s["busy_s"] == pytest.approx(4e-6)
    assert reduce_trace.kernel_seconds(s, "dtw_wavefront_pairs") == \
        pytest.approx(3e-6)
    assert reduce_trace.kernel_seconds(
        s, "dtw_wavefront_pairs", r"custom-call") == pytest.approx(1.5e-6)
    assert reduce_trace.kernel_seconds(s, "collision_count_batch") == \
        pytest.approx(1e-6)
    # idle: 0..1000 and 7000..10000 under the batch span, 4000..6000
    # mostly under np.asarray
    assert s["idle"]["np.asarray"] == pytest.approx(2e-6)
    assert s["idle"]["engine.search_batch"] == pytest.approx(4e-6)
    assert sum(s["idle"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"])
    bd = reduce_trace.breakdown(s)
    assert bd["device_ops"][0][0] == "jit_dtw_wavefront_pairs|custom-call.1"
    assert bd["idle_gaps"][0] == ["engine.search_batch (x2)",
                                  pytest.approx(4e-6)]


def test_trace_reduction_on_a_trace_recorded_on_the_chip():
    # five seconds of ecg2048-poisson's window on one TPU v5 lite; the
    # harness's window span shares its thread's line name ("python3")
    # with another thread
    import gzip
    from pathlib import Path
    from jax.profiler import ProfileData
    raw = gzip.decompress((Path(spec.HERE) / "testdata"
                           / "ecg2048-poisson.xplane.pb.gz").read_bytes())
    s = reduce_trace.summarize_profile(ProfileData.from_serialized_xspace(raw))
    assert s["devices"] == 1
    assert s["window_s"] == pytest.approx(5.07, abs=0.01)
    assert 0.0 < s["busy_s"] < s["window_s"]
    for kernel in ("dtw_wavefront_pairs", "collision_count_batch"):
        assert reduce_trace.kernel_seconds(s, kernel) > 0.0
    bd = reduce_trace.breakdown(s)
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) <= 10
    assert all(" = " not in name for name, _ in bd["device_ops"])
    assert bd["device_ops"][0][0] == \
        "jit_dtw_wavefront_pairs|%dtw_wavefront_pairs.1"
    assert bd["idle_gaps"][0][0].startswith("engine.search_batch ")
