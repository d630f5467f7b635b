"""A whole run of a CPU-sized cell: the result line's schema, a cell
added as files only, and ``correct`` against the plain reference."""
import json

import chipbench_testcell as tc
import numpy as np
import pytest

from chipbench import harness, spec


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tc.copy(tmp_path_factory.mktemp("bench"))
    before = tc.digest(root)
    old = json.loads((root / "BENCHMARK.json").read_text())
    tc.add_cell(root)
    return root, before, old


@pytest.fixture(scope="module")
def line(checkout):
    return tc.run(checkout[0])


def test_new_cell_is_found_by_name_without_editing_a_file(checkout, line):
    root, before, old = checkout
    after = tc.digest(root)
    changed = [f for f in before if f != "BENCHMARK.json"
               and after[f] != before[f]]
    assert changed == []
    new = json.loads((root / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert [e["name"] for e in new[key][:len(old[key])]] == \
            [e["name"] for e in old[key]]
    assert line["correct"] is True


def test_result_line_schema(line):
    text = json.dumps(line)
    parsed = json.loads(text)
    assert list(parsed)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in parsed
    assert parsed["attempted"] == 16 and parsed["failed"] == 0
    dev = parsed["device"]
    assert set(dev) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(parsed["metrics"]) == {"throughput_qps", "build_rows_s",
                                      "setup_s"}
    for m in parsed["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    for c in parsed["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]


def test_new_reader_reports_from_counters(checkout):
    root = checkout[0]
    cell = spec.load_cell(tc.CELL, root=root)
    counters = {"requests": 12, "n_candidates": [5] * 12,
                "batch_sizes": {1: 4, 2: 4}, "batches": 8,
                "latency_ms": [100.0] * 11 + [900.0]}
    out = harness.per_layer(cell, None, counters, "TPU v5 lite")
    assert out[tc.READER] == {"value": 1.5, "unit": "requests"}
    assert out["batch_size_mean.qps"]["value"] == 1.5
    assert out["dtw_candidates_per_query.qps"]["value"] == 5.0
    assert out["request_latency_p95_ms.qps"]["value"] == \
        pytest.approx(100.0 + 0.45 * 800.0)
    # trace readers find nothing to read without a trace: left out
    assert "device_idle_pct.qps" not in out


def test_unknown_cell_and_device_are_errors(checkout):
    root = checkout[0]
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell", root=root)
    with pytest.raises(KeyError):
        spec.peaks("TPU v99", root=root)
    assert spec.peaks("TPU v5 lite", root=root)["hbm_bytes_s"] == 819e9


def test_same_seed_same_inputs(checkout):
    from chipbench import data
    cfg = spec.load_cell(tc.CELL, root=checkout[0]).config
    a = data.make_stream(cfg["dataset"], 8, 2 ** 40 + 3)
    b = data.make_stream(cfg["dataset"], 8, 2 ** 40 + 3)
    c = data.make_stream(cfg["dataset"], 8, 2 ** 40 + 4)
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_closed_loop_cell_runs_from_its_traffic_file(checkout):
    got = tc.run(checkout[0], seed=2 ** 32 + 17, name=tc.CLOSED_CELL)
    assert got["correct"] is True and got["failed"] == 0
    # eight clients, each sending its next query on its answer
    assert got["attempted"] >= 8
    assert got["metrics"]["throughput_qps"]["value"] > 0
