"""A copy of the benchmark with one CPU-sized cell added as new files.

``make(tmp)`` copies ``BENCHMARK.json`` and ``chipbench/`` into ``tmp``
and adds a configuration, a traffic mix and a per-layer reader there,
each as a file of its own plus its ``BENCHMARK.json`` entries.  Nothing
that was copied is edited, which is what a later PR that adds a cell
does.
"""
import hashlib
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

CELL = "tiny-open"
CLOSED_CELL = "tiny-closed"
READER = "requests_per_batch.tiny"


def digest(root: Path) -> dict:
    """sha256 of every file the benchmark had before the additions."""
    files = [root / "BENCHMARK.json"] + sorted(
        p for p in (root / "chipbench").rglob("*")
        if p.is_file() and "__pycache__" not in p.parts)
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in files}


def copy(tmp: Path) -> Path:
    root = tmp / "checkout"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def add_cell(root: Path) -> None:
    """The new cell's files, and its entries appended to the lists."""
    config = {
        "name": "tiny", "precision": "float32",
        "dataset": {"generator": "ecg", "length": 128, "rows": 512,
                    "stride": 1},
        "encoder": {"encoder": "ssh", "window": 24, "step": 3, "ngram": 8,
                    "num_filters": 1, "num_hashes": 8, "num_tables": 4,
                    "seed": 7},
        "search": {"searcher": "engine", "backend": "auto", "topk": 5,
                   "top_c": 64, "band": 6, "multiprobe_offsets": 3,
                   "rank_by_signature": True, "use_lb_cascade": True,
                   "early_abandon": True, "stage_timings": False,
                   "batch_policy": {"mode": "fixed", "max_batch": 4,
                                    "max_wait_ms": 2.0}},
        "warm_rows": 64,
        "check": {"sample": 8,
                  "limits": {"rank_gap": 1e-4, "pair_gap": 1e-4}},
    }
    (root / "chipbench/configs/tiny.json").write_text(json.dumps(config))
    (root / "chipbench/traffic/tiny-open.json").write_text(json.dumps(
        {"loop": "open", "process": "poisson", "rate_qps": 16.0,
         "shape_seed": 5, "pool": 64}))
    (root / "chipbench/traffic/tiny-closed.json").write_text(json.dumps(
        {"loop": "closed", "clients": 8, "pool": 64}))
    (root / f"chipbench/layer_metrics/{READER}.py").write_text(
        '"""Requests a batch, from the engine\'s counts."""\n\n\n'
        "def read(ctx):\n"
        "    b = ctx['counters']['batches']\n"
        "    return ctx['counters']['requests'] / b if b else None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "chipbench/configs/tiny.json",
                             "reduced": [], "why": "CPU-sized"})
    for name in (CELL, CLOSED_CELL):
        bench["workloads"].append({"name": name, "config": "tiny",
                                   "traffic": name, "chips": 1,
                                   "why": "CPU-sized"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [CELL, CLOSED_CELL]
    bench["per_layer"].append({
        "name": READER, "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "batcher",
        "moves": "throughput_qps", "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))


def run(root: Path, seed: int = 2 ** 33 + 5, seconds: float = 1.0,
        name: str = CELL):
    """One run of a tiny cell on the CPU, chip look and cache off."""
    import time
    from chipbench import harness, spec
    cell = spec.load_cell(name, root=root)
    return harness.run_cell(cell, seed, seconds, False,
                            time.perf_counter(), require_chip=False,
                            use_cache=False)
