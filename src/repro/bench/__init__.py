"""repro.bench — perf telemetry + benchmark trajectory subsystem.

Three layers (DESIGN.md §8):

* ``timing`` — :class:`StageTimer`, the synchronized per-stage clock the
  search hot path records into (``SearchStats.stage_seconds``), and the
  program's ``ssh.*`` profiler spans (``StageTimer.stage``/``span``,
  ``to_host``).  Leaf module: importable from ``repro.core`` without
  cycles.
* ``schema`` — the versioned ``BENCH_*.json`` document model
  (:class:`BenchCase`/:class:`BenchResult`/:class:`BenchReport`) and its
  validator.
* ``runner``/``regression`` — :class:`BenchRunner` (writes one validated
  report per benchmark module) and the baseline diff that the CI
  ``bench-smoke`` gate exits nonzero on.

``python -m repro.bench.validate FILE...`` validates emitted reports
standalone (the CI artifact check).
"""
from repro.bench.timing import DISABLED, STAGES, StageTimer, to_host
from repro.bench.schema import (SCHEMA_VERSION, BenchCase, BenchReport,
                                BenchResult, SchemaError,
                                has_full_stage_breakdown, load_report,
                                dump_report, validate_report)
from repro.bench.regression import (Finding, compare_reports, failures)
from repro.bench.runner import BenchRunner, compare_dirs, git_sha

__all__ = [
    "DISABLED", "STAGES", "StageTimer", "to_host",
    "SCHEMA_VERSION", "BenchCase", "BenchReport", "BenchResult",
    "SchemaError", "has_full_stage_breakdown", "load_report",
    "dump_report", "validate_report",
    "Finding", "compare_reports", "failures",
    "BenchRunner", "compare_dirs", "git_sha",
]
