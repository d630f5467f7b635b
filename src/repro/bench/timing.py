"""Stage timers for the search hot path (DESIGN.md §8).

The pipeline's five stages — ``encode`` (query signature build),
``probe`` (collision count + top-C), ``lb`` (seed DTW for the pruning
threshold + the staged LB cascade), ``lb_improved`` (Lemire's two-pass
bound over cascade survivors), ``dtw`` (banded, early-abandoning DTW
over the survivors) — are timed with a :class:`StageTimer` threaded through
``hash_probe``/``rerank`` and their batched twins.  Accumulated seconds
land in ``SearchStats.stage_seconds`` so every entry point
(``ssh_search``, ``ssh_search_batch``, the ``ServingEngine``) surfaces
the same breakdown to ``repro.bench`` and ``ServingMetrics``.

Timing asynchronous dispatch honestly requires a device sync at each
stage boundary: the context manager yields a ``sync`` callable that the
instrumented code applies to the stage's output value
(``jax.block_until_ready``) before the clock stops.  A disabled timer
(``StageTimer(enabled=False)``, or the module default used when no
timer is passed) makes ``sync`` the identity and records nothing, so
the production path pays no extra barriers when telemetry is off
(``SearchConfig(stage_timings=False)``).

Every stage is also a profiler span: ``stage(name)`` opens
``jax.profiler.TraceAnnotation("ssh.<name>")`` whether the timer is on
or off, so a trace taken with ``jax.profiler`` shows each stage on the
device trace's clock at no sync.  ``StageTimer.span`` marks host work
that is none of the five stages (batch forming, pair bookkeeping) the
same way, and :func:`to_host` marks every device→host fetch as an
``ssh.fetch`` span.  With no profiler running a span costs about a
microsecond and adds no device operation.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict

import jax
import numpy as np

#: Canonical hot-path stages, pipeline order.  ``SearchStats``
#: carries exactly these keys when telemetry is on; the distributed
#: fan-out — whose shard_map program fuses all four — reports the
#: extra ``"fused"`` key instead (see ``serving.engine``).
STAGES = ("encode", "probe", "lb", "lb_improved", "dtw")


def _sync(value):
    """Block until ``value`` (any pytree) is computed; returns it."""
    return jax.block_until_ready(value)


def _identity(value):
    return value


def to_host(x) -> np.ndarray:
    """``np.asarray(x)``; on a ``jax.Array`` inside an ``ssh.fetch`` span
    with stat ``bytes``, so the trace counts device→host round trips."""
    if isinstance(x, jax.Array):
        with jax.profiler.TraceAnnotation("ssh.fetch", bytes=x.nbytes):
            return np.asarray(x)
    return np.asarray(x)


class StageTimer:
    """Accumulates per-stage wall-clock seconds.

    Usage::

        timer = StageTimer()
        with timer.stage("encode") as sync:
            sig = sync(index.query_signature(q))
        timer.timings  # {"encode": 0.0012, ...}

    ``sync`` blocks on device values so the recorded span covers the
    stage's actual compute, not just its dispatch.  Re-entering a stage
    accumulates (the batched re-rank visits ``dtw`` once per chunk).
    """

    def __init__(self, enabled: bool = True, prefill=()):
        self.enabled = enabled
        self.timings: Dict[str, float] = \
            {s: 0.0 for s in prefill} if enabled else {}

    @contextmanager
    def stage(self, name: str, **stats):
        """Time stage ``name`` (enabled timer only) inside the profiler
        span ``ssh.<name>`` carrying ``stats`` (always)."""
        with jax.profiler.TraceAnnotation(f"ssh.{name}", **stats):
            if not self.enabled:
                yield _identity
                return
            t0 = time.perf_counter()
            try:
                yield _sync
            finally:
                self.timings[name] = (self.timings.get(name, 0.0)
                                      + time.perf_counter() - t0)

    @staticmethod
    def span(name: str, **stats):
        """The profiler span ``ssh.<name>`` with ``stats``: no clock, no
        sync, for host work outside the five ``STAGES``."""
        return jax.profiler.TraceAnnotation(f"ssh.{name}", **stats)


#: Shared disabled timer — the default for un-instrumented callers, so
#: hot-path signatures can take ``timer=DISABLED`` without allocating.
DISABLED = StageTimer(enabled=False)
