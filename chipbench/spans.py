"""The program's own ``ssh.*`` spans in a JAX profiler trace.

The program opens a ``jax.profiler.TraceAnnotation`` named ``ssh.<name>``
at each layer boundary of the served query path
(``repro.bench.timing``): the engine's wait for a request, batch
forming, each batch, each search stage, the host pair bookkeeping, and
every device→host fetch (``ssh.fetch``, stat ``bytes``).  They share the
device trace's clock, so ``summarize`` can put every idle moment of the
device under the host work that held it back.

``summarize`` reads the same ``.xplane.pb`` as ``reduce_trace`` and
returns a plain dict over the spans inside the ``chipbench.window`` span
(each clipped to it):

* ``window_s``, and ``busy_s`` and ``idle_s`` of the device (the union
  of its "XLA Ops" intervals, as in ``reduce_trace``, averaged over
  devices; a trace with no device plane counts the device idle
  throughout);
* ``names`` — per span name: ``count``, ``total_s``, ``self_s`` (less
  the time of the ``ssh.*`` spans nested in it on its thread),
  ``busy_s`` and ``idle_s`` of the device under it, ``own_idle_s`` (the
  idle it is the innermost span over, below), and the ``ssh.fetch``
  spans under it (``fetches``, ``fetch_s``, ``fetch_bytes``);
* ``own_idle`` — every idle piece of the device goes to the innermost
  ``ssh.*`` span over it on any thread (deepest nesting, then the latest
  to open), or to ``NO_SPAN``; the values sum to ``idle_s``.  Spans of
  the JAX runtime (``np.asarray(jax.Array)``, ``DevicePut``) are not
  ``ssh.*`` and take no part;
* per span name besides, ``stats``: the sum of each stat the program
  puts on it (``ssh.batch``: ``size``, ``bucket``, ``head_wait_us``;
  ``ssh.pairs``: ``pairs``, ``union``; ``ssh.dtw``: ``pairs``) over its
  spans inside a batch, and ``ssh.batch``'s over every batch;
  ``head_wait_us`` — each batch's ``head_wait_us``, sorted;
* ``batches`` — ``ssh.batch`` spans; ``batch_fetches`` — ``ssh.fetch``
  spans with an ``ssh.batch`` around them; ``fetch_parents`` — the
  fetches counted by the name of the ``ssh.*`` span just around them
  (``NO_SPAN`` for none); ``fetch_sizes`` — the largest fetch sizes,
  each with its count and seconds;
* ``stray`` — spans of a batch's work (neither ``ssh.batch`` nor
  ``ssh.engine.*``) with no ``ssh.batch`` around them, and
  ``stray_between_batches`` of them.  The profiler records a span when
  it closes, and only if it opened while tracing, so the batch open at
  either end of the trace leaves its stages stray; a stray span between
  two batches is program work outside every batch.

``readings`` turns the summary into the per-layer numbers, each per
recorded ``ssh.batch`` over the spans inside one (``in_batch_s``), and
the spans' stats into the batch fill, the head-of-batch wait and the
pairs each batch carries through the re-rank.
"""
from __future__ import annotations

import heapq
from collections import defaultdict
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench import reduce_trace

PREFIX = "ssh."
BATCH = "ssh.batch"
FETCH = "ssh.fetch"
NO_SPAN = "no ssh span"
_SIZES = 8              # fetch sizes kept in ``fetch_sizes``


def summarize(path: Path, log: Optional[Callable[[str], None]] = None
              ) -> dict:
    """The reduction of the ``.xplane.pb`` at ``path``; with ``log``, one
    line per span name besides."""
    from jax.profiler import ProfileData
    out = summarize_profile(ProfileData.from_file(str(path)))
    if log is not None:
        for line in table(out):
            log(line)
    return out


class _Span:
    __slots__ = ("name", "start", "end", "stats", "parent", "depth",
                 "in_batch")

    def __init__(self, name, start, end, stats):
        self.name, self.start, self.end, self.stats = name, start, end, stats
        self.parent: Optional["_Span"] = None
        self.depth = 0
        self.in_batch = False           # an ssh.batch span is around it


def _window(pd):
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == reduce_trace.WINDOW:
                        return e.start_ns, e.start_ns + e.duration_ns
    raise ValueError(f"the trace has no host span {reduce_trace.WINDOW!r}")


def _thread_spans(pd, lo: float, hi: float) -> List[List[_Span]]:
    """The ``ssh.*`` spans of each host thread's line, clipped to the
    window, nested by time (parent and depth set)."""
    threads = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:            # one line per thread
            spans = []
            for e in line.events:
                if not e.name.startswith(PREFIX):
                    continue
                s = max(e.start_ns, lo)
                t = min(e.start_ns + e.duration_ns, hi)
                if t > s or (t == s and lo <= s < hi):
                    spans.append(_Span(e.name, s, t,
                                       {k: v for k, v in e.stats}))
            if spans:
                spans.sort(key=lambda x: (x.start, -x.end))
                _nest(spans)
                threads.append(spans)
    return threads


def _nest(spans: List[_Span]) -> None:
    stack: List[_Span] = []
    for sp in spans:
        while stack and stack[-1].end <= sp.start:
            stack.pop()
        if stack:
            sp.parent = stack[-1]
            sp.depth = stack[-1].depth + 1
            sp.end = min(sp.end, stack[-1].end)
            sp.in_batch = sp.parent.in_batch or sp.parent.name == BATCH
        stack.append(sp)


class _Idle:
    """The device's idle time before any instant of the window."""

    def __init__(self, gaps: np.ndarray):
        self.a = gaps[:, 0]
        self.len = gaps[:, 1] - gaps[:, 0]
        self.cum = np.concatenate([[0.0], np.cumsum(self.len)])

    def before(self, t: np.ndarray) -> np.ndarray:
        t = np.asarray(t, np.float64)
        i = np.searchsorted(self.a, t, side="right") - 1
        j = np.maximum(i, 0)
        inside = np.clip(t - self.a[j], 0.0, self.len[j]) if len(self.a) \
            else np.zeros_like(t)
        return np.where(i >= 0, self.cum[j] + inside, 0.0)

    def between(self, a, b) -> np.ndarray:
        return self.before(b) - self.before(a)


def _device_idle(pd, lo: float, hi: float) -> List[_Idle]:
    """Per device plane, its idle gaps inside the window (none: one
    device idle throughout)."""
    out = []
    dev, _ = reduce_trace.load_planes(pd)
    for _, lines in dev:
        op_evs, _ = reduce_trace._op_lines(lines)
        if not op_evs:
            continue
        iv = np.asarray(sorted((s, e) for _, s, e in op_evs), np.float64)
        u = reduce_trace._union(reduce_trace._clip(iv.reshape(-1, 2), lo,
                                                   hi))
        edges = np.concatenate([[lo], u.reshape(-1), [hi]]).reshape(-1, 2)
        out.append(_Idle(edges[edges[:, 1] > edges[:, 0]]))
    if not out:                      # no device plane: idle throughout
        out.append(_Idle(np.asarray([[lo, hi]], np.float64)))
    return out


def _innermost(spans: List[_Span], lo: float, hi: float):
    """Pieces (starts, ends, owner index or -1) covering [lo, hi], each
    owned by the innermost span over it."""
    cuts = sorted({lo, hi} | {sp.start for sp in spans}
                  | {sp.end for sp in spans})
    opens = defaultdict(list)
    closes = defaultdict(list)
    for k, sp in enumerate(spans):
        if sp.end > sp.start:
            opens[sp.start].append(k)
            closes[sp.end].append(k)
    heap: list = []
    ended = set()
    starts, ends, owner = [], [], []
    for t0, t1 in zip(cuts[:-1], cuts[1:]):
        for k in closes.get(t0, ()):
            ended.add(k)
        for k in opens.get(t0, ()):
            heapq.heappush(heap, (-spans[k].depth, -spans[k].start, k))
        while heap and heap[0][2] in ended:
            heapq.heappop(heap)
        starts.append(t0)
        ends.append(t1)
        owner.append(heap[0][2] if heap else -1)
    return (np.asarray(starts, np.float64), np.asarray(ends, np.float64),
            np.asarray(owner, np.int64))


def summarize_profile(pd) -> dict:
    lo, hi = _window(pd)
    spans = [sp for th in _thread_spans(pd, lo, hi) for sp in th]
    idle = _device_idle(pd, lo, hi)
    window_s = (hi - lo) * 1e-9
    idle_s = float(np.mean([d.between(lo, hi) for d in idle])) * 1e-9

    start = np.asarray([sp.start for sp in spans], np.float64)
    end = np.asarray([sp.end for sp in spans], np.float64)
    span_idle = (np.mean([d.between(start, end) for d in idle], axis=0)
                 if spans else np.zeros(0))
    names: Dict[str, dict] = defaultdict(lambda: {
        "count": 0, "total_s": 0.0, "self_s": 0.0, "busy_s": 0.0,
        "idle_s": 0.0, "own_idle_s": 0.0, "in_batch_s": 0.0, "fetches": 0,
        "fetch_s": 0.0, "fetch_bytes": 0, "stats": {}})
    child_s: Dict[int, float] = defaultdict(float)
    for sp in spans:
        if sp.parent is not None:
            child_s[id(sp.parent)] += sp.end - sp.start
    for k, sp in enumerate(spans):
        n = names[sp.name]
        dur = (sp.end - sp.start) * 1e-9
        n["count"] += 1
        n["total_s"] += dur
        n["in_batch_s"] += dur if sp.in_batch else 0.0
        n["self_s"] += dur - child_s[id(sp)] * 1e-9
        n["idle_s"] += float(span_idle[k]) * 1e-9
        n["busy_s"] += dur - float(span_idle[k]) * 1e-9

    for sp in spans:
        if sp.in_batch or sp.name == BATCH:
            st = names[sp.name]["stats"]
            for k, v in sp.stats.items():
                st[k] = st.get(k, 0) + v
    head_wait_us = sorted(sp.stats["head_wait_us"] for sp in spans
                          if sp.name == BATCH and "head_wait_us" in sp.stats)

    batch_fetches = 0
    fetch_parents: Dict[str, int] = defaultdict(int)
    sizes: Dict[int, List[float]] = defaultdict(lambda: [0, 0.0])
    for sp in spans:
        if sp.name != FETCH:
            continue
        dur = (sp.end - sp.start) * 1e-9
        nbytes = int(sp.stats.get("bytes", 0))
        sizes[nbytes][0] += 1
        sizes[nbytes][1] += dur
        fetch_parents[sp.parent.name if sp.parent else NO_SPAN] += 1
        seen = set()
        p: Optional[_Span] = sp
        while p is not None:
            if p.name not in seen:
                seen.add(p.name)
                n = names[p.name]
                n["fetches"] += 1
                n["fetch_s"] += dur
                n["fetch_bytes"] += nbytes
            p = p.parent
        batch_fetches += BATCH in seen

    a, b, owner = _innermost(spans, lo, hi)
    piece_idle = np.mean([d.between(a, b) for d in idle], axis=0) * 1e-9
    owned = np.bincount(owner + 1, weights=piece_idle,
                        minlength=len(spans) + 1)
    own_idle: Dict[str, float] = defaultdict(float)
    own_idle[NO_SPAN] = float(owned[0])
    for k, sp in enumerate(spans):
        own_idle[sp.name] += float(owned[k + 1])
        names[sp.name]["own_idle_s"] += float(owned[k + 1])

    # work of a batch whose ssh.batch span the profiler cut off (one open
    # when tracing started or still open when it stopped) lies outside
    # every batch; only such a batch, at either end, may leave any
    batch_iv = [(sp.start, sp.end) for sp in spans if sp.name == BATCH]
    first = min((a for a, _ in batch_iv), default=hi)
    last = max((b for _, b in batch_iv), default=lo)
    stray = [sp for sp in spans if not sp.in_batch and sp.name != BATCH
             and not sp.name.startswith("ssh.engine.")]
    top = sorted(sizes.items(), key=lambda kv: -kv[0])[:_SIZES]
    return {"window_s": window_s, "busy_s": window_s - idle_s,
            "idle_s": idle_s,
            "names": {k: dict(v) for k, v in names.items()},
            "own_idle": dict(own_idle),
            "batches": names[BATCH]["count"] if BATCH in names else 0,
            "head_wait_us": head_wait_us,
            "batch_fetches": batch_fetches,
            "fetch_parents": dict(fetch_parents),
            "stray": len(stray),
            "stray_between_batches": sum(first < sp.start < last
                                         for sp in stray),
            "fetch_sizes": [[k, v[0], v[1]] for k, v in top]}


def table(s: dict) -> List[str]:
    """One line per span name, longest total first, then the idle no span
    owns and the largest fetches."""
    out = []
    for name, n in sorted(s["names"].items(),
                          key=lambda kv: -kv[1]["total_s"]):
        out.append(
            f"span {name}: count={n['count']} total_s={n['total_s']:.6f} "
            f"self_s={n['self_s']:.6f} fetches={n['fetches']} "
            f"fetch_s={n['fetch_s']:.6f} fetch_bytes={n['fetch_bytes']} "
            f"busy_s={n['busy_s']:.6f} idle_s={n['idle_s']:.6f} "
            f"own_idle_s={n['own_idle_s']:.6f}"
            + "".join(f" {k}={v}" for k, v in sorted(n["stats"].items())))
    out.append(f"span {NO_SPAN}: own_idle_s="
               f"{s['own_idle'].get(NO_SPAN, 0.0):.6f} of idle_s="
               f"{s['idle_s']:.6f} in window_s={s['window_s']:.6f}")
    for nbytes, count, sec in s["fetch_sizes"]:
        out.append(f"fetch size bytes={nbytes}: count={count} "
                   f"seconds={sec:.6f}")
    return out


def _ms_per_batch(s: dict, *names: str) -> float:
    total = sum(s["names"].get(n, {}).get("in_batch_s", 0.0)
                for n in names)
    return 1e3 * total / s["batches"]


def _per_batch(s: dict, name: str, stat: str) -> float:
    return s["names"].get(name, {}).get("stats", {}).get(stat, 0) \
        / s["batches"]


def readings(s: dict) -> Dict[str, float]:
    """The per-layer numbers the spans give, by metric name; empty for a
    trace with no ``ssh.batch`` span (a program without the spans)."""
    if not s["batches"]:
        return {}
    b = s["names"][BATCH]
    wait = s["names"].get("ssh.engine.wait", {}).get("total_s", 0.0)
    p50, p95 = np.percentile(s["head_wait_us"] or [0], [50, 95]) / 1e3
    return {
        # device idle under the batches over their time, in %
        "batch_device_idle_pct": 100.0 * b["idle_s"] / b["total_s"],
        # the batcher waiting with no request queued, in % of the window
        "engine_wait_pct": 100.0 * wait / s["window_s"],
        # device→host fetches inside a batch, per batch
        "host_fetches_per_batch": s["batch_fetches"] / s["batches"],
        "encode_ms_per_batch": _ms_per_batch(s, "ssh.encode"),
        "probe_ms_per_batch": _ms_per_batch(s, "ssh.probe"),
        "lb_ms_per_batch": _ms_per_batch(s, "ssh.lb", "ssh.pairs",
                                         "ssh.lb_improved"),
        "dtw_ms_per_batch": _ms_per_batch(s, "ssh.dtw"),
        # requests over the compiled rows they were padded to, in %
        "batch_fill_pct": 100.0 * b["stats"].get("size", 0)
        / max(b["stats"].get("bucket", 0), 1),
        # the oldest request's wait before its batch began, in ms
        "head_wait_ms_p50": float(p50),
        "head_wait_ms_p95": float(p95),
        # pairs past the cascade, the archive rows gathered for them on
        # the host, and the pairs left for DTW after LB_Improved
        "lb_pairs_per_batch": _per_batch(s, "ssh.pairs", "pairs"),
        "union_rows_per_batch": _per_batch(s, "ssh.pairs", "union"),
        "dtw_pairs_per_batch": _per_batch(s, "ssh.dtw", "pairs"),
    }


def main(argv=None) -> int:
    """``python3 -m chipbench.spans <trace dir or .xplane.pb[.gz]>``, from
    the root of a checkout: the table and the readings of a kept trace
    (``chipbench/run.py --keep-trace <dir>``)."""
    import argparse
    import gzip
    import json
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("trace", type=Path)
    args = ap.parse_args(argv)
    path = args.trace
    if path.is_dir():
        path = reduce_trace.find_xplane(path)
    if path.suffix == ".gz":
        from jax.profiler import ProfileData
        s = summarize_profile(ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes())))
        for line in table(s):
            print(line)
    else:
        s = summarize(path, log=print)
    print(json.dumps(readings(s)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
