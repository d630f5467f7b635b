"""Seeded traffic for a cell: arrival schedules and the loops that drive
``TimeSeriesDB.submit``.

A traffic file (``chipbench/traffic/<name>.json``) names the loop:

* ``{"loop": "open", "process": "poisson", "rate_qps": r, ...}`` — each
  request is sent at its intended time whatever the server does, and its
  latency runs from that intended time to its answer (no coordinated
  omission);
* ``{"loop": "closed", "clients": c, ...}`` — each of ``c`` clients sends
  its next query when its answer arrives.

Every seed gets the same amount of work: the open loop's gaps are drawn
once from the file's ``shape_seed`` and scaled so that exactly
``round(rate * seconds)`` requests fall in the window; ``--seed`` only
rotates the gap sequence and orders the query pool.  The arrival
processes are copies of ``repro.loadgen.arrivals``.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Callable, List, Optional

import numpy as np


# -- arrival processes (copies of repro.loadgen.arrivals) -----------------

def poisson_arrivals(rate_qps: float, n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rate_qps, size=n))


def mmpp_arrivals(rate_qps: float, n: int, seed: int,
                  burst_factor: float = 4.0,
                  dwell_s: float = 0.25) -> np.ndarray:
    """Two-state Markov-modulated Poisson process, equal mean dwell in
    a quiet and a burst state, long-run mean ``rate_qps``."""
    rng = np.random.default_rng(seed)
    r_quiet = 2.0 * rate_qps / (1.0 + burst_factor)
    rates = (r_quiet, burst_factor * r_quiet)
    out = np.empty(n)
    t, state = 0.0, 0
    t_switch = rng.exponential(dwell_s)
    for k in range(n):
        gap = rng.exponential(1.0 / rates[state])
        while t + gap > t_switch:
            frac = (t_switch - t) / gap
            t = t_switch
            state = 1 - state
            t_switch = t + rng.exponential(dwell_s)
            gap = (1.0 - frac) * gap * rates[1 - state] / rates[state]
        t += gap
        out[k] = t
    return out


def diurnal_arrivals(rate_qps: float, n: int, seed: int,
                     period_s: float = 20.0,
                     depth: float = 0.8) -> np.ndarray:
    """Sinusoidal ramp by Lewis-Shedler thinning, mean ``rate_qps``."""
    rng = np.random.default_rng(seed)
    peak = rate_qps * (1.0 + depth)
    out = np.empty(n)
    t, k = 0.0, 0
    while k < n:
        t += rng.exponential(1.0 / peak)
        rate_t = rate_qps * (1.0 + depth * np.sin(2 * np.pi * t / period_s))
        if rng.uniform() * peak <= rate_t:
            out[k] = t
            k += 1
    return out


PROCESSES = {"poisson": poisson_arrivals, "mmpp": mmpp_arrivals,
             "diurnal": diurnal_arrivals}


def schedule(traffic: dict, seconds: float, seed: int,
             rate_qps: Optional[float] = None) -> np.ndarray:
    """Intended send times (seconds from the window's start) of an open
    loop: the file's gap sequence, rotated by ``seed``, scaled so that
    ``round(rate * seconds)`` requests fall inside the window."""
    rate = float(rate_qps if rate_qps is not None else traffic["rate_qps"])
    n = max(1, int(round(rate * seconds)))
    fn = PROCESSES[traffic.get("process", "poisson")]
    times = fn(rate, n, int(traffic["shape_seed"]),
               **traffic.get("process_args", {}))
    gaps = np.diff(np.concatenate([[0.0], times]))
    gaps = np.roll(gaps, int(seed) % n)
    gaps *= seconds * n / (n + 1) / gaps.sum()
    return np.cumsum(gaps)


def query_order(pool_size: int, n: int, seed: int) -> np.ndarray:
    """Pool rows for ``n`` requests: successive permutations of the pool
    drawn from ``seed``, so no query repeats before the pool is spent."""
    rng = np.random.default_rng([int(seed), 1])
    reps = -(-n // pool_size)
    return np.concatenate([rng.permutation(pool_size)
                           for _ in range(reps)])[:n]


# -- the loops ------------------------------------------------------------

@dataclasses.dataclass
class WindowResult:
    """What one measured window saw, request by request."""
    seconds: float
    sent_at: List[float]            # intended send time, window clock
    done_at: List[Optional[float]]  # answer time, window clock (None: none)
    pool_ids: List[int]
    results: List[object]           # the SearchResult of each request
    errors: List[Optional[str]]
    send_lag_s: List[float]         # how late the generator sent each

    @property
    def attempted(self) -> int:
        return len(self.sent_at)

    @property
    def answered(self) -> List[int]:
        return [k for k, r in enumerate(self.results) if r is not None]

    @property
    def failed(self) -> int:
        return self.attempted - len(self.answered)

    def latencies_ms(self) -> np.ndarray:
        return np.asarray([(self.done_at[k] - self.sent_at[k]) * 1e3
                           for k in self.answered])

    def completed_in_window(self) -> int:
        return sum(1 for k in self.answered
                   if self.done_at[k] <= self.seconds)


def _collect(futs, t0: float, grace_s: float, res: WindowResult) -> None:
    deadline = t0 + res.seconds + grace_s
    for k, fut in enumerate(futs):
        try:
            res.results[k] = fut.result(
                timeout=max(0.0, deadline - time.perf_counter()))
            # result() can return before the setting thread has run the
            # done-callback that stamps the answer's time
            while res.done_at[k] is None:
                time.sleep(1e-4)
        except Exception as exc:            # timeout or a failed request
            res.errors[k] = f"{type(exc).__name__}: {exc}"
            res.results[k] = None
            res.done_at[k] = None


def run_open(submit: Callable, pool: np.ndarray, times: np.ndarray,
             pool_ids: np.ndarray, seconds: float,
             grace_s: float = 60.0) -> WindowResult:
    """Send request k at ``times[k]`` whatever the server does."""
    n = int(times.shape[0])
    res = WindowResult(seconds=seconds, sent_at=[float(t) for t in times],
                       done_at=[None] * n,
                       pool_ids=[int(i) for i in pool_ids],
                       results=[None] * n, errors=[None] * n,
                       send_lag_s=[0.0] * n)
    futs = []
    t0 = time.perf_counter()

    def stamp(k):
        def _cb(_fut):
            res.done_at[k] = time.perf_counter() - t0
        return _cb

    for k in range(n):
        delay = t0 + times[k] - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        res.send_lag_s[k] = time.perf_counter() - t0 - times[k]
        fut = submit(pool[pool_ids[k]])
        fut.add_done_callback(stamp(k))
        futs.append(fut)
    _collect(futs, t0, grace_s, res)
    return res


def run_closed(submit: Callable, pool: np.ndarray, clients: int,
               pool_ids: np.ndarray, seconds: float,
               grace_s: float = 60.0) -> WindowResult:
    """``clients`` clients, each sending its next query when its answer
    arrives, until the window closes."""
    res = WindowResult(seconds=seconds, sent_at=[], done_at=[], pool_ids=[],
                       results=[], errors=[], send_lag_s=[])
    done_q: "queue.SimpleQueue" = queue.SimpleQueue()
    futs = []
    lock = threading.Lock()
    t0 = time.perf_counter()

    def send():
        with lock:
            k = len(futs)
            res.sent_at.append(time.perf_counter() - t0)
            res.done_at.append(None)
            res.pool_ids.append(int(pool_ids[k % len(pool_ids)]))
            res.results.append(None)
            res.errors.append(None)
            res.send_lag_s.append(0.0)
            fut = submit(pool[res.pool_ids[k]])
            futs.append(fut)

        def _cb(_fut, k=k):
            res.done_at[k] = time.perf_counter() - t0
            done_q.put(k)
        fut.add_done_callback(_cb)

    for _ in range(clients):
        send()
    while True:
        left = t0 + seconds - time.perf_counter()
        if left <= 0:
            break
        try:
            done_q.get(timeout=left)
        except queue.Empty:
            break
        if time.perf_counter() - t0 < seconds:
            send()
    _collect(list(futs), t0, grace_s, res)
    return res
