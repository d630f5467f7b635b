"""Seeded data for a benchmark cell: one stream per run, cut into windows.

The generators are copies of ``repro.data.timeseries.synthetic_ecg`` and
``random_walk``, kept here so that no change to the program can change
what the benchmark measures on.  A run draws one stream from ``--seed``:
its first ``rows + length - 1`` points hold the archive (every stride-1
window, z-normalised), and the points after them hold the queries
(windows at stride ``length``, so no query overlaps an archive window:
new series searched against history).  The stream is made on the host
(a few MB); the windows are cut and normalised on the device in one
jitted call.
"""
from __future__ import annotations

import functools

import numpy as np


def _pqrst_beat(t: np.ndarray) -> np.ndarray:
    """One heartbeat on t in [0, 1): P, Q, R, S, T Gaussian bumps."""
    centers = np.array([0.18, 0.36, 0.40, 0.44, 0.70])
    widths = np.array([0.060, 0.022, 0.030, 0.022, 0.080])
    amps = np.array([0.15, -0.18, 1.20, -0.25, 0.30])
    out = np.zeros_like(t)
    for c, w, a in zip(centers, widths, amps):
        out += a * np.exp(-0.5 * ((t - c) / w) ** 2)
    return out


def synthetic_ecg(n_points: int, seed: int, hz: int = 250,
                  bpm: float = 72.0, noise: float = 0.03) -> np.ndarray:
    """ECG-like stream: jittered beats, baseline wander, sensor noise."""
    rng = np.random.default_rng(seed)
    out = np.zeros(n_points, np.float32)
    samples_per_beat = int(hz * 60.0 / bpm)
    pos = 0
    while pos < n_points:
        jitter = rng.normal(1.0, 0.05)
        amp = rng.normal(1.0, 0.08)
        nb = max(16, int(samples_per_beat * jitter))
        t = np.arange(nb) / nb
        seg = amp * _pqrst_beat(t)
        end = min(pos + nb, n_points)
        out[pos:end] += seg[: end - pos].astype(np.float32)
        pos += nb
    tt = np.arange(n_points) / hz
    out += 0.08 * np.sin(2 * np.pi * 0.25 * tt).astype(np.float32)
    out += rng.normal(0.0, noise, n_points).astype(np.float32)
    return out


def random_walk(n_points: int, seed: int) -> np.ndarray:
    """x_t = x_{t-1} + N(0, 1), the UCR suite's random-walk generator."""
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.normal(0.0, 1.0, n_points)).astype(np.float32)


GENERATORS = {"ecg": synthetic_ecg, "randomwalk": random_walk}


def stream_layout(dataset: dict, n_queries: int) -> dict:
    """Where the archive and the queries lie in the run's one stream."""
    m, rows = int(dataset["length"]), int(dataset["rows"])
    archive_points = (rows - 1) * int(dataset.get("stride", 1)) + m
    return {"length": m, "rows": rows, "archive_points": archive_points,
            "query_start": archive_points, "n_queries": n_queries,
            "points": archive_points + n_queries * m}


def make_stream(dataset: dict, n_queries: int, seed: int) -> np.ndarray:
    """The run's stream on the host, float32."""
    lay = stream_layout(dataset, n_queries)
    gen = GENERATORS[dataset["generator"]]
    return gen(lay["points"], seed)


@functools.lru_cache(maxsize=None)
def _window_fn(length: int, chunk: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def windows(stream, starts):
        """(n_chunks, chunk) start offsets -> (n_chunks * chunk, length)
        z-normalised windows of ``stream``."""
        offs = jnp.arange(length, dtype=jnp.int32)

        def one_chunk(s):
            w = stream[s[:, None] + offs[None, :]]
            mu = jnp.mean(w, axis=1, keepdims=True)
            sd = jnp.std(w, axis=1, keepdims=True) + 1e-8
            return (w - mu) / sd

        out = jax.lax.map(one_chunk, starts)
        return out.reshape(-1, length)

    return windows


def device_windows(stream_dev, starts: np.ndarray, length: int,
                   chunk: int = 1024):
    """Z-normalised windows at ``starts`` of a device-resident stream,
    made on the device: (len(starts), length) float32."""
    n = int(starts.shape[0])
    chunk = min(chunk, n)
    pad = (-n) % chunk
    s = np.concatenate([starts, np.repeat(starts[-1:], pad)]).astype(
        np.int32).reshape(-1, chunk)
    out = _window_fn(length, chunk)(stream_dev, s)
    return out[:n] if pad else out


def archive_starts(dataset: dict) -> np.ndarray:
    return np.arange(int(dataset["rows"]), dtype=np.int64) * int(
        dataset.get("stride", 1))


def query_starts(dataset: dict, n_queries: int) -> np.ndarray:
    lay = stream_layout(dataset, n_queries)
    m = lay["length"]
    return lay["query_start"] + np.arange(n_queries, dtype=np.int64) * m
