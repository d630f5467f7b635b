"""95th percentile of the window's request latencies, in ms, each from
its intended send time to its answer (a request never answered counts
with the whole wait): the tail, kept per layer in a cell whose runs
spread too widely for it to be held to a bound."""
import numpy as np


def read(ctx):
    lat = ctx["counters"]["latency_ms"]
    return float(np.percentile(lat, 95)) if lat else None
