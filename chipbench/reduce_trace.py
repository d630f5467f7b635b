"""From a JAX profiler trace to the numbers the per-layer readers use.

``summarize`` reads the ``.xplane.pb`` the profiler wrote and returns a
plain dict:

* ``window_s`` — the length of the host span named ``WINDOW`` (the
  measured window, opened and closed by the harness);
* ``busy_s`` — per device, the union of the intervals in which an
  operation ran on it, clipped to the window, averaged over devices;
* ``ops`` — device seconds per (program, operation) pair, where the
  program is the jitted module the operation ran in (``jit_<name>``);
* ``idle`` — the device's idle gaps inside the window, each labelled
  with the host span that overlapped it most tightly (what the host was
  doing while the device waited), summed per label.

The reduction runs the same way on every PR.  It reads the device plane's
"XLA Ops" and "XLA Modules" lines; ``tests/chipbench`` checks it on a
trace written in the profiler's own format.
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW = "chipbench.window"
_ID = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (n, 2) intervals sorted by start into disjoint intervals."""
    if not len(iv):
        return iv
    out = [list(iv[0])]
    for a, b in iv[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return np.asarray(out)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if not len(iv):
        return iv.reshape(0, 2)
    iv = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return iv[iv[:, 1] > iv[:, 0]]


def load_planes(pd):
    """(device planes, host planes) of a ``jax.profiler.ProfileData`` as
    plain lists: [(plane, {line: [(event, start_ns, end_ns), ...]}), ...]."""
    dev, host = [], []
    for plane in pd.planes:
        lines: Dict[str, list] = defaultdict(list)
        for ln in plane.lines:          # threads may share a line name
            lines[ln.name].extend(_events(ln))
        lines = dict(lines)
        if plane.name.startswith("/device:TPU:"):
            dev.append((plane.name, lines))
        elif plane.name.startswith("/host:"):
            host.append((plane.name, lines))
    return dev, host


def _op_lines(lines: Dict[str, list]):
    """(op events, module events) of a device plane: the lines named
    "XLA Ops" and "XLA Modules"."""
    return lines.get("XLA Ops", []), lines.get("XLA Modules", [])


def _module_of(ops, mods) -> List[str]:
    """The module each op started inside (by time), '' when none."""
    if not mods:
        return [""] * len(ops)
    mods = sorted(mods, key=lambda e: e[1])
    starts = np.asarray([m[1] for m in mods])
    out = []
    for name, s, _ in ops:
        i = int(np.searchsorted(starts, s, side="right")) - 1
        if i >= 0 and mods[i][1] <= s <= mods[i][2]:
            out.append(_ID.sub("", mods[i][0]))
        else:
            out.append("")
    return out


def _host_spans(host) -> Tuple[List[str], np.ndarray]:
    names, iv = [], []
    for _, lines in host:
        for lname, evs in lines.items():
            for name, s, e in evs:
                if name != WINDOW:
                    names.append(name)
                    iv.append((s, e))
    return names, np.asarray(iv, np.float64).reshape(-1, 2)


def _label_gaps(gaps: np.ndarray, names: List[str], spans: np.ndarray,
                limit: int = 400) -> Dict[str, List[float]]:
    """Idle seconds and gap count per host label, for the ``limit``
    longest gaps."""
    out: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    if not len(gaps):
        return out
    order = np.argsort(gaps[:, 0] - gaps[:, 1])[:limit]
    for a, b in gaps[order]:
        label = "no host span"
        if len(spans):
            ov = np.minimum(spans[:, 1], b) - np.maximum(spans[:, 0], a)
            hit = np.nonzero(ov > 0.5 * (b - a))[0]
            if len(hit):
                dur = spans[hit, 1] - spans[hit, 0]
                label = names[int(hit[np.argmin(dur)])]
            elif (ov > 0).any():
                label = names[int(np.argmax(ov))]
        out[label][0] += (b - a) * 1e-9
        out[label][1] += 1
    return out


def summarize(path: Path) -> dict:
    """The reduction of the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData
    return summarize_profile(ProfileData.from_file(str(path)))


def summarize_profile(pd) -> dict:
    dev, host = load_planes(pd)
    window = [(s, e) for _, lines in host for evs in lines.values()
              for name, s, e in evs if name == WINDOW]
    if not window:
        raise ValueError(f"the trace has no host span {WINDOW!r}")
    lo, hi = window[0]
    names, spans = _host_spans(host)
    busy, ops = [], defaultdict(float)
    gaps_all = []
    for _, lines in dev:
        op_evs, mod_evs = _op_lines(lines)
        if not op_evs:
            continue
        for (name, s, e), mod in zip(op_evs, _module_of(op_evs, mod_evs)):
            a, b = max(s, lo), min(e, hi)
            if b > a:   # an op's event is named by its whole HLO line
                ops[(mod, name.split(" = ")[0])] += (b - a) * 1e-9
        iv = np.asarray(sorted((s, e) for _, s, e in op_evs), np.float64)
        u = _union(_clip(iv.reshape(-1, 2), lo, hi))
        busy.append(float((u[:, 1] - u[:, 0]).sum()) * 1e-9 if len(u)
                    else 0.0)
        edges = np.concatenate([[lo], u.reshape(-1), [hi]]).reshape(-1, 2)
        gaps_all.append(edges[edges[:, 1] > edges[:, 0]])
    if not busy:
        raise ValueError("the trace has no device operation")
    gaps = (np.concatenate(gaps_all) if gaps_all
            else np.zeros((0, 2)))
    idle = _label_gaps(gaps, names, spans)
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": float(np.mean(busy)),
            "devices": len(busy),
            "ops": {f"{m}|{o}": v for (m, o), v in ops.items()},
            "idle": {k: v[0] for k, v in idle.items()},
            "idle_count": {k: v[1] for k, v in idle.items()}}


def kernel_seconds(summary: dict, module: str,
                   op_pattern: Optional[str] = None) -> float:
    """Device seconds of the ops inside programs named ``jit_<module>``
    (matching ``op_pattern``, when given)."""
    rx = re.compile(op_pattern) if op_pattern else None
    total = 0.0
    for key, sec in summary["ops"].items():
        mod, op = key.split("|", 1)
        if mod == f"jit_{module}" and (rx is None or rx.search(op)):
            total += sec
    return total


def breakdown(summary: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the device operations that took the
    most time, and the idle time by what the host was doing."""
    ops = sorted(summary["ops"].items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(summary["idle"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[f"{k} (x{summary['idle_count'][k]})", v]
                          for k, v in idle]}
