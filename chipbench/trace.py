"""From a profiler trace (``.xplane.pb``) to device numbers.

The JAX profiler writes one XSpace: a plane per device
(``/device:TPU:<n>``) with a line of whole programs (``XLA Modules``,
one event per execution, named ``jit_<function>(<id>)``) and a line of
the operations inside them (``XLA Ops``), and host planes whose lines
carry ``jax.profiler.TraceAnnotation`` spans. Every timestamp is on one
clock, so the benchmark's own host spans (``chipbench.*``) line up with
the device's work.

``summarize`` reduces a trace to what the per-layer metrics read:

* the traced window: the host span ``chipbench.window``;
* busy time per device: the union of its operations' intervals inside
  the window (``busy_s``, averaged over devices);
* device time per program: the summed durations of its executions
  inside the window, keyed by function name (``jit_ingest(7)`` ->
  ``ingest``), with the number of executions;
* the operations that took most device time (``program:op``; an
  operation that holds others, as a ``while`` does, counts their time
  too), and the device's idle time split by what the host was doing
  then (inside a ``chipbench.*`` span, by its name; outside any,
  ``driver``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

import numpy as np

WINDOW = "chipbench.window"
_PROGRAM = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")
_DEVICE = re.compile(r"^/device:(?:TPU|GPU):\d+$")


def program_name(event_name: str) -> str:
    """``jit_ingest(12)`` -> ``ingest``."""
    return _PROGRAM.match(event_name).group(1)


def op_name(event_name: str) -> str:
    """``%while.3 = (s32[]...) while(...)`` -> ``while.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                           # mean over devices
    devices: int
    programs: Dict[str, Tuple[float, int]]  # name -> (seconds, executions)
    top_ops: List[Tuple[str, float]]
    idle_by_host: List[Tuple[str, float]]   # per device, averaged

    def program_s(self, names) -> Tuple[float, int]:
        s = sum(self.programs.get(n, (0.0, 0))[0] for n in names)
        c = sum(self.programs.get(n, (0.0, 0))[1] for n in names)
        return s, c


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} .xplane.pb under {log_dir}")
    return found[0]


def _union(iv: np.ndarray) -> np.ndarray:
    """Disjoint sorted cover of (start, end) intervals."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.r_[True, iv[1:, 0] > ends[:-1]]
    starts = iv[new, 0]
    idx = np.flatnonzero(new)
    last = np.r_[idx[1:] - 1, len(iv) - 1]
    return np.stack([starts, ends[last]], axis=1)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Total overlap of two disjoint sorted interval sets."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            tot += hi - lo
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return tot


def summarize(profile, top: int = 10) -> Summary:
    """Reduce a ``jax.profiler.ProfileData`` (see the module docstring)."""
    devices, host = [], {}
    for plane in profile.planes:
        if _DEVICE.match(plane.name):
            lines = {ln.name: [(e.name, e.start_ns, e.end_ns)
                               for e in ln.events] for ln in plane.lines}
            devices.append(lines)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("chipbench."):
                        host.setdefault(e.name, []).append(
                            (e.start_ns, e.end_ns))
    if not devices:
        raise ValueError("the trace holds no device plane")
    if WINDOW in host:
        lo = min(s for s, _ in host[WINDOW])
        hi = max(e for _, e in host[WINDOW])
    else:
        ends = [(s, e) for d in devices for evs in d.values()
                for _, s, e in evs]
        lo, hi = min(s for s, _ in ends), max(e for _, e in ends)
    spans = {name: _union(_clip(np.asarray(v, np.float64).reshape(-1, 2),
                                lo, hi))
             for name, v in host.items() if name != WINDOW}
    busy, programs, ops, idle = [], {}, {}, {}
    for d in devices:
        evs = d.get("XLA Ops") or [e for v in d.values() for e in v]
        iv = _clip(np.asarray([(s, e) for _, s, e in evs],
                              np.float64).reshape(-1, 2), lo, hi)
        cover = _union(iv)
        busy.append(float((cover[:, 1] - cover[:, 0]).sum()))
        mods = sorted(d.get("XLA Modules", []), key=lambda m: m[1])
        m_start = np.asarray([m[1] for m in mods], np.float64)
        for name, s, e in mods:
            w = min(e, hi) - max(s, lo)
            if w > 0:
                p = program_name(name)
                t, c = programs.get(p, (0.0, 0))
                programs[p] = (t + w * 1e-9, c + 1)
        # each operation under the program whose execution holds it
        for name, s, e in evs:
            w = min(e, hi) - max(s, lo)
            if w <= 0:
                continue
            j = int(np.searchsorted(m_start, s, side="right")) - 1
            owner = (program_name(mods[j][0]) + ":"
                     if j >= 0 and s < mods[j][2] else "")
            key = owner + op_name(name)
            ops[key] = ops.get(key, 0.0) + w
        # idle: the window less the busy cover, split by host activity
        gaps = np.stack([np.r_[lo, cover[:, 1]], np.r_[cover[:, 0], hi]],
                        axis=1)
        gaps = gaps[gaps[:, 1] > gaps[:, 0]]
        total = float((gaps[:, 1] - gaps[:, 0]).sum())
        named = 0.0
        for name, sp in spans.items():
            o = _overlap(gaps, sp)
            idle[name] = idle.get(name, 0.0) + o
            named += o
        idle["driver"] = idle.get("driver", 0.0) + max(0.0, total - named)
    n = len(devices)
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=float(np.mean(busy)) * 1e-9,
        devices=n,
        programs=programs,
        top_ops=sorted(((k, v * 1e-9 / n) for k, v in ops.items()),
                       key=lambda kv: -kv[1])[:top],
        idle_by_host=sorted(((k, v * 1e-9 / n) for k, v in idle.items()
                             if v > 0), key=lambda kv: -kv[1])[:top],
    )


def load(log_dir: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(find_xplane(log_dir))
