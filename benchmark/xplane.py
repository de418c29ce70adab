"""Reduce a profiler trace (``.xplane.pb``) to device busy time, idle
gaps and the operations that took the time.

The window is the host annotation the harness opens around the measured
window (``bench.window``).  A device's busy time is the union of the
intervals in which one of its operations ran (the ``XLA Ops`` line of its
plane), clipped to the window; an idle gap is a stretch of the window in
which none ran.  Each gap is attributed to the host annotation it
overlaps most among those whose names start with ``serve.dispatch(``, or
to ``"none"``.

Operations nest (a ``while`` holds its body's operations), so each is
charged its self time: its time less that of the operations nested in
it.  An operation is named ``<program>/<instruction>``, the program
being the ``XLA Modules`` event that holds it.

From a reduction come the per-layer shares: the device's idle share, and
the share of its roofline that a window's work reached.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

WINDOW = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                      # averaged over the devices
    devices: int
    device_ops: List[Tuple[str, float]]    # (name, seconds), most first
    idle_gaps: List[Tuple[str, float]]     # (host annotation, seconds)


def idle_share(r: Optional[Reduction]) -> Optional[float]:
    """Percent of the window in which no operation ran on the device."""
    if r is None or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)


def roofline_share(r: Optional[Reduction], work, peaks: dict
                   ) -> Optional[float]:
    """Percent of its roofline that the device reached: the least time the
    chip's peaks allow for ``work`` (``(ops, bytes)`` of each request),
    max(ops / peak FLOP/s, bytes / peak bytes/s) summed over requests,
    over the device's busy time."""
    if r is None or r.busy_s <= 0 or not work:
        return None
    flops = float(peaks["bf16_flops_per_s"])
    bw = float(peaks["hbm_bytes_per_s"])
    least = sum(max(ops / flops, nbytes / bw) for ops, nbytes in work)
    return 100.0 * least / r.busy_s


def find_trace(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def union(intervals: np.ndarray) -> np.ndarray:
    """Merge ``[start, end)`` rows into disjoint, sorted intervals."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.flatnonzero(np.r_[new[1:], True])
    return np.stack([starts, ends[last]], axis=1)


def clip(intervals: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(intervals) == 0:
        return intervals.reshape(0, 2)
    iv = np.stack([np.maximum(intervals[:, 0], lo),
                   np.minimum(intervals[:, 1], hi)], axis=1)
    return iv[iv[:, 1] > iv[:, 0]]


def gaps(busy: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The stretches of ``[lo, hi)`` that disjoint sorted ``busy`` leaves."""
    edges = np.concatenate([[lo], busy.reshape(-1), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def attribute(gap_iv: np.ndarray, host: Sequence[Tuple[str, float, float]]
              ) -> List[str]:
    """For each gap, the host annotation that overlaps it most."""
    names = []
    for g0, g1 in gap_iv:
        best, best_overlap = "none", 0.0
        for name, h0, h1 in host:
            overlap = min(g1, h1) - max(g0, h0)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        names.append(best)
    return names


def self_times(ops: Sequence[Tuple[str, float, float]], lo: float,
               hi: float) -> Dict[str, float]:
    """Time of each named operation inside ``[lo, hi)``, less the time of
    the operations nested inside it."""
    out: Dict[str, float] = {}
    stack: List[list] = []     # [name, end, clipped time, children's time]

    def close(entry):
        out[entry[0]] = out.get(entry[0], 0.0) + entry[2] - entry[3]

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        t = max(0.0, min(e, hi) - max(s, lo))
        if stack:
            stack[-1][3] += t
        stack.append([name, e, t, 0.0])
    while stack:
        close(stack.pop())
    return out


def reduce_events(window: Tuple[float, float],
                  device_ops: Dict[str, Sequence[Tuple[str, float, float]]],
                  host: Sequence[Tuple[str, float, float]],
                  top: int = 10) -> Reduction:
    """The reduction on plain events: ``device_ops`` maps a device to its
    ``(name, start_ns, end_ns)`` operations, ``host`` lists the host
    annotations to attribute gaps to."""
    lo, hi = window
    busy_total = 0.0
    per_op: Dict[str, float] = {}
    all_gaps: List[Tuple[str, float]] = []
    host = sorted(host, key=lambda h: h[1])
    for ops in device_ops.values():
        iv = np.array([(s, e) for _, s, e in ops], float).reshape(-1, 2)
        busy = union(clip(iv, lo, hi))
        busy_total += float(np.sum(busy[:, 1] - busy[:, 0]))
        for name, t in self_times(ops, lo, hi).items():
            per_op[name] = per_op.get(name, 0.0) + t
        gap_iv = gaps(busy, lo, hi)
        longest = gap_iv[np.argsort(gap_iv[:, 0] - gap_iv[:, 1],
                                    kind="stable")[:top]]
        for name, (g0, g1) in zip(attribute(longest, host), longest):
            all_gaps.append((name, (g1 - g0) / 1e9))
    n = max(1, len(device_ops))
    ops_sorted = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return Reduction(
        window_s=(hi - lo) / 1e9, busy_s=busy_total / n / 1e9,
        devices=len(device_ops),
        device_ops=[(k, v / n / 1e9) for k, v in ops_sorted],
        idle_gaps=sorted(all_gaps, key=lambda g: -g[1])[:top])


def _named_ops(ops, modules):
    """``(name, start_ns, end_ns)`` of each operation, named
    ``<program>/<instruction>`` (``%fusion.3 = f32[...] ...`` gives
    ``fusion.3``)."""
    mods = sorted((m.start_ns, m.start_ns + m.duration_ns, m.name)
                  for m in modules)
    starts = [m[0] for m in mods]
    out = []
    for ev in ops:
        s = ev.start_ns
        i = bisect.bisect_right(starts, s) - 1
        prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
        inst = ev.name.split(" = ", 1)[0].lstrip("%")
        out.append((f"{prog}/{inst}", s, s + ev.duration_ns))
    return out


def load(path: str, attribute_prefix: str = "serve.dispatch(",
         top: int = 10) -> Optional[Reduction]:
    """Reduce one ``.xplane.pb``; ``None`` when it holds no window or no
    device operations."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = None
    host: List[Tuple[str, float, float]] = []
    device_ops: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: list(line.events) for line in plane.lines}
            device_ops[plane.name] = _named_ops(lines.get(OPS_LINE, ()),
                                                lines.get(MODULES_LINE, ()))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name.startswith(attribute_prefix):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    if window is None or not any(device_ops.values()):
        return None
    return reduce_events(window, device_ops, host, top)
