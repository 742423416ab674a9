"""Reduction of one profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

* The window is the host span ``bench.window`` that the harness opens
  around the traced calls.
* Device ops are the events of each TPU plane's ``XLA Ops`` line. The
  trace starts with the device idle (set-up ends in ``block_until_ready``)
  and stops right after the window's own ``block_until_ready``, so every
  device op in it belongs to the window. Ops are not clipped to the host
  span: the device's timestamps lead the host's by up to a few ms on a
  v5e, which clipping would cut off. ``busy_s`` is the union of their
  intervals, averaged over the TPU planes that ran anything.
* A device op's trace name is its HLO instruction as XLA prints it. A
  Mosaic kernel is an op whose HLO is a custom call to
  ``tpu_custom_call``; its time is ``kernel_s`` and every other op's
  (gathers, copies, the combine, the chain's rescale) is ``other_s``.
* An op that encloses others on its line, such as a ``while`` loop or its
  body around the ops it runs, is not an op of its own: only leaf ops add
  to ``kernel_s``, ``other_s`` and the breakdown. Every op, enclosing or
  not, counts towards ``busy_s``.
* Idle gaps are the stretches of the window in which no op ran on a
  device, each labelled by the innermost ``bench.*`` host span open at the
  gap's middle (``host`` where none is).
"""
from __future__ import annotations

import dataclasses
import glob
import os

WINDOW = "bench.window"
HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MOSAIC = 'custom_call_target="tpu_custom_call"'
TOP = 10
NAME_CHARS = 160      # an op's name in the breakdown: its HLO, cut here


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    kernel_s: float
    other_s: float
    ops: list            # [(name, seconds)], most time first
    gaps: list           # [(label, seconds)], longest first

    def breakdown(self) -> dict:
        return {"device_ops": [[n[:NAME_CHARS], s] for n, s in self.ops[:TOP]],
                "idle_gaps": [[n, s] for n, s in self.gaps[:TOP]]}


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {files}")
    return files[0]


def is_mosaic(name: str) -> bool:
    """Whether a device op, by its trace name, is a Mosaic kernel."""
    return MOSAIC in name


def union_length(intervals) -> tuple[float, list]:
    """Total length of the union of (start, end) intervals, and the merged
    intervals in order."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def enclosing(intervals) -> list[bool]:
    """For each (start, end) interval of one line, whether it encloses
    another of positive length, one that starts no earlier and ends no
    later; of two equal intervals the first encloses the second."""
    order = sorted(range(len(intervals)),
                   key=lambda i: (intervals[i][0], -intervals[i][1]))
    outer = [False] * len(intervals)
    stack = []                          # the intervals open at the start
    for i in order:
        s, e = intervals[i]
        while stack and intervals[stack[-1]][1] <= s:
            stack.pop()
        if stack and s < e <= intervals[stack[-1]][1]:
            outer[stack[-1]] = True
        stack.append(i)
    return outer


def _host_spans(planes):
    """(window, spans): the ``bench.window`` interval and every other
    ``bench.*`` host span, in ns."""
    window, spans = None, []
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(HOST_PREFIX):
                    continue
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name == WINDOW:
                    window = iv
                else:
                    spans.append((iv[0], iv[1], ev.name))
    return window, spans


def _label(spans, t: float) -> str:
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "host"


def reduce_planes(planes) -> Summary:
    planes = list(planes)               # ProfileData yields them once
    window, spans = _host_spans(planes)
    if window is None:
        raise RuntimeError(f"no {WINDOW} span in the trace")
    w0, w1 = window
    busy_total, kernel_ns, other_ns, n_dev = 0.0, 0.0, 0.0, 0
    by_name: dict[str, float] = {}
    gaps = []
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        intervals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            events = list(line.events)
            line_iv = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in events]
            intervals += line_iv
            for ev, (s, e), outer in zip(events, line_iv,
                                         enclosing(line_iv)):
                if outer:
                    continue
                by_name[ev.name] = by_name.get(ev.name, 0.0) + (e - s)
                if is_mosaic(ev.name):
                    kernel_ns += e - s
                else:
                    other_ns += e - s
        if not intervals:
            continue
        n_dev += 1
        busy, merged = union_length(intervals)
        busy_total += busy
        edges = ([min(w0, merged[0][0])] + [x for iv in merged for x in iv]
                 + [max(w1, merged[-1][1])])
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_label(spans, (a + b) / 2), (b - a) * 1e-9))
    n = max(n_dev, 1)
    ops = sorted(((k, v * 1e-9 / n) for k, v in by_name.items()),
                 key=lambda kv: -kv[1])
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(w1 - w0) * 1e-9, busy_s=busy_total * 1e-9 / n,
                   kernel_s=kernel_ns * 1e-9 / n, other_s=other_ns * 1e-9 / n,
                   ops=ops, gaps=gaps)


def reduce(path: str) -> Summary:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)
