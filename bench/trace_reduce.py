"""Reduce a JAX profiler trace to device busy time, idle share, the
device operations that took most time, and idle gaps labelled by the
harness span that was open on the host.

Input is the ``.xplane.pb`` that ``jax.profiler.trace`` writes. Device
operations are the events of the ``XLA Ops`` line of every
``/device:*`` plane; on a host with no device plane (the CPU backend)
they are the host events that carry an ``hlo_op`` stat. Host spans are
the ``jax.profiler.TraceAnnotation`` events whose names start with
``bench.``. All times are in the trace's own nanosecond clock.

Busy time is the union of a device's operation intervals inside the
window span (``bench.window``); the idle share is one minus busy over
the window, averaged over the devices.
"""
from __future__ import annotations

import collections
import glob
import heapq
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OP_NAME_CHARS = 120
_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_name(name: str) -> str:
    """An HLO op's trace name without layouts, cut to ``OP_NAME_CHARS``:
    enough to tell a fusion's program and shapes apart."""
    return _LAYOUT.sub("", name).lstrip("%")[:OP_NAME_CHARS]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _stats(event) -> Dict[str, object]:
    try:
        return dict(event.stats)
    except (AttributeError, TypeError, ValueError):
        return {}


def extract(profile) -> Tuple[Dict[str, List[Tuple[float, float, str]]],
                              List[Tuple[float, float, str]]]:
    """(device events by device, host spans) from a ``ProfileData``.

    Device events are ``(start_ns, end_ns, name)`` keyed by plane (or by
    device ordinal on the CPU backend); host spans are
    ``(start_ns, end_ns, name)`` of the ``bench.*`` annotations."""
    device: Dict[str, List[Tuple[float, float, str]]] = \
        collections.defaultdict(list)
    spans: List[Tuple[float, float, str]] = []
    cpu_ops: Dict[str, List[Tuple[float, float, str]]] = \
        collections.defaultdict(list)
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
            for line in ops:
                for ev in line.events:
                    device[plane.name].append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         op_name(ev.name)))
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
                    continue
                st = _stats(ev)
                if "hlo_op" in st:
                    key = f"cpu:{st.get('device_ordinal', 0)}"
                    cpu_ops[key].append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         str(st["hlo_op"])))
    return (dict(device) if device else dict(cpu_ops)), spans


def merge(intervals: Iterable[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Union of intervals, clipped to [lo, hi], sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] that ``busy`` (merged) leaves."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost_segments(spans: Sequence[Tuple[float, float, str]]
                       ) -> List[Tuple[float, float, str]]:
    """Disjoint ``(start, end, name)`` pieces of the time the spans cover,
    each named by the innermost span open there (the latest opened; the
    harness's spans nest). The window span is left out."""
    bounds = []
    for i, (s, e, name) in enumerate(spans):
        if name != WINDOW_SPAN and e > s:
            bounds.append((s, 1, i))
            bounds.append((e, 0, i))
    bounds.sort()
    heap: List[Tuple[float, int]] = []
    closed = set()
    out: List[Tuple[float, float, str]] = []
    prev = None
    for t, opening, i in bounds:
        while heap and heap[0][1] in closed:
            heapq.heappop(heap)
        if heap and prev is not None and t > prev:
            out.append((prev, t, spans[heap[0][1]][2]))
        if opening:
            heapq.heappush(heap, (-spans[i][0], i))
        else:
            closed.add(i)
        prev = t
    return out


def label_gaps(idle: Sequence[Interval],
               spans: Sequence[Tuple[float, float, str]]
               ) -> Dict[str, float]:
    """Idle nanoseconds by the innermost host span open at each instant.

    A gap that starts in one span and ends in another is shared between
    them; idle time covered by no span but the window is ``host.other``."""
    segs = innermost_segments(spans)
    out: Dict[str, float] = collections.defaultdict(float)
    j = 0
    for gs, ge in idle:
        while j < len(segs) and segs[j][1] <= gs:
            j += 1
        covered = 0.0
        k = j
        while k < len(segs) and segs[k][0] < ge:
            d = min(ge, segs[k][1]) - max(gs, segs[k][0])
            if d > 0:
                out[segs[k][2]] += d
                covered += d
            k += 1
        if ge - gs - covered > 0:
            out["host.other"] += ge - gs - covered
    return dict(out)


def reduce_events(device: Dict[str, List[Tuple[float, float, str]]],
                  spans: Sequence[Tuple[float, float, str]],
                  window: Optional[Interval] = None, top: int = 10
                  ) -> Dict[str, object]:
    """Busy and idle seconds, top device ops and labelled idle gaps.

    ``window`` defaults to the ``bench.window`` span. Busy time and the
    op totals are averaged over the devices."""
    if window is None:
        win = [s for s in spans if s[2] == WINDOW_SPAN]
        if not win:
            raise ValueError("trace holds no bench.window span")
        window = (win[0][0], win[0][1])
    lo, hi = window
    if not device:
        raise ValueError("trace holds no device operation")
    n = len(device)
    busy_ns = 0.0
    ops: Dict[str, float] = collections.defaultdict(float)
    idle: Dict[str, float] = collections.defaultdict(float)
    for events in device.values():
        merged = merge(((s, e) for s, e, _ in events), lo, hi)
        busy_ns += sum(e - s for s, e in merged)
        for s, e, name in events:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                ops[name] += d
        for name, ns in label_gaps(gaps(merged, lo, hi), spans).items():
            idle[name] += ns
    window_s = (hi - lo) * 1e-9
    busy_s = busy_ns / n * 1e-9

    def ranked(d):
        return [[k, v / n * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "devices": n, "device_ops": ranked(ops),
            "idle_gaps": ranked(idle)}


def reduce_trace(trace_dir: str, top: int = 10) -> Dict[str, object]:
    """Read the newest ``.xplane.pb`` under ``trace_dir`` and reduce it."""
    from jax.profiler import ProfileData
    device, spans = extract(ProfileData.from_file(find_xplane(trace_dir)))
    return reduce_events(device, spans, top=top)
