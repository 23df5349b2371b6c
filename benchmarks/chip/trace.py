"""Reduce a profiler trace of the measured window to the benchmark's numbers.

Input is plain data, so that the reduction can be checked on a synthetic
event list: per device, the operations that ran on it as ``(name, start_ns,
duration_ns)``; the harness's host spans (``bench.window``, ``bench.batch``,
``bench.dispatch``, ``bench.wait``) in the same form and on the same clock.
``load_xplane`` builds both from the ``.xplane.pb`` that ``jax.profiler``
writes.

On the TPU an operation's event is named by its HLO text
(``%fusion.3 = bf16[...] fusion(...)``), and a loop's event (``%while``)
spans the operations of its body: only operations that contain no other
(leaves) count, and each is named by its instruction name (``fusion.3``).

* busy: the union of a device's leaf operation intervals inside the
  window; idle share = 1 - busy / window, averaged over the devices;
* exposed collective time: the part of a device's collective operations
  (all-gather, all-reduce, reduce-scatter, collective-permute, all-to-all,
  with their ``-start``/``-done`` halves) that no other operation on that
  device covers, averaged over the devices;
* top operations: device seconds per operation name, averaged over devices;
* idle gaps: the longest gaps between busy intervals on any device, each
  named by the host span that overlaps it most;
* idle inside host spans: for each of ``bench.batch``, ``bench.dispatch``
  and ``bench.wait``, the idle time of a device that falls inside that
  span, averaged over the devices.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns

COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)")
OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."
GAP_SPANS = ("bench.batch", "bench.dispatch", "bench.wait")


def short_name(name: str) -> str:
    """``%fusion.3 = bf16[8]{0} fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def is_collective(name: str) -> bool:
    """By the instruction's name or, in HLO text, by its opcode."""
    if COLLECTIVE.match(short_name(name)):
        return True
    if " = " in name:
        op = OPCODE.search(name.split(" = ", 1)[1])
        return op is not None and COLLECTIVE.match(op.group(1)) is not None
    return False


def leaves(events: Sequence[Event]) -> List[Event]:
    """The events that contain no other event (a loop's event spans its
    body's operations)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    parent = [False] * len(evs)
    open_: List[int] = []
    for i, (_, s, d) in enumerate(evs):
        while open_ and evs[open_[-1]][1] + evs[open_[-1]][2] <= s:
            open_.pop()
        if open_:
            parent[open_[-1]] = True
        open_.append(i)
    return [e for e, p in zip(evs, parent) if not p]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals; the result is sorted and disjoint."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out = []
    b = union(b)
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def window_of(spans: Sequence[Event]) -> Tuple[float, float]:
    w = [(s, s + d) for n, s, d in spans if n == "bench.window"]
    if not w:
        raise ValueError("trace: no bench.window span")
    return max(w, key=lambda x: x[1] - x[0])


def summarize(devices: Dict[str, Sequence[Event]], spans: Sequence[Event],
              top: int = 10) -> dict:
    """The window's device numbers; see the module docstring."""
    if not devices:
        raise ValueError("trace: no device operations")
    lo, hi = window_of(spans)
    win = hi - lo
    busy, exposed, n_coll, op_time, gaps = [], [], 0, {}, []
    host = [(n, s, s + d) for n, s, d in spans if n in GAP_SPANS]
    inside = {g: clip(union([(s, e) for n, s, e in host if n == g]), lo, hi)
              for g in GAP_SPANS}
    idle_in = {g: 0.0 for g in GAP_SPANS}
    for dev, evs in sorted(devices.items()):
        ops = [(short_name(n), s, d, is_collective(n))
               for n, s, d in leaves(evs) if s < hi and s + d > lo]
        merged = clip(union([(s, s + d) for _, s, d, _ in ops]), lo, hi)
        busy.append(length(merged))
        coll = [(s, s + d) for _, s, d, c in ops if c]
        n_coll += len(coll)
        other = [(s, s + d) for _, s, d, c in ops if not c]
        exposed.append(length(subtract(clip(union(coll), lo, hi), other)))
        for n, s, d, _ in ops:
            op_time[n] = op_time.get(n, 0.0) + length(clip([(s, s + d)], lo, hi))
        idle = subtract([(lo, hi)], merged)
        for g, ivs in inside.items():
            idle_in[g] += length(idle) - length(subtract(idle, ivs))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                gaps.append((ge - gs, gs, ge))
    nd = len(busy)

    def cause(gs, ge):
        best, name = 0.0, "other"
        for n, s, e in host:
            ov = min(e, ge) - max(s, gs)
            if ov > best:
                best, name = ov, n
        return name

    gaps.sort(reverse=True)
    return {
        "devices": nd,
        "window_s": win / 1e9,
        "busy_s": sum(busy) / nd / 1e9,
        "idle_share": 1.0 - sum(busy) / nd / win,
        "collective_ops": n_coll,
        "exposed_collective_s": sum(exposed) / nd / 1e9,
        "top_ops": [[n, t / nd / 1e9] for n, t in
                    sorted(op_time.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": [[cause(gs, ge), g / 1e9] for g, gs, ge in gaps[:top]],
        "idle_in_span_s": {g: t / nd / 1e9 for g, t in idle_in.items()},
    }


def load_xplane(trace_dir: str):
    """``(devices, spans)`` from the newest ``.xplane.pb`` under
    ``trace_dir``: the ``XLA Ops`` line of each TPU device plane, and the
    host's ``bench.*`` spans."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"trace: no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(HOST_SPAN_PREFIX))
    return devices, spans
