"""Reduce a device trace of the window by the program's own names.

Beside ``trace.py``, whose numbers it leaves as they are, this module reads
what the program puts into the trace itself:

* the scope of each leaf device operation: the first of :data:`SCOPES`
  (``jax.named_scope`` names of the model and the train step) in the
  operation's op-name path, ``unscoped`` where none is.  The op name is
  taken from the event (its HLO text's ``metadata={op_name=...}``, or a
  stat that carries the path) and, where the event carries none, from the
  compiled HLO text of the window's executable, joined by instruction name;
* device seconds per scope inside the window, averaged over the devices,
  with the largest unscoped operations and the op name of each;
* the idle time of a device inside each ``repro.*`` host span (the
  program's spans, ``repro.obs.trace.span``), averaged over the devices;
  a span that nests in another counts its idle in both.

Input is plain data, as in ``trace.py``: per device ``(name, start_ns,
duration_ns)`` events, host spans in the same form, and a map from
instruction name to op name.  ``load_program_trace`` builds them from the
``.xplane.pb`` that ``jax.profiler`` writes.

The harness's ``run.py`` does not call this module yet; ``scope_report.py``
does, on one cell, for ``PERF.md``.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.chip.trace import (Event, clip, leaves, length, short_name,
                                   subtract, union, window_of)

SCOPES = ("attention", "mlp", "head", "optimizer")
UNSCOPED = "unscoped"
PROGRAM_SPAN_PREFIX = "repro."
OP_NAME = re.compile(r'op_name="([^"]*)"')
WORD = re.compile(r"\w+")
# an HLO instruction line: ``[ROOT] [%]name = type opcode(...), ...``
HLO_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+) = ")
# stats of a device event that may carry its op-name path
PATH_STATS = ("tf_op", "long_name", "op_name", "name")


def op_name_of(name: str, stats: Optional[dict] = None,
               hlo: Optional[Dict[str, str]] = None) -> Optional[str]:
    """The op-name path of a device event; None where nothing carries it."""
    m = OP_NAME.search(name)
    if m:
        return m.group(1)
    for key in PATH_STATS:
        v = (stats or {}).get(key)
        if isinstance(v, str):
            m = OP_NAME.search(v)
            if m:
                return m.group(1)
            if "/" in v and " = " not in v:
                return v
    if hlo:
        return hlo.get(short_name(name))
    return None


def scope_of(op_name: Optional[str]) -> str:
    """The first of :data:`SCOPES` that is a word of the op-name path
    (``transpose(jvp(attention))`` is ``attention``), else ``unscoped``."""
    for w in WORD.findall(op_name or ""):
        if w in SCOPES:
            return w
    return UNSCOPED


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> op name, from compiled HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = HLO_LINE.match(line)
        if m:
            op = OP_NAME.search(line)
            if op:
                out[m.group(1)] = op.group(1)
    return out


def scope_times(devices: Dict[str, Sequence[Event]], spans: Sequence[Event],
                op_names: Dict[str, Optional[str]], top: int = 5) -> dict:
    """Device seconds per scope in the window, averaged over devices.

    ``op_names`` maps each event name of ``devices`` to its op name (None
    where unknown).  Returns ``{"seconds": {scope: s}, "unscoped_top":
    [[instruction, s, op name], ...], "named_share": share of leaf device
    time whose op name was known}``."""
    if not devices:
        raise ValueError("scopes: no device operations")
    lo, hi = window_of(spans)
    seconds = {s: 0.0 for s in SCOPES + (UNSCOPED,)}
    unscoped: Dict[str, float] = {}
    named = total = 0.0
    for evs in devices.values():
        for n, s, d in leaves(evs):
            t = length(clip([(s, s + d)], lo, hi))
            if t <= 0:
                continue
            op = op_names.get(n)
            scope = scope_of(op)
            seconds[scope] += t
            total += t
            if op is not None:
                named += t
            if scope == UNSCOPED:
                unscoped[n] = unscoped.get(n, 0.0) + t
    nd = len(devices)
    worst = sorted(unscoped.items(), key=lambda x: -x[1])[:top]
    return {
        "seconds": {k: v / nd / 1e9 for k, v in seconds.items()},
        "unscoped_top": [[short_name(n), t / nd / 1e9, op_names.get(n)]
                         for n, t in worst],
        "named_share": named / total if total else 0.0,
    }


def idle_in_spans(devices: Dict[str, Sequence[Event]], spans: Sequence[Event],
                  prefix: str = PROGRAM_SPAN_PREFIX) -> Dict[str, float]:
    """Seconds in which a device ran nothing while the host was inside each
    span whose name starts with ``prefix``, inside the window, averaged over
    the devices."""
    if not devices:
        raise ValueError("scopes: no device operations")
    lo, hi = window_of(spans)
    names = sorted({n for n, _, _ in spans if n.startswith(prefix)})
    inside = {g: clip(union([(s, s + d) for n, s, d in spans if n == g]),
                      lo, hi) for g in names}
    idle_in = {g: 0.0 for g in names}
    for evs in devices.values():
        busy = clip(union([(s, s + d) for _, s, d in leaves(evs)]), lo, hi)
        idle = subtract([(lo, hi)], busy)
        for g, ivs in inside.items():
            idle_in[g] += length(idle) - length(subtract(idle, ivs))
    return {g: t / len(devices) / 1e9 for g, t in idle_in.items()}


def load_program_trace(trace_dir: str, sample: int = 5):
    """``(devices, spans, op_names, samples)`` from the newest
    ``.xplane.pb`` under ``trace_dir``: the ``XLA Ops`` line of each TPU
    device plane, the host's ``bench.*`` and ``repro.*`` spans, the op name
    each device event carries (None where it carries none), and the name
    and stats of the first ``sample`` device events as they are."""
    from jax.profiler import ProfileData

    from benchmarks.chip.trace import DEVICE_PLANE, OPS_LINE

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"scopes: no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    op_names: Dict[str, Optional[str]] = {}
    samples: List[Tuple[str, dict]] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    devices.setdefault(plane.name, []).append(
                        (e.name, e.start_ns, e.duration_ns))
                    if e.name in op_names:
                        continue
                    op = op_name_of(e.name)
                    stats = None
                    if op is None or len(samples) < sample:
                        stats = dict(e.stats)
                        op = op or op_name_of(e.name, stats)
                    if len(samples) < sample:
                        samples.append((e.name, stats))
                    op_names[e.name] = op
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(("bench.",
                                                   PROGRAM_SPAN_PREFIX)))
    return devices, spans, op_names, samples
