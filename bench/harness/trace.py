"""Reduction of a profiler trace to device metrics.

``load`` turns an ``.xplane.pb`` into plain data: planes, their lines, and
events ``[name, start_ns, duration_ns]``.
Everything after that works on the plain data, so the tests can build a
trace by hand.  Device planes are ``/device:TPU:<n>``; their ``XLA Ops``
line holds one event per operation that ran, and ``XLA Modules`` one per
compiled program.  The host's ``TraceAnnotation`` spans sit on host planes
on the same clock; the span ``bench.window`` bounds the measured window.

Definitions (each per device, then averaged over the devices used):

* busy: the union of the op intervals inside the window;
* idle share: 1 - busy / window;
* a program's or an op's device time: the sum of its events' durations
  inside the window;
* idle gaps: the complement of busy in the window, each attributed to the
  innermost of the benchmark's host spans open at its midpoint (``host``
  when none is);
* an op is named by its HLO instruction and the program around it
  (``jit_prefill/fusion.3``); a Pallas kernel is a ``tpu_custom_call``
  whose instruction carries the kernel's name (``moe_pack.1``) or, behind
  ``lax.platform_dependent``, the branch's (``branch_0_fun.7``).
"""

from __future__ import annotations

import heapq
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW = "bench.window"
OPS, MODULES = "XLA Ops", "XLA Modules"


def load(path: str) -> Dict:
    """Plain data from an ``.xplane.pb`` (device and host planes only)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        dev = DEVICE.match(plane.name)
        if not dev and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            evs = []
            for e in line.events:
                evs.append([e.name, int(e.start_ns), int(e.duration_ns)])
            lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def devices(tr: Dict) -> List[Dict]:
    return [p for p in tr["planes"] if DEVICE.match(p["name"])]


def events(plane: Dict, line: str) -> List[list]:
    for ln in plane["lines"]:
        if ln["name"] == line:
            return ln["events"]
    return []


def host_spans(tr: Dict) -> List[list]:
    out = []
    for p in tr["planes"]:
        if p["name"].startswith("/host:"):
            for ln in p["lines"]:
                out.extend(ln["events"])
    return out


def window(tr: Dict) -> Tuple[int, int]:
    spans = [e for e in host_spans(tr) if e[0] == WINDOW]
    if not spans:
        raise ValueError(f"no '{WINDOW}' span in the trace")
    e = max(spans, key=lambda e: e[2])
    return e[1], e[1] + e[2]


def clip(iv: Iterable[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def union(iv: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy(plane: Dict, lo: int, hi: int) -> List[Tuple[int, int]]:
    return union(clip(((e[1], e[1] + e[2]) for e in events(plane, OPS)), lo, hi))


def gaps(busy_iv: Sequence[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    out, t = [], lo
    for a, b in busy_iv:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def innermost(spans: Sequence[list], times: Sequence[int]) -> List[str]:
    """For each of ``times``, the name of the innermost span open at it
    (``start <= t < start + duration``): the shortest, and of equal ones
    the first in ``spans``; ``host`` where none is open.  One sweep over
    the spans and the times sorted by start, with a heap of the spans open
    so far keyed by (duration, index): one that has ended leaves it when it
    comes to the top."""
    starts = sorted(range(len(spans)), key=lambda i: spans[i][1])
    heap: List[Tuple[int, int]] = []
    out = ["host"] * len(times)
    j = 0
    for k in sorted(range(len(times)), key=times.__getitem__):
        t = times[k]
        while j < len(starts) and spans[starts[j]][1] <= t:
            heapq.heappush(heap, (spans[starts[j]][2], starts[j]))
            j += 1
        while heap and spans[heap[0][1]][1] + heap[0][0] <= t:
            heapq.heappop(heap)
        if heap:
            out[k] = spans[heap[0][1]][0]
    return out


def op_name(text: str) -> str:
    """An op event's name is its HLO instruction (``%fusion.3 = ...``):
    keep the instruction's own name."""
    return text.split(" = ", 1)[0].lstrip("%")


def module_name(text: str) -> str:
    """``jit_prefill(1234)`` -> ``jit_prefill``."""
    return text.split("(", 1)[0]


def attributed_ops(plane: Dict, lo: int, hi: int) -> List[Tuple[str, str, float]]:
    """(op text, enclosing program, seconds inside the window) per op."""
    mods = sorted((e[1], e[1] + e[2], module_name(e[0]))
                  for e in events(plane, MODULES))
    out, j = [], 0
    for e in sorted(events(plane, OPS), key=lambda e: e[1]):
        a, b = max(e[1], lo), min(e[1] + e[2], hi)
        if b <= a:
            continue
        while j < len(mods) and mods[j][1] <= e[1]:
            j += 1
        mod = mods[j][2] if j < len(mods) and mods[j][0] <= e[1] else ""
        out.append((e[0], mod, (b - a) * 1e-9))
    return out


def reduce(tr: Dict, n_devices: int, spans: Sequence[str] = (),
           top: int = 10) -> Dict:
    """Device busy time, op and program times, and the breakdown.

    ``spans``: the names of the benchmark's own host spans; an idle gap is
    attributed to the innermost of them open at its midpoint."""
    lo, hi = window(tr)
    planes = sorted(devices(tr), key=lambda p: int(DEVICE.match(p["name"])[1]))
    planes = planes[:n_devices]
    if not planes:
        raise ValueError("no TPU device plane in the trace")
    n = len(planes)
    names = set(spans) - {WINDOW}
    host = [e for e in host_spans(tr)
            if e[0] in names and e[1] < hi and e[1] + e[2] > lo]
    busy_s = 0.0
    ops, modules, counts, idle = (defaultdict(float), defaultdict(float),
                                  defaultdict(float), defaultdict(float))
    attributed = []
    for p in planes:
        iv = busy(p, lo, hi)
        busy_s += sum(b - a for a, b in iv) * 1e-9 / n
        these = attributed_ops(p, lo, hi)
        attributed.append(these)
        for text, mod, sec in these:
            ops[f"{mod}/{op_name(text)}"] += sec / n
        for e in events(p, MODULES):
            a, b = max(e[1], lo), min(e[1] + e[2], hi)
            if b > a:
                modules[module_name(e[0])] += (b - a) * 1e-9 / n
                counts[module_name(e[0])] += 1 / n
        idle_iv = gaps(iv, lo, hi)
        for (a, b), name in zip(idle_iv, innermost(
                host, [(a + b) // 2 for a, b in idle_iv])):
            idle[name] += (b - a) * 1e-9 / n
    rank = lambda d: [[k, v] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy_s, "devices": n,
            "modules": dict(modules), "module_calls": dict(counts),
            "ops": attributed,
            "breakdown": {"device_ops": rank(ops), "idle_gaps": rank(idle)}}


def op_seconds(red: Dict, pattern: str, module: Optional[str] = None) -> float:
    """Mean device seconds per chip of the ops whose HLO text matches
    ``pattern`` (inside programs matching ``module``, when given)."""
    rx = re.compile(pattern)
    mx = re.compile(module) if module else None
    total = sum(sec for dev in red["ops"] for text, mod, sec in dev
                if rx.search(text) and (mx is None or mx.search(mod)))
    return total / red["devices"]


def module_seconds(red: Dict, pattern: str) -> Tuple[float, float]:
    """(device seconds per chip, calls per chip) of the matching programs."""
    rx = re.compile(pattern)
    s = sum(v for k, v in red["modules"].items() if rx.search(k))
    c = sum(v for k, v in red["module_calls"].items() if rx.search(k))
    return s, c
