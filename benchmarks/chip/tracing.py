"""From a ``jax.profiler`` trace to numbers.

``load(dir)`` turns the ``.xplane.pb`` under a trace directory into plain
event lists (device ops per chip, host annotations); everything after that
works on those lists, so the tests can feed it a small recorded trace.
A TPU trace names each op by its whole HLO instruction; the lists keep the
instruction's name alone (``_gqa_pallas.11``, ``while.7``). Ops nest on
the device line (a layer loop holds the ops of its body).

Host spans are the benchmark's own ``jax.profiler.TraceAnnotation``s
(``bench.*``), written around each call into the program; the traced
window is the ``bench.window`` span.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]          # (name, start_s, end_s)

WINDOW = "bench.window"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all",
    re.I)
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OP_LINES = ("XLA Ops",)


def load(trace_dir: str) -> dict:
    """{"device": {chip: [Event]}, "host": [Event]} from a trace dir."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no xplane trace under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    device: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in _OP_LINES:
                device.setdefault(m.group(1), []).extend(
                    (_op_name(e.name), e.start_ns * 1e-9, e.end_ns * 1e-9)
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                host.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"device": device, "host": host}


def _op_name(hlo: str) -> str:
    return hlo.split(" = ", 1)[0].lstrip("%")


def window(tr: dict) -> Tuple[float, float]:
    spans = [(s, e) for n, s, e in tr["host"] if n == WINDOW]
    if not spans:
        raise ValueError("the trace holds no bench.window span")
    return spans[0]


def _clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(tr: dict) -> float:
    """Seconds in which an op ran on the device, averaged over chips."""
    lo, hi = window(tr)
    per = [sum(e - s for s, e in union((s, e) for _, s, e in
                                      _clip(evs, lo, hi)))
           for evs in tr["device"].values()]
    return sum(per) / len(per) if per else 0.0


def op_seconds(tr: dict, pattern: str) -> Optional[float]:
    """Device seconds of ops whose name matches ``pattern``, summed over
    the window and averaged over chips; None when no op matches."""
    lo, hi = window(tr)
    rx = re.compile(pattern)
    per, hit = [], False
    for evs in tr["device"].values():
        t = 0.0
        for n, s, e in _clip(evs, lo, hi):
            if rx.search(n):
                t += e - s
                hit = True
        per.append(t)
    return sum(per) / len(per) if hit else None


def collective_seconds(tr: dict) -> Optional[float]:
    return op_seconds(tr, COLLECTIVE.pattern)


def leaves(events: Iterable[Event]) -> List[Event]:
    """The ops that hold no other op (a loop's body ops, not the loop)."""
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    parent = [False] * len(evs)
    stack: List[int] = []
    for i, (_n, s, _e) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent[stack[-1]] = True
        stack.append(i)
    return [ev for ev, p in zip(evs, parent) if not p]


def top_ops(tr: dict, k: int = 10) -> List[List]:
    """Device seconds by op in the window, over the ops that hold no other
    op, with the instruction number dropped (``fusion.12`` -> ``fusion``),
    averaged over chips."""
    lo, hi = window(tr)
    tot: Dict[str, float] = defaultdict(float)
    n_chips = max(len(tr["device"]), 1)
    for evs in tr["device"].values():
        for n, s, e in leaves(_clip(evs, lo, hi)):
            tot[re.sub(r"\.\d+$", "", n)] += (e - s) / n_chips
    return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(tr: dict, k: int = 10) -> List[List]:
    """The device's idle time in the window (first chip), by the host
    span that was open at the middle of each gap (the innermost one)."""
    lo, hi = window(tr)
    if not tr["device"]:
        return []
    first = sorted(tr["device"])[0]
    busy = union((s, e) for _, s, e in _clip(tr["device"][first], lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    host = [(n, s, e) for n, s, e in tr["host"] if n != WINDOW]
    tot: Dict[str, float] = defaultdict(float)
    for s, e in gaps:
        mid = 0.5 * (s + e)
        open_ = [(he - hs, n) for n, hs, he in host if hs <= mid < he]
        tot[min(open_)[1] if open_ else "host: no bench span"] += e - s
    return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]
