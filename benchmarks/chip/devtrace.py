"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into plain
lists (nanoseconds on the profiler's clock, which host and device planes
share): per TPU plane its "XLA Ops" (nested: a loop op holds its body's
ops) and "XLA Modules" events, and the host events of the thread that
carries the harness's spans. ``summarize`` turns them into what the
per-layer readers need, for the span named ``window``:

* ``busy_s``: union of the device's op intervals, averaged over chips;
* per ``engine.step`` span: device busy time inside it, and the device
  time of the decode-step and prefill programs;
* a breakdown: device ops by self time, and idle time on the device
  summed by what the host was doing (harness span / innermost other
  host event).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

HARNESS = ("engine.step", "generator.submit", "generator.wait")
DECODE_MODULE = "jit__step_fn"       # the engine's jitted decode step
PREFILL_MODULE = "jit__prefill_fn"   # the engine's jitted prefill
TOP = 10

Interval = Tuple[float, float]


def load(tdir: str) -> Dict:
    """The trace under ``tdir`` as lists of [start, end, name]."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {tdir}, got {paths}")
    pd = ProfileData.from_file(paths[0])
    devices: Dict[str, Dict] = {}
    host: List = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    d[key] = [[e.start_ns, e.start_ns + e.duration_ns,
                               e.name] for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [[e.start_ns, e.start_ns + e.duration_ns, e.name]
                       for e in line.events]
                if any(e[2] == "window" for e in evs):
                    host = evs
    return {"devices": devices, "host": host}


def union(intervals: Sequence[Sequence]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[List[float]] = []
    for a, b, *_ in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(merged: List[Interval], starts: List[float], a: float,
            b: float) -> float:
    """Length of [a, b] that the disjoint ``merged`` cover."""
    i = max(0, bisect.bisect_right(starts, a) - 1)
    tot = 0.0
    while i < len(merged) and merged[i][0] < b:
        tot += max(0.0, min(b, merged[i][1]) - max(a, merged[i][0]))
        i += 1
    return tot


def self_times(ops: Sequence[Sequence]) -> Dict[str, float]:
    """Seconds of each op name net of the ops nested inside it."""
    tot: Dict[str, float] = {}
    stack: List[List] = []       # [end, name, child_ns, dur]

    def close(item):
        tot[item[1]] = tot.get(item[1], 0.0) + (item[3] - item[2]) / 1e9

    for a, b, name in sorted(ops, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= a:
            close(stack.pop())
        if stack:
            stack[-1][2] += b - a
        stack.append([b, short_name(name), 0.0, b - a])
    while stack:
        close(stack.pop())
    return tot


_OP = re.compile(r"%?([\w.\-]+) = \(?([a-z0-9]+\[[^\]]*\])")


def short_name(hlo: str) -> str:
    """``%fusion.7 = bf16[16,3072]{1,0:...} fusion(...)`` ->
    ``fusion.7 bf16[16,3072]``."""
    m = _OP.match(hlo)
    return f"{m.group(1)} {m.group(2)}" if m else hlo[:64]


def _window(host: List) -> Interval:
    wins = [e for e in host if e[2] == "window"]
    if not wins:
        raise RuntimeError("the trace holds no 'window' span")
    return wins[0][0], wins[0][1]


def _clip(evs, w0, w1):
    return [[max(a, w0), min(b, w1), n] for a, b, n in evs
            if b > w0 and a < w1]


def summarize(trace: Dict, rec) -> Dict:
    """Device numbers of the traced window; ``rec`` gives the chips."""
    host = trace["host"]
    w0, w1 = _window(host)
    names = sorted(trace["devices"],
                   key=lambda n: int(n.rsplit(":", 1)[1]))[:rec.cell.chips]
    if not names:
        raise RuntimeError("the trace holds no TPU plane")
    devs = [trace["devices"][n] for n in names]
    merged = [union(_clip(d["ops"], w0, w1)) for d in devs]
    busy = sum(b - a for m in merged for a, b in m) / len(merged)

    steps = sorted(e for e in host if e[2] == "engine.step"
                   and w0 <= e[0] < w1)
    m0, s0 = merged[0], [a for a, _ in merged[0]]
    per_step = [{"t0": a, "t1": b, "busy_s": covered(m0, s0, a, b) / 1e9,
                 "decode_s": 0.0, "prefill_s": 0.0} for a, b, _ in steps]
    # a program run belongs to the step whose span it overlaps most: the
    # device's clock runs a fraction of a millisecond off the host's, so
    # a run may start before its step's span does, or end after it
    starts = [a for a, _, _ in steps]
    for ma, mb, mn in devs[0]["modules"]:
        key = ("decode_s" if mn.startswith(DECODE_MODULE + "(") else
               "prefill_s" if mn.startswith(PREFILL_MODULE + "(") else None)
        if key is None:
            continue
        i = bisect.bisect_right(starts, mb)
        best, over = None, 0.0
        for j in range(max(0, i - 2), i):
            o = min(mb, steps[j][1]) - max(ma, steps[j][0])
            if o > over:
                best, over = j, o
        if best is not None:
            per_step[best][key] += (mb - ma) / 1e9

    ops = self_times(_clip(devs[0]["ops"], w0, w1))
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    idle = _idle_by_host(m0, host, w0, w1)
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return {"window_s": (w1 - w0) / 1e9, "busy_s": busy / 1e9,
            "steps": per_step,
            "breakdown": {"device_ops": [[n, v] for n, v in top_ops],
                          "idle_gaps": [[n, v] for n, v in top_idle]}}


def _idle_by_host(merged: List[Interval], host: List, w0: float,
                  w1: float) -> Dict[str, float]:
    """Device idle seconds inside [w0, w1], summed by what the host was
    doing: the harness span and the innermost other host event over each
    piece of a gap (pieces end where a host event starts or ends)."""
    gaps, t = [], w0
    for a, b in merged:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < w1:
        gaps.append((t, w1))
    evs = sorted((e for e in host if e[2] != "window"),
                 key=lambda e: e[0])
    starts = [e[0] for e in evs]
    out: Dict[str, float] = {}
    for a, b in gaps:
        i = bisect.bisect_left(starts, b)
        near = [e for e in evs[max(0, i - 2000):i] if e[1] > a]
        cuts = sorted({a, b} | {x for e in near for x in e[:2]
                                if a < x < b})
        for lo, hi in zip(cuts, cuts[1:]):
            mid = (lo + hi) / 2
            over = [e for e in near if e[0] <= mid < e[1]]
            span = next((e[2] for e in over if e[2] in HARNESS), "window")
            inner = [e for e in over if e[2] not in HARNESS]
            what = (min(inner, key=lambda e: e[1] - e[0])[2] if inner
                    else "host")
            key = f"{span}/{what}"
            out[key] = out.get(key, 0.0) + (hi - lo) / 1e9
    return out
