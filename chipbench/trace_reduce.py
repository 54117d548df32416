"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

A TPU trace holds one plane per chip (``/device:TPU:<n>``) with a line of
XLA operations and a line of XLA programs ("modules"), and host planes whose
threads carry the benchmark's own spans (``jax.profiler.TraceAnnotation``
names starting with ``cb.``).  The window is the ``cb.window`` span; only
device time inside it counts.  Times are seconds; per-chip quantities are
averaged over the chips.  The profiler puts device and host events on clocks
that agree to about a millisecond (on a TPU v5e a program's device events
began ~1 ms before the host call that issued it), which is nothing against a
window of seconds but can shift the label of a short idle gap.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

SPAN_PREFIX = "cb."
WINDOW_SPAN = "cb.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"ppermute|send|recv|psum", re.IGNORECASE)
INSTRUCTION = re.compile(r"^%?([^\s=]+)\s*=")
TOP = 10


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float  # union of op intervals, per chip
    chips: int
    modules: dict  # program name -> [executions, seconds], per chip
    collective_s: float  # op time of collectives, per chip
    exposed_collective_s: float  # collective and no other op, per chip
    top_ops: list  # [[name, seconds per chip], ...]
    idle_gaps: list  # [[host span, seconds], ...], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module(self, pattern: str):
        """[executions, seconds] summed over programs whose name matches."""
        hits = [v for k, v in self.modules.items() if re.search(pattern, k)]
        if not hits:
            return None
        return [sum(h[0] for h in hits), sum(h[1] for h in hits)]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def subtract(a, b) -> list:
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


def op_name(event_name: str) -> str:
    """The HLO instruction's name: a TPU trace names each op event by its
    whole instruction text (``%fusion.12 = bf16[...] fusion(...)``)."""
    m = INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9


def reduce(planes) -> Reduced:
    """``planes``: an iterable of objects with ``name`` and ``lines`` (as
    ``jax.profiler.ProfileData.planes``), each line with ``name`` and
    ``events`` (``name``, ``start_ns``, ``duration_ns``)."""
    spans, devices = [], {}
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = {ln.name: list(_events(ln)) for ln in plane.lines
                                        if ln.name in (OPS_LINE, MODULES_LINE)}
            continue
        for ln in plane.lines:
            spans.extend(e for e in _events(ln) if e[0].startswith(SPAN_PREFIX))
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    lo, hi = windows[0]
    spans = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]

    busy = coll = exposed = 0.0
    modules: dict = {}
    op_time: dict = {}
    gaps: list = []
    for dev in devices.values():
        ops = [(op_name(n), s, e) for n, s, e in dev.get(OPS_LINE, []) if e > lo and s < hi]
        merged = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy += length(merged)
        for n, s, e in ops:
            op_time[n] = op_time.get(n, 0.0) + min(e, hi) - max(s, lo)
        c = union(clip([(s, e) for n, s, e in ops if COLLECTIVE.search(n)], lo, hi))
        other = union(clip([(s, e) for n, s, e in ops if not COLLECTIVE.search(n)],
                           lo, hi))
        coll += length(c)
        exposed += length(subtract(c, other))
        for n, s, e in dev.get(MODULES_LINE, []):
            if lo <= s < hi:
                name = re.sub(r"\(\d+\)$", "", n)
                rec = modules.setdefault(name, [0, 0.0])
                rec[0] += 1
                rec[1] += min(e, hi) - s
        gaps.extend(subtract([[lo, hi]], merged))
    n = len(devices)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    gaps.sort(key=lambda g: g[0] - g[1])
    return Reduced(
        window_s=hi - lo, busy_s=busy / n, chips=n,
        modules={k: [v[0] / n, v[1] / n] for k, v in modules.items()},
        collective_s=coll / n, exposed_collective_s=exposed / n,
        top_ops=[[k, v / n] for k, v in top],
        idle_gaps=[[_label(spans, s, e), e - s] for s, e in gaps[:TOP]])


def _label(spans, s, e) -> str:
    """The host span that covers most of the gap [s, e)."""
    best, cover = "none", 0.0
    for n, a, b in spans:
        c = min(b, e) - max(a, s)
        if c > cover:
            best, cover = n, c
    return best


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(path).planes)
