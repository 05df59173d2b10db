"""From a profiler trace to device busy and idle time, top operations,
idle gaps named by the harness's host spans, and exposed collective time.

:func:`load_events` reads an ``.xplane.pb`` with JAX's own reader and keeps
two kinds of events: the operations of each device plane's ``XLA Ops`` line,
and the harness's host spans (``TraceAnnotation`` names that start with
``bench:``). Everything else works on that plain list of events, so the
arithmetic is checked on a small recorded trace without a chip.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
                        r"|allreduce|allgather|reducescatter", re.I)


def load_events(path: str) -> dict:
    """{"devices": {id: [(op, start_ns, end_ns)]}, "modules": {id: [(program,
    start_ns, end_ns)]}, "spans": [(name, start_ns, end_ns)]}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, modules, spans = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                into = devices if line.name == OPS_LINE else modules
                into.setdefault(int(m.group(2)), []).extend(
                    (e.name, float(e.start_ns), float(e.end_ns)) for e in line.events)
            elif not m:
                spans.extend((e.name, float(e.start_ns), float(e.end_ns))
                             for e in line.events if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "modules": modules, "spans": spans}


def short_name(op: str, module: str | None = None) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` in ``jit_step(123)`` -> ``jit_step/fusion.3``."""
    name = op.split(" = ")[0].lstrip("%")
    return f"{module.split('(')[0]}/{name}" if module else name


def self_each(intervals) -> list:
    """The self time of each (start, end) interval, in their order: its
    length less that of the intervals nested inside it (a loop's body runs
    inside the loop's own event). An interval that only overlaps another, as
    an asynchronous collective overlaps compute, is not nested in it."""
    out = [0.0] * len(intervals)
    stack = []  # [index, end, nested time]
    for i in sorted(range(len(intervals)), key=lambda i: (intervals[i][0], -intervals[i][1])):
        s, e = intervals[i]
        while stack and stack[-1][1] <= s:
            j, _, nested = stack.pop()
            out[j] = intervals[j][1] - intervals[j][0] - nested
        if stack and e <= stack[-1][1]:
            stack[-1][2] += e - s
        stack.append([i, e, 0.0])
    for j, _, nested in stack:
        out[j] = intervals[j][1] - intervals[j][0] - nested
    return out


def module_at(modules):
    """A function from a time to the name of the program run (``modules``:
    (name, start, end)) that holds it, else None."""
    mods = sorted(modules, key=lambda x: x[1])
    starts = [s for _, s, _ in mods]

    def at(t):
        i = bisect.bisect_right(starts, t) - 1
        return mods[i][0] if i >= 0 and mods[i][2] >= t else None

    return at


def self_times(ops, modules=()) -> dict:
    """:func:`self_each` of the ops, summed by :func:`short_name`."""
    module_of = module_at(modules)
    out = defaultdict(float)
    for (name, s, _), t in zip(ops, self_each([(s, e) for _, s, e in ops])):
        out[short_name(name, module_of(s))] += t
    return out


def union(intervals) -> list:
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Parts of merged intervals ``a`` not covered by merged intervals ``b``."""
    out, j = [], 0
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


def window_of(events: dict) -> tuple:
    """The span of the ``bench:window`` annotation, else of all device ops."""
    win = [(s, e) for n, s, e in events["spans"] if n == SPAN_PREFIX + "window"]
    if win:
        return min(s for s, _ in win), max(e for _, e in win)
    ops = [(s, e) for ev in events["devices"].values() for _, s, e in ev]
    return min(s for s, _ in ops), max(e for _, e in ops)


def reduce(events: dict, window: tuple | None = None) -> dict:
    """Busy and idle seconds, the ops with the most self time, idle gaps by
    host span, and the collective time with no other operation beside it,
    averaged over devices."""
    lo, hi = window or window_of(events)
    win_s = (hi - lo) / 1e9
    devs = sorted(events["devices"])
    if not devs:
        return {"window_s": win_s, "busy_s": 0.0, "idle_share": None, "collective_exposed_s": 0.0,
                "devices": 0, "device_ops": [], "idle_gaps": []}
    busy, exposed = 0.0, 0.0
    op_time = defaultdict(float)
    for d in devs:
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in events["devices"][d] if e > lo and s < hi]
        busy += length(union((s, e) for _, s, e in ops))
        for n, t in self_times(ops, events.get("modules", {}).get(d, ())).items():
            op_time[n] += t
        coll = union((s, e) for n, s, e in ops if COLLECTIVE.search(n))
        comp = union((s, e) for n, s, e in ops if not COLLECTIVE.search(n))
        exposed += length(subtract(coll, comp))
    n = len(devs)
    busy_s, exposed_s = busy / n / 1e9, exposed / n / 1e9
    top = sorted(((k, v / n / 1e9) for k, v in op_time.items()), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": win_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / win_s if win_s > 0 else None,
        "collective_exposed_s": exposed_s,
        "devices": n,
        "device_ops": [[k, v] for k, v in top],
        "idle_gaps": idle_gaps(events, devs[0], lo, hi),
    }


def idle_gaps(events: dict, device: int, lo: float, hi: float, top: int = 10) -> list:
    """Idle time of one device inside the window, summed by the innermost
    host span that covers each gap's midpoint (``host`` where none does)."""
    busy = union((max(s, lo), min(e, hi)) for _, s, e in events["devices"][device]
                 if e > lo and s < hi)
    gaps = subtract([(lo, hi)], busy)
    spans = sorted(((s, e, n) for n, s, e in events["spans"]
                    if n != SPAN_PREFIX + "window"), key=lambda x: x[0])
    by_name = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) / 2
        covering = [(e2 - s2, n) for s2, e2, n in spans if s2 <= mid <= e2]
        name = min(covering)[1][len(SPAN_PREFIX):] if covering else "host"
        by_name[name] += (e - s) / 1e9
    return [[k, v] for k, v in sorted(by_name.items(), key=lambda kv: -kv[1])[:top]]
