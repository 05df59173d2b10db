"""From a profiler trace to the program's own spans and scopes, on one clock.

:mod:`trace_reduce` reads the device's operations and the harness's
``bench:`` spans. This module reads what the program marks itself (see
``repro.obs.spans``):

* the program's ``nbi:`` host spans beside the ``bench:`` spans;
* one offset between the host's clock and the device's, from the runs the
  trace links by ``run_id``: the host enqueues each run
  (``DoEnqueueProgram``) before the device starts it, and the device ends it
  before the host's completion callback (``CompleteCallbacks``) starts. Each
  run bounds the offset from both sides;
* device idle time by the innermost host span over each gap, and inside each
  span name, with the device's intervals moved onto the host's clock;
* device self time by the model's ``jax.named_scope`` names, read from the
  ``op_name`` metadata of the compiled programs' HLO text: by the innermost
  catalogued scope (:data:`SCOPES`), and by every name on an op's path, so
  that a scope the program adds is read by its name with no list to extend;
* device self time and calls of every operation by name, a Pallas kernel
  by its kernel's name, and the runs and device time of each program.

:func:`reduce` returns only keys :func:`trace_reduce.reduce` does not.
"""

from __future__ import annotations

import heapq
import re
import statistics
import sys
from collections import defaultdict

from . import trace_reduce as tr

SPAN_PREFIXES = ("bench:", "nbi:")
LAUNCH = "DoEnqueueProgram"
DONE = "CompleteCallbacks"
# the model's named scopes, as repro.obs.spans lists them
SCOPES = ("embed", "attention", "mlp", "decode_attention", "kv_cache_write", "lm_head",
          "loss", "grad_cast", "clip", "optimizer")
UNSCOPED = "-"


def load_events(path: str) -> dict:
    """:func:`trace_reduce.load_events` with ``nbi:`` spans kept too, and
    ``runs``: {"device": [(key, start_ns, end_ns)], "launch": [(key, start_ns)],
    "done": [(key, start_ns)]}, keyed by (device, run_id)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, modules, spans = {}, {}, []
    runs = {"device": [], "launch": [], "done": []}
    for plane in data.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (tr.OPS_LINE, tr.MODULES_LINE):
                dev = int(m.group(2))
                into = devices if line.name == tr.OPS_LINE else modules
                for e in line.events:
                    into.setdefault(dev, []).append((e.name, float(e.start_ns), float(e.end_ns)))
                    if line.name == tr.MODULES_LINE:
                        run = dict(e.stats).get("run_id")
                        if run is not None:
                            runs["device"].append(((dev, int(run)), float(e.start_ns),
                                                   float(e.end_ns)))
            elif not m:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.name, float(e.start_ns), float(e.end_ns)))
                    elif e.name in (LAUNCH, DONE):
                        st = dict(e.stats)
                        if "run_id" in st:
                            key = (int(st.get("device_ordinal", 0)), int(st["run_id"]))
                            runs["launch" if e.name == LAUNCH else "done"].append(
                                (key, float(e.start_ns)))
    return {"devices": devices, "modules": modules, "spans": spans, "runs": runs}


def clock_offset(runs: dict) -> tuple:
    """(offset_ns, bound_ns, pairs): host time = device time + offset.

    Each run whose launch, device interval and completion callback are all
    in the trace gives ``launch - device start <= offset <= callback - device
    end``. Where the bounds of all runs meet, the offset is the middle of
    their intersection and ``bound_ns`` its width; where they conflict, the
    median of the runs' midpoints, and ``bound_ns`` is negative by the
    conflict. No linked run: offset 0, bound None."""
    launch = dict(runs.get("launch", ()))
    done = dict(runs.get("done", ()))
    pairs = [(launch[k] - s, done[k] - e) for k, s, e in runs.get("device", ())
             if k in launch and k in done]
    if not pairs:
        return 0.0, None, 0
    lo, hi = max(a for a, _ in pairs), min(b for _, b in pairs)
    if lo <= hi:
        return (lo + hi) / 2, hi - lo, len(pairs)
    return statistics.median((a + b) / 2 for a, b in pairs), hi - lo, len(pairs)


def innermost(spans, points) -> list:
    """For each of the sorted ``points``, the name of the shortest span with
    ``start <= point <= end``, or None. One sweep over the spans sorted by
    start, with a heap of the spans begun so far by length; a span that
    ended before one point has ended before every later one."""
    order = sorted(spans, key=lambda x: x[1])
    heap, out, i = [], [], 0
    for p in points:
        while i < len(order) and order[i][1] <= p:
            n, s, e = order[i]
            heapq.heappush(heap, (e - s, e, n))
            i += 1
        while heap and heap[0][1] < p:
            heapq.heappop(heap)
        out.append(heap[0][2] if heap else None)
    return out


def innermost_scope(op_name: str | None) -> str | None:
    """The last of :data:`SCOPES` in an ``op_name`` path, through transforms:
    ``jit(step)/transpose(jvp(attention))/dot_general`` -> ``attention``."""
    found = [p for p in re.split(r"[/()]", op_name or "") if p in SCOPES]
    return found[-1] if found else None


def scope_path(op_name: str | None) -> tuple:
    """Every name on an ``op_name`` path but the operation at its end, each
    out of the transforms that wrap it, once:
    ``jit(step)/transpose(jvp(mlp))/experts/dot_general`` -> ``("step",
    "mlp", "experts")``."""
    parts = [p.split("(")[-1].rstrip(")") for p in (op_name or "").split("/")[:-1]]
    return tuple(dict.fromkeys(p for p in parts if p))


_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def program_scopes(hlo: str) -> dict:
    """{"module": name, "scope": {instruction: scope}, "path": {instruction:
    names}} from compiled HLO text: :func:`innermost_scope` and
    :func:`scope_path` of the ``op_name`` each instruction is read by.

    An instruction of a computation that is not a fusion's body is read by
    its own ``op_name``. XLA gives a fusion the metadata of the op it is
    built round, so a fusion under no scope is read by its fused
    computation's root, and failing that by an instruction fused in it that
    is under ``kv_cache_write``: the scan's write-back of each layer's cache
    into the stacked cache is the fusion that makes the one-hot update, and
    its own ``op_name`` is the scan's. An instruction under no scope by
    these rules keeps the names on its own path, else its root's."""
    module = ""
    comps: dict = {}
    cur = None
    for line in hlo.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), {"root": None, "ops": {}, "calls": {}})
            continue
        m = _INSTRUCTION.match(line)
        if m and cur is not None:
            name = m.group(2)
            op = _OP_NAME.search(line)
            cur["ops"][name] = op.group(1) if op else None
            if m.group(1):
                cur["root"] = name
            if " fusion(" in line:
                calls = _CALLS.search(line)
                if calls:
                    cur["calls"][name] = calls.group(1)
    fused = {c for comp in comps.values() for c in comp["calls"].values()}
    scope, path = {}, {}
    for cname, comp in comps.items():
        if cname in fused:
            continue
        for name, own in comp["ops"].items():
            body = comps.get(comp["calls"].get(name, ""))
            root = body["ops"].get(body["root"]) if body else None
            read = own if innermost_scope(own) else None
            if read is None and body is not None:
                read = root if innermost_scope(root) else next(
                    (o for o in body["ops"].values()
                     if innermost_scope(o) == "kv_cache_write"), None)
            read = read or own or root
            scope[name], path[name] = innermost_scope(read), scope_path(read)
    return {"module": module, "scope": scope, "path": path}


def _match_programs(seen: dict, programs: list) -> dict:
    """{trace module name: program} by the instruction names each module ran:
    the program of the same name holding most of them (ties: none)."""
    out = {}
    for module, names in seen.items():
        base = module.split("(")[0]
        cands = [p for p in programs if p["module"] == base] or programs
        scores = sorted(((len(names & p["scope"].keys()) / len(names), i)
                         for i, p in enumerate(cands)), reverse=True)
        if scores and scores[0][0] > 0.5 and (len(scores) == 1 or scores[1][0] < scores[0][0]):
            out[module] = cands[scores[0][1]]
    return out


def scope_ops(ops, modules, programs: list) -> tuple:
    """([(program, scope, instruction, start, end, path)] for the ops of one
    device, {trace module name: program} as matched). The program is the
    matched one's ``label`` (else the trace's module name), the scope ``-``
    where none is found, the path the names of :func:`scope_path` (none
    where no program matched)."""
    module_of = tr.module_at(modules)
    placed = [(module_of(s), tr.short_name(n), s, e) for n, s, e in ops]
    seen = defaultdict(set)
    for mod, name, _, _ in placed:
        if mod is not None:
            seen[mod].add(name)
    match = _match_programs(seen, programs)
    out = []
    for mod, name, s, e in placed:
        prog = match.get(mod)
        scope = (prog["scope"].get(name) if prog else None) or UNSCOPED
        path = prog.get("path", {}).get(name, ()) if prog else ()
        out.append((prog.get("label", mod) if prog else mod, scope, name, s, e, path))
    return out, match


def _inside(s: float, e: float, lo: float, hi: float) -> float:
    """The share of the interval (s, e) that lies inside (lo, hi)."""
    return (min(e, hi) - max(s, lo)) / (e - s) if e > s else 1.0


def reduce(events: dict, programs: list = (), window: tuple | None = None,
           top: int = 10) -> dict:
    """Keys :func:`trace_reduce.reduce` does not give, over its window:
    ``clock_offset_ms`` and ``clock_bound_ms`` (see :func:`clock_offset`),
    ``span_stats`` (count and seconds of the spans of each name inside the
    window), ``idle_under`` (device idle seconds by the innermost span over
    each gap's midpoint, ``host`` where none), ``idle_inside`` (device idle
    seconds inside the spans of each name, nested spans included),
    ``scope_self_s`` (device self seconds by named scope, ``-`` for ops under
    none), ``scope_incl_s`` (device self seconds of the ops under each name
    of :func:`scope_path`, nested scopes included), ``scope_ops`` (the
    ``top`` ops by self time, as ``program/instruction [scope]``),
    ``op_self_s`` and ``op_calls`` (device self seconds and calls of every
    op by instruction name, over all programs), ``program_runs`` and
    ``program_s`` (runs and device seconds of each program by its ``label``,
    else by the trace's module name). An op or a run that the window cuts
    counts by the share of it inside. Device
    seconds and counts are averaged over devices. ``programs`` are
    :func:`program_scopes` of the programs the trace ran, each with a
    ``label``."""
    lo, hi = window or tr.window_of(events)
    offset, bound, pairs = clock_offset(events.get("runs", {}))
    log(f"[bench] clock offset {offset / 1e6!r} ms (host = device + offset; "
        f"bound {None if bound is None else bound / 1e6!r} ms from {pairs} runs)")
    spans = [(n, s, e) for n, s, e in events["spans"]
             if n != "bench:window" and s >= lo and e <= hi]
    stats = defaultdict(lambda: {"count": 0, "s": 0.0})
    for n, s, e in spans:
        stats[n]["count"] += 1
        stats[n]["s"] += (e - s) / 1e9
    by_name = defaultdict(list)
    for n, s, e in spans:
        by_name[n].append((s, e))
    devs = sorted(events["devices"])
    under, inside = defaultdict(float), defaultdict(float)
    scope_t, incl_t, op_t = defaultdict(float), defaultdict(float), defaultdict(float)
    op_self, op_calls, runs = defaultdict(float), defaultdict(float), defaultdict(float)
    run_t = defaultdict(float)
    for d in devs:
        ops = [(n, s + offset, e + offset) for n, s, e in events["devices"][d]
               if e + offset > lo and s + offset < hi]
        gaps = tr.subtract([(lo, hi)], tr.union((max(s, lo), min(e, hi)) for _, s, e in ops))
        for (s, e), name in zip(gaps, innermost(spans, [(s + e) / 2 for s, e in gaps])):
            under[name or "host"] += (e - s) / 1e9
        for n, ivs in by_name.items():
            merged = tr.union(ivs)
            inside[n] += (tr.length(merged) - tr.length(tr.subtract(merged, gaps))) / 1e9
        mods = [(m, s + offset, e + offset) for m, s, e in events.get("modules", {}).get(d, ())]
        clipped = [(n, max(s, lo), min(e, hi)) for n, s, e in ops]
        scoped, match = scope_ops(clipped, mods, list(programs))
        own = tr.self_each([(s, e) for _, _, _, s, e, _ in scoped])
        for (prog, scope, name, _, _, path), t, (_, s, e) in zip(scoped, own, ops):
            scope_t[scope] += t / 1e9
            op_t[f"{prog}/{name} [{scope}]"] += t / 1e9
            for p in path:
                incl_t[p] += t / 1e9
            op_self[name] += t / 1e9
            op_calls[name] += _inside(s, e, lo, hi)
        for m, s, e in mods:
            if e > lo and s < hi:
                prog = match.get(m)
                label = prog.get("label", m) if prog else m.split("(")[0]
                runs[label] += _inside(s, e, lo, hi)
                run_t[label] += (min(e, hi) - max(s, lo)) / 1e9
    n = max(1, len(devs))
    desc = lambda d: {k: v / n for k, v in sorted(d.items(), key=lambda kv: -kv[1])}  # noqa: E731
    return {
        "clock_offset_ms": offset / 1e6,
        "clock_bound_ms": None if bound is None else bound / 1e6,
        "span_stats": dict(sorted(stats.items())),
        "idle_under": desc(under),
        "idle_inside": desc(inside),
        "scope_self_s": desc(scope_t) if programs else {},
        "scope_incl_s": desc(incl_t),
        "scope_ops": [[k, v] for k, v in desc(op_t).items()][:top] if programs else [],
        "op_self_s": desc(op_self),
        "op_calls": {k: v / n for k, v in sorted(op_calls.items())},
        "program_runs": {k: v / n for k, v in sorted(runs.items())},
        "program_s": {k: v / n for k, v in sorted(run_t.items())},
    }


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
