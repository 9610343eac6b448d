"""From a ``jax.profiler`` trace (``.xplane.pb``) to what the per-layer
readers need. Two steps, so the second can be tested on a small recorded
trace without the profiler:

``load(path)`` -> plain events: ``{"device": {plane: [(name, start_s,
dur_s), ...]}, "host": [(name, start_s, dur_s), ...]}`` — the device
planes' "XLA Ops" lines, and the host's TraceAnnotations whose names
start with ``bench.``.

``reduce(events)`` -> busy and idle seconds in the window, the ops that
took most (self) time, idle gaps by the host span they fall in, and
collective seconds (a ``-start`` to its ``-done``, or the op itself) with
the part no compute covers (the collective ops' own time on the line). Host spans named
``bench.part.<x>`` are stretches that hold other spans (the rounds, the
evaluations): idle time is also given inside each of them.
"""

import glob
import os
import re

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"
PART = "part."  # bench.part.<x>: a stretch that holds other spans
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|async-collective|collective-broadcast|^send|^recv")


def find_xplane(trace_dir):
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load(path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device.setdefault(plane.name, []).extend(
                        (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                    for ev in line.events if ev.name.startswith(SPAN_PREFIX))
    return {"device": device, "host": host}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _total(intervals):
    return sum(e - s for s, e in intervals)


def _overlap(a, b):
    """Seconds of sorted disjoint ``a`` covered by sorted disjoint ``b``."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def _self_times(events):
    """Ops nest on one line (a ``while`` spans its body): give each event
    its duration less its children's. Returns [(name, start, end, self)]."""
    out, stack = [], []
    for name, s, d in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        e = s + d
        while stack and stack[-1][2] <= s:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= min(e, stack[-1][2]) - s
        stack.append([name, s, e, d])
    out.extend(tuple(x) for x in stack)
    return out


def reduce(events, top=10):
    host = events["host"]
    wins = [(s, s + d) for n, s, d in host if n == WINDOW]
    dev = events["device"]
    if not dev:
        return None
    if wins:
        lo, hi = min(w[0] for w in wins), max(w[1] for w in wins)
    else:
        lo = min(s for evs in dev.values() for _, s, _ in evs)
        hi = max(s + d for evs in dev.values() for _, s, d in evs)
    spans = {}
    for n, s, d in host:
        if n != WINDOW:
            spans.setdefault(n[len(SPAN_PREFIX):], []).append((s, s + d))
    spans = {n: _union(_clip(v, lo, hi)) for n, v in spans.items()}

    parts = {n[len(PART):]: v for n, v in spans.items() if n.startswith(PART)}
    spans = {n: v for n, v in spans.items() if not n.startswith(PART)}
    n_dev = len(dev)
    busy = 0.0
    ops, gaps, idle_in = {}, {}, {}
    coll = exposed = 0.0
    for evs in dev.values():
        evs = [ev for ev in evs if ev[1] + ev[2] > lo and ev[1] < hi]
        union = _union(_clip([(s, s + d) for _, s, d in evs], lo, hi))
        busy += _total(union)
        idle, at = [], lo
        for s, e in union:
            if s > at:
                idle.append((at, s))
            at = e
        if hi > at:
            idle.append((at, hi))
        left = _total(idle)
        for n, v in parts.items():
            idle_in[n] = idle_in.get(n, 0.0) + _overlap(idle, v)
        for n, v in spans.items():
            got = _overlap(idle, v)
            gaps[n] = gaps.get(n, 0.0) + got
            left -= got
        gaps["outside_any_span"] = gaps.get("outside_any_span", 0.0) + left
        selfs = _self_times(evs)
        flight, started = [], {}
        for n, s, e, t in sorted(selfs, key=lambda x: x[1]):
            ops[n] = ops.get(n, 0.0) + t
            if not COLLECTIVE.search(n):
                continue
            # ops on one line never overlap but by nesting, so a
            # collective's self time is time no compute ran on the device
            exposed += t
            base = re.sub(r"-(start|done)\b", "", n.split(".")[0])
            if "-start" in n:
                started.setdefault(base, []).append(s)
            elif "-done" in n and started.get(base):
                flight.append((started[base].pop(0), e))
            else:
                flight.append((s, e))
        coll += _total(_union(flight))
    return {
        "window_s": hi - lo,
        "busy_s": busy / n_dev,
        "n_devices": n_dev,
        "device_ops": sorted(((n, t / n_dev) for n, t in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(((n, t / n_dev) for n, t in gaps.items()),
                            key=lambda kv: -kv[1])[:top],
        "collective_s": coll / n_dev,
        "exposed_collective_s": exposed / n_dev,
        "idle_in_part_s": {n: t / n_dev for n, t in idle_in.items()},
        "part_s": {n: _total(v) for n, v in parts.items()},
    }
