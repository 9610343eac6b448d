"""From a ``jax.profiler`` trace (``.xplane.pb``) to what the per-layer
readers need. Two steps, so the second can be tested on a small recorded
trace without the profiler:

``load(path)`` -> plain events: ``{"device": {plane: [(name, start_s,
dur_s, scope), ...]}, "host": [(name, start_s, dur_s), ...]}`` — the
device planes' "XLA Ops" lines, each op with the scope its program gave
it, and the host's TraceAnnotations whose names start with one of
``SPAN_PREFIXES``: the harness's ``bench.*`` and the program's
``scenario.*`` spans.

``reduce(events)`` -> busy and idle seconds in the window, the ops that
took most (self) time, self time by scope, idle gaps by the innermost
host span they fall in, and collective seconds (a ``-start`` to its
``-done``, or the op itself) with the part no compute covers (the
collective ops' own time on the line). Host spans named
``bench.part.<x>`` are stretches that hold other spans (the rounds, the
evaluations): idle time is also given inside each of them.

The scope of a device op is the ``tf_op`` stat of its event METADATA in
the device plane: the name stack jax gave the HLO instruction
(``jit(round_fn)/.../fit.value_and_grad/jvp(SmallCNN)/Conv_0/conv_general_dilated:``),
``jax.named_scope``s among its components. ``jax.profiler.ProfileData``
of jax 0.9.0 does not show metadata stats, so ``load`` reads the file's
protobuf wire format itself (``xplane.proto``'s field numbers, below).
A fused op carries ONE scope, the one XLA kept for the fusion; an op
XLA made itself (a layout copy, a loop's bookkeeping) may carry none,
and neither does any op of an executable that was compiled from a
program without scopes: the compile cache's key leaves metadata out, so
a cache filled by an older program hands such executables on. Their
time goes to the row ``UNSCOPED``.
"""

import glob
import os
import re

OPS_LINE = "XLA Ops"
SPAN_PREFIXES = ("bench.", "scenario.")
WINDOW = "bench.window"
PART = "bench.part."  # bench.part.<x>: a stretch that holds other spans
OUTSIDE = "outside_any_span"
OTHER = "other_spans"
UNSCOPED = "(no scope)"
SCOPE_STAT = "tf_op"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|async-collective|collective-broadcast|^send|^recv")


def find_xplane(trace_dir):
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


# --------------------------------------------------------------------------
# the .xplane.pb, field by field (tsl/profiler/protobuf/xplane.proto):
#   XSpace   1 planes
#   XPlane   2 name, 3 lines, 4 event_metadata (map: 1 key, 2 value),
#            5 stat_metadata (map)
#   XLine    2 name, 3 timestamp_ns, 4 events
#   XEvent   1 metadata_id, 2 offset_ps, 3 duration_ps
#   XEventMetadata  1 id, 2 name, 5 stats
#   XStatMetadata   1 id, 2 name
#   XStat    1 metadata_id, 5 str_value, 7 ref_value (a stat_metadata id
#            whose name is the value)


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are
    stepped over."""
    at, end = 0, len(buf)

    def varint():
        nonlocal at
        val = shift = 0
        while True:
            b = buf[at]
            at += 1
            val |= (b & 0x7F) << shift
            if b < 0x80:
                return val
            shift += 7

    while at < end:
        key = varint()
        kind = key & 7
        if kind == 0:
            yield key >> 3, varint()
        elif kind == 2:
            size = varint()
            yield key >> 3, buf[at:at + size]
            at += size
        elif kind == 1:
            at += 8
        elif kind == 5:
            at += 4
        else:
            raise ValueError(f"wire type {kind} in an xplane")


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _metadata(entries):
    """A plane's metadata map -> {id: (name, [stat message, ...])}."""
    out = {}
    for entry in entries:
        for f, v in _fields(entry):
            if f != 2:
                continue
            ident, name, stats = 0, "", []
            for g, w in _fields(v):
                if g == 1:
                    ident = w
                elif g == 2:
                    name = _text(w)
                elif g == 5:
                    stats.append(w)
            out[ident] = (name, stats)
    return out


def _scope(stats, stat_names):
    """The op's name stack from its metadata's stats, less the
    primitive's type that follows the last colon; '' where it has none."""
    for stat in stats:
        fields = dict(_fields(stat))
        if stat_names.get(fields.get(1)) != SCOPE_STAT:
            continue
        if 5 in fields:
            return _text(fields[5]).rpartition(":")[0]
        return stat_names.get(fields.get(7), "").rpartition(":")[0]
    return ""


def _line(line):
    """One line -> its name, its start in ns, its events."""
    name, t0_ns, events = "", 0, []
    for f, v in _fields(line):
        if f == 2:
            name = _text(v)
        elif f == 3:
            t0_ns = v
        elif f == 4:
            events.append(v)
    return name, t0_ns, events


def _timed(t0_ns, events, keep):
    """(metadata id, start_s, dur_s) of the events whose metadata id is
    in ``keep``."""
    for ev in events:
        got = dict(_fields(ev))
        if got.get(1) in keep:
            yield (got[1], t0_ns * 1e-9 + got.get(2, 0) * 1e-12,
                   got.get(3, 0) * 1e-12)


def load(path):
    with open(path, "rb") as f:
        space = memoryview(f.read())
    device, host = {}, []
    for f, plane in _fields(space):
        if f != 1:
            continue
        name, lines, ev_meta, stat_meta = "", [], [], []
        for g, v in _fields(plane):
            if g == 2:
                name = _text(v)
            elif g == 3:
                lines.append(v)
            elif g == 4:
                ev_meta.append(v)
            elif g == 5:
                stat_meta.append(v)
        on_device = name.startswith("/device:") and "TPU" in name
        if not on_device and not name.startswith("/host:"):
            continue
        meta = _metadata(ev_meta)
        if on_device:
            stat_names = {k: n for k, (n, _) in _metadata(stat_meta).items()}
            scopes = {k: _scope(stats, stat_names)
                      for k, (_, stats) in meta.items()}
            for line_name, t0_ns, events in map(_line, lines):
                if line_name == OPS_LINE:
                    device.setdefault(name, []).extend(
                        (meta[k][0], s, d, scopes[k])
                        for k, s, d in _timed(t0_ns, events, meta))
        else:
            keep = {k for k, (n, _) in meta.items()
                    if n.startswith(SPAN_PREFIXES)}
            for _, t0_ns, events in map(_line, lines):
                host.extend((meta[k][0], s, d)
                            for k, s, d in _timed(t0_ns, events, keep))
    return {"device": device, "host": host}


# --------------------------------------------------------------------------


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
    its duration less its children's. Returns [(name, start, end, self,
    scope)]."""
    out, stack = [], []
    for name, s, d, *scope in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        e = s + d
        while stack and stack[-1][2] <= s:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= min(e, stack[-1][2]) - s
        stack.append([name, s, e, d, scope[0] if scope else ""])
    out.extend(tuple(x) for x in stack)
    return out


def _innermost(spans, lo, hi):
    """Host spans nest (``scenario.wait`` inside ``scenario.round``
    inside ``bench.round``), one thread beside another too. Cut [lo, hi]
    at every span's edge and name each piece by the span that began last
    among those that cover it: {name: sorted disjoint intervals}, with
    ``OUTSIDE`` for the pieces no span covers."""
    spans = sorted(((n, max(s, lo), min(e, hi)) for n, s, e in spans
                    if min(e, hi) > max(s, lo)), key=lambda x: (x[1], -x[2]))
    edges = sorted({lo, hi} | {s for _, s, _ in spans} | {e for _, _, e in spans})
    out, live, at = {}, [], 0
    for a, b in zip(edges, edges[1:]):
        while at < len(spans) and spans[at][1] <= a:
            live.append(spans[at])
            at += 1
        live = [x for x in live if x[2] > a]
        name = live[-1][0] if live else OUTSIDE
        pieces = out.setdefault(name, [])
        if pieces and pieces[-1][1] == a:
            pieces[-1][1] = b
        else:
            pieces.append([a, b])
    return out


def _longest(seconds, top):
    """The ``top`` longest rows, the last of them ``OTHER`` for what the
    rest add up to, so that the rows still sum to the whole."""
    rows = sorted(seconds.items(), key=lambda kv: -kv[1])
    if len(rows) > top:
        rows = rows[:top - 1] + [(OTHER, sum(t for _, t in rows[top - 1:]))]
    return rows


def reduce(events, top=10):
    host = events["host"]
    wins = [(s, s + d) for n, s, d in host if n == WINDOW]
    dev = events["device"]
    if not dev:
        return None
    if wins:
        lo, hi = min(w[0] for w in wins), max(w[1] for w in wins)
    else:
        lo = min(ev[1] for evs in dev.values() for ev in evs)
        hi = max(ev[1] + ev[2] for evs in dev.values() for ev in evs)
    parts = {}
    for n, s, d in host:
        if n.startswith(PART):
            parts.setdefault(n[len(PART):], []).append((s, s + d))
    parts = {n: _union(_clip(v, lo, hi)) for n, v in parts.items()}
    named = _innermost([(n, s, s + d) for n, s, d in host
                        if n != WINDOW and not n.startswith(PART)], lo, hi)
    n_dev = len(dev)
    busy = 0.0
    ops, scope_s, gaps, idle_in = {}, {}, {}, {}
    coll = exposed = 0.0
    for evs in dev.values():
        evs = [ev for ev in evs if ev[1] + ev[2] > lo and ev[1] < hi]
        union = _union(_clip([(ev[1], ev[1] + ev[2]) for ev in evs], lo, hi))
        busy += _total(union)
        idle, at = [], lo
        for s, e in union:
            if s > at:
                idle.append((at, s))
            at = e
        if hi > at:
            idle.append((at, hi))
        for n, v in parts.items():
            idle_in[n] = idle_in.get(n, 0.0) + _overlap(idle, v)
        for n, v in named.items():
            got = _overlap(idle, v)
            if got > 0.0:
                gaps[n] = gaps.get(n, 0.0) + got
        selfs = _self_times(evs)
        flight, started = [], {}
        for n, s, e, t, scope in sorted(selfs, key=lambda x: x[1]):
            ops[n] = ops.get(n, 0.0) + t
            row = scope or UNSCOPED
            scope_s[row] = scope_s.get(row, 0.0) + t
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
        "scope_s": {n: t / n_dev for n, t in scope_s.items()},
        "idle_gaps": _longest({n: t / n_dev for n, t in gaps.items()}, top),
        "collective_s": coll / n_dev,
        "exposed_collective_s": exposed / n_dev,
        "idle_in_part_s": {n: t / n_dev for n, t in idle_in.items()},
        "part_s": {n: _total(v) for n, v in parts.items()},
    }


def scope_seconds(reduced, *names):
    """Device self seconds of the ops whose scope path holds one of
    ``names`` as a component (between two ``/``); ``None`` where no op
    does, so that a reader reports nothing rather than nought."""
    hits = [t for path, t in reduced["scope_s"].items()
            if not set(names).isdisjoint(path.split("/"))]
    return sum(hits) if hits else None
