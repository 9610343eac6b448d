"""Near-zero-overhead span/event tracer for federation hot paths.

Design constraints (in priority order):

1. **Disabled means free.** The data plane pushes ~400k frames per
   24-node round pair (perf.md §7b); instrumentation that allocates
   per frame while off would show up in the very numbers it exists to
   explain. Every hot call site gates on one attribute read
   (``tracer.enabled``); ``span()`` while disabled returns one shared
   ``NULL_SPAN`` singleton (no allocation), and ``count()`` returns
   before touching any state.
2. **Enabled means cheap.** A closed span is one tuple appended to a
   bounded ``collections.deque`` — an atomic, thread-safe operation
   under CPython, so asyncio callbacks and executor threads (the
   learner's fit runs in one, node.py _fit) record into the same ring
   without a lock on the span path. Counters take a small lock; they
   fire at per-message rate only when tracing is on.
3. **Mergeable across processes.** Each tracer records a wall-clock /
   perf_counter anchor pair at reset; exported span timestamps are
   perf_counter-relative (monotonic, immune to NTP steps mid-run) and
   the anchor lets ``p2pfl_tpu.obs.traceview`` shift every process
   onto one wall-clock timeline.

The process tracer is a singleton that is **configured in place**
(never replaced): call sites may cache the reference, so
``configure()`` mutates the one object everyone holds.

Enablement comes from ``P2PFL_TRACE``: ``0`` = off, ``1`` = on (the
launcher decides the export dir), any other value = on with that value
as the export directory; unset leaves the tracer as the process set it.
``span()`` ALSO records while a ``jax.profiler`` session is live, and is
then a ``jax.profiler.TraceAnnotation`` of the same name too: whoever
starts a profiler finds the program's spans in the xplane's host plane,
on the clock the device ops are on, and in the ring, without setting
anything. Per-message sites of the socket plane gate on ``enabled``
alone. No span name starts with ``bench.``: the benchmark's trace
reduction takes those for its own.

``Tracer.watch()`` puts a stall watch beside the caller by the same
rule (a thread while the tracer records, ``NULL_SPAN`` while it does
not): a pause that stops every thread of the process is in the ring as
``host.stall``, on the spans' clock, with what the operating system
says of the same stretch.

Export is Chrome trace-event JSON (the ``{"traceEvents": [...]}``
object form) — loadable in ``chrome://tracing`` / Perfetto directly,
or merged first via ``python -m p2pfl_tpu.obs.traceview``.

The XLA recompile counter hooks ``jax.monitoring``'s duration events:
every real backend compile fires ``.../backend_compile_duration``
(jit-cache hits do not), so a repeat of the round-7 recompile storm
(~450 mid-round compiles, ≈32% of wall — perf.md §7b) is loudly
visible in every benchmark line instead of needing a hand profile.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import pathlib
import sys
import threading
import time
from collections import deque
from typing import Any

from p2pfl_tpu.obs.records import make_record

ENV_VAR = "P2PFL_TRACE"
_RING_MAX = 1 << 16  # spans kept per process; oldest evicted first

#: the stall watch's record (``Tracer.watch``): a ring span, on a lane of
#: its own. Not a ``scenario.*`` name: the benchmark's trace reduction
#: names a device's idle gaps by the ``scenario.*`` span that began last
STALL_SPAN = "host.stall"
STALL_LANE = "watch"
STALL_PERIOD_S = 0.005  # the watch thread's sleep
STALL_THRESHOLD_S = 0.020  # a wake-up later than this is a stall


class _NullSpan:
    """The disabled-path span: one shared, stateless instance. Usable
    as a context manager; records nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = _NullSpan()

# jax.profiler.TraceAnnotation, looked up once jax is in the process:
# this module stays importable (traceview, the monitor) without jax
_Annotation = None


def _profiling() -> bool:
    """Is a ``jax.profiler`` session live? One C call."""
    global _Annotation
    if _Annotation is None:
        if "jax" not in sys.modules:
            return False
        from jax.profiler import TraceAnnotation

        _Annotation = TraceAnnotation
    return _Annotation.is_enabled()


class _Span:
    """One live span. Closing appends (name, lane, t0, dur, args) to
    the owning tracer's ring — a single deque.append, no lock. With
    ``annotate`` it is also the profiler's TraceAnnotation of that name
    (args as its keywords)."""

    __slots__ = ("_tracer", "name", "lane", "args", "t0", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, lane: str | None,
                 args: dict | None, annotate: bool = False):
        self._tracer = tracer
        self.name = name
        self.lane = lane
        self.args = args
        self.t0 = 0.0
        self._annotation = (
            _Annotation(name, **(args or {})) if annotate else None)

    def __enter__(self) -> "_Span":
        if self._annotation is not None:
            self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self._tracer._events.append(
            (self.name, self.lane, self.t0,
             time.perf_counter() - self.t0, self.args)
        )
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        return False


def _thread_counters():
    """A reader of what the operating system counts over a stretch of
    the CALLING thread's life (so: made by the thread it is to read),
    and the reader's ``close``: ``run_delay_s``, seconds the thread was
    runnable and not run (``/proc/thread-self/schedstat``, second
    field); ``nivcsw``, its involuntary context switches
    (``RUSAGE_THREAD``); ``cpu_s``, the CPU seconds of the whole process
    (``time.process_time``), which every platform has: the machine this
    repo is measured on has neither of the first two. A platform
    without one leaves that key out."""
    try:
        fd = os.open("/proc/thread-self/schedstat", os.O_RDONLY)
    except OSError:
        fd = None
    try:
        import resource

        who = resource.RUSAGE_THREAD
    except (ImportError, AttributeError):
        resource = who = None

    def read() -> dict[str, float]:
        out = {"cpu_s": time.process_time()}
        if fd is not None:
            out["run_delay_s"] = int(os.pread(fd, 64, 0).split()[1]) * 1e-9
        if who is not None:
            out["nivcsw"] = resource.getrusage(who).ru_nivcsw
        return out

    def close() -> None:
        if fd is not None:
            os.close(fd)

    return read, close


class _StallWatch:
    """A daemon thread that sleeps ``STALL_PERIOD_S`` at a time and,
    whenever it wakes more than ``STALL_THRESHOLD_S`` after it meant to,
    appends one ``STALL_SPAN`` to the tracer's ring, dated back: ``t0``
    the wake-up it meant, ``dur`` the lateness, on the clock of every
    ring span. ``args`` is the difference, over that late sleep, of the
    thread's ``_thread_counters``: a thread that was runnable and not
    run (``run_delay_s`` about the lateness) sat on a busy host; one
    that was not even runnable while the clock moved belongs to a
    stopped process or a paused machine, and so does a process that
    used no CPU meanwhile (``cpu_s`` near 0); ``cpu_s`` about the
    lateness or more is a thread of this process that computed and did
    not hand the interpreter's lock over. A context manager: the thread
    lives from ``__enter__`` to ``__exit__``."""

    def __init__(self, tracer: "Tracer", clock=time.perf_counter):
        self._tracer = tracer
        self._clock = clock
        self._counters = dict  # the thread's own reader, once it runs
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._due = 0.0
        self._seen: dict[str, float] = {}

    def arm(self) -> None:
        """Note when the sleep that starts now means to end, and the
        counters it starts from."""
        self._seen = self._counters()
        self._due = self._clock() + STALL_PERIOD_S

    def tick(self) -> None:
        """On waking: record the sleep that just ended if it ended
        late, and arm the next."""
        due, seen = self._due, self._seen
        late = self._clock() - due
        self.arm()  # reads the counters: once a tick, late or not
        if late > STALL_THRESHOLD_S:
            self._tracer._events.append(
                (STALL_SPAN, STALL_LANE, due, late,
                 {k: v - seen[k] for k, v in self._seen.items()} or None))

    def _run(self) -> None:
        self._counters, close = _thread_counters()
        try:
            self.arm()
            stopped = False
            while not stopped:  # the last sleep, cut short, is looked at too
                stopped = self._stop.wait(STALL_PERIOD_S)
                self.tick()
        finally:
            close()

    def __enter__(self) -> "_StallWatch":
        self._thread = threading.Thread(
            target=self._run, name="p2pfl-stall-watch", daemon=True)
        self._thread.start()
        return self

    def __exit__(self, *exc) -> bool:
        self._stop.set()
        self._thread.join()
        return False


class Tracer:
    """Span ring + counters + high-water gauges for one process.

    ``lane`` names a timeline row in the merged view — nodes sharing a
    process (k-nodes-per-proc launch layouts) each trace into their own
    lane (``node<idx>``) of the same tracer.
    """

    def __init__(self, ring_max: int = _RING_MAX):
        self.enabled = False
        self.export_dir: pathlib.Path | None = None
        self._ring_max = ring_max
        self._lock = threading.Lock()
        self._reset_locked()

    # -- configuration --------------------------------------------------
    def _reset_locked(self) -> None:
        self._events: deque = deque(maxlen=self._ring_max)
        self._counters: dict[str, float] = {}
        self._gauges: dict[str, float] = {}
        # wall/perf anchor pair: spans are perf_counter-relative; the
        # anchor maps them back onto the wall clock for cross-process
        # merging (traceview shifts by wall_t0 deltas)
        self.wall_t0 = time.time()
        self.perf_t0 = time.perf_counter()
        # per-process trace identity: span ids minted by next_span_id()
        # are prefixed with this, so they stay unique in a merged
        # multi-process trace and a wire-propagated parent id resolves
        # without pid coordination
        self.trace_id = os.urandom(4).hex()
        self._span_ids = itertools.count(1)

    def configure(self, enabled: bool | None = None,
                  export_dir: str | pathlib.Path | None = None,
                  ring_max: int | None = None) -> "Tracer":
        """Mutate IN PLACE (call sites cache the singleton)."""
        with self._lock:
            if ring_max is not None and ring_max != self._ring_max:
                self._ring_max = ring_max
                self._events = deque(self._events, maxlen=ring_max)
            if export_dir is not None:
                self.export_dir = pathlib.Path(export_dir)
            if enabled is not None:
                self.enabled = bool(enabled)
        return self

    def reset(self) -> None:
        """Drop all recorded state and re-anchor the clocks."""
        with self._lock:
            self._reset_locked()

    # -- recording ------------------------------------------------------
    def span(self, name: str, lane: str | None = None,
             args: dict | None = None):
        """Context manager timing one operation. Disabled and no
        profiler session live: returns the shared NULL_SPAN — no
        allocation. Hot per-frame sites should additionally gate on
        ``tracer.enabled`` so even the call's argument construction is
        skipped."""
        profiling = _profiling()
        if not (self.enabled or profiling):
            return NULL_SPAN
        return _Span(self, name, lane, args, annotate=profiling)

    def watch(self):
        """Context manager under which a stall watch runs beside the
        caller (``_StallWatch``): whatever stops every thread of this
        process for longer than ``STALL_THRESHOLD_S`` leaves a
        ``STALL_SPAN`` in the ring. By ``span()``'s rule: disabled and
        no profiler session live, the shared NULL_SPAN, and no thread."""
        if not (self.enabled or _profiling()):
            return NULL_SPAN
        return _StallWatch(self)

    def next_span_id(self) -> str:
        """Mint a globally-unique span id (``<trace_id>.<n>``) for a
        span whose identity must cross the wire (the ``tc`` header).
        Only meaningful while enabled — callers gate on ``enabled``
        first, so the disabled path never reaches the allocation.
        ``itertools.count`` is GIL-atomic, no lock."""
        return f"{self.trace_id}.{next(self._span_ids)}"

    def count(self, key: str, n: float = 1) -> None:
        """Accumulate a counter (message/byte totals, compile seconds)."""
        if not self.enabled:
            return
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + n

    def high_water(self, key: str, value: float) -> None:
        """Record a max-seen gauge (egress-lane queue depths)."""
        if not self.enabled:
            return
        with self._lock:
            if value > self._gauges.get(key, float("-inf")):
                self._gauges[key] = value

    # -- reading --------------------------------------------------------
    def counters(self) -> dict[str, float]:
        with self._lock:
            return dict(self._counters)

    def gauges(self) -> dict[str, float]:
        with self._lock:
            return dict(self._gauges)

    def spans(self) -> list[tuple]:
        """Snapshot of the ring: (name, lane, t0, dur_s, args) tuples."""
        return list(self._events)

    def summarize(self) -> dict[str, Any]:
        """Per-span-name totals + counters + gauges, in the shared
        record shape (obs.records.make_record) — what ``p2p.launch``
        returns under ``obs``."""
        agg: dict[str, list[float]] = {}
        for name, _lane, _t0, dur, _args in list(self._events):
            agg.setdefault(name, [0, 0.0, 0.0])
            s = agg[name]
            s[0] += 1
            s[1] += dur
            s[2] = max(s[2], dur)
        return make_record(
            None,
            spans={
                k: {"count": int(c), "total_s": round(t, 6),
                    "max_s": round(m, 6)}
                for k, (c, t, m) in sorted(agg.items())
            },
            counters=self.counters(),
            gauges=self.gauges(),
        )

    # -- export ---------------------------------------------------------
    def chrome_events(self, pid: int | None = None,
                      process_name: str | None = None) -> list[dict]:
        """The ring + counters as Chrome trace-event dicts. Span
        timestamps are µs relative to this tracer's perf anchor; lanes
        map to small tids with thread_name metadata."""
        pid = os.getpid() if pid is None else pid
        lanes: dict[str | None, int] = {None: 0}
        events: list[dict] = [{
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": process_name or f"p2pfl[{pid}]"},
        }]
        out: list[dict] = []
        last_ts = 0.0
        for name, lane, t0, dur, args in list(self._events):
            if lane not in lanes:
                lanes[lane] = len(lanes)
            ts = (t0 - self.perf_t0) * 1e6
            last_ts = max(last_ts, ts + dur * 1e6)
            ev = {"name": name, "ph": "X", "pid": pid,
                  "tid": lanes[lane], "ts": ts, "dur": dur * 1e6}
            if args:
                ev["args"] = args
                # cross-process causal edges render as Perfetto flow
                # arrows: a span that minted a wire-propagated id is a
                # flow source; one recorded with a parent id is a sink
                sid = args.get("sid")
                if sid is not None:
                    out.append({"name": "tc", "cat": "tc", "ph": "s",
                                "id": sid, "pid": pid,
                                "tid": lanes[lane], "ts": ts})
                parent = args.get("parent")
                if parent is not None:
                    out.append({"name": "tc", "cat": "tc", "ph": "f",
                                "bp": "e", "id": parent, "pid": pid,
                                "tid": lanes[lane], "ts": ts})
            out.append(ev)
        for lane, tid in lanes.items():
            events.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                "args": {"name": lane or "main"},
            })
        events.extend(out)
        for key, val in sorted(self.counters().items()):
            events.append({
                "name": key, "ph": "C", "pid": pid, "tid": 0,
                "ts": last_ts, "args": {"value": val},
            })
        return events

    def export(self, path: str | pathlib.Path | None = None,
               process_name: str | None = None) -> pathlib.Path | None:
        """Write this process's trace file. Default target is
        ``<export_dir>/proc<pid>.trace.json``; returns None when no
        path is known (tracer enabled ad hoc without a directory)."""
        if path is None:
            if self.export_dir is None:
                return None
            path = self.export_dir / f"proc{os.getpid()}.trace.json"
        path = pathlib.Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        doc = {
            "traceEvents": self.chrome_events(process_name=process_name),
            "displayTimeUnit": "ms",
            "metadata": {
                "wall_t0": self.wall_t0,
                "perf_t0": self.perf_t0,
                "pid": os.getpid(),
                "counters": self.counters(),
                "gauges": self.gauges(),
            },
        }
        tmp = path.with_suffix(path.suffix + ".tmp")
        tmp.write_text(json.dumps(doc))
        os.replace(tmp, path)
        return path


# ---------------------------------------------------------------------
# process singleton
# ---------------------------------------------------------------------
_TRACER = Tracer()


def get_tracer() -> Tracer:
    """The process tracer. Cache-safe: configure() mutates in place."""
    return _TRACER


def configure(enabled: bool | None = None,
              export_dir: str | pathlib.Path | None = None,
              ring_max: int | None = None) -> Tracer:
    return _TRACER.configure(enabled=enabled, export_dir=export_dir,
                             ring_max=ring_max)


def configure_from_env(
    default_dir: str | pathlib.Path | None = None,
    env: dict | None = None,
) -> Tracer:
    """Apply the ``P2PFL_TRACE`` convention: unset/empty → as the
    process left it (off, unless someone enabled the tracer in code);
    ``0`` → disabled; ``1`` → enabled, exporting to ``default_dir``
    (the launcher wires it next to the status dir); any other value →
    enabled, exporting to that path."""
    raw = (env if env is not None else os.environ).get(ENV_VAR, "")
    if raw == "":
        return _TRACER
    if raw == "0":
        return _TRACER.configure(enabled=False)
    if raw == "1":
        return _TRACER.configure(enabled=True, export_dir=default_dir)
    return _TRACER.configure(enabled=True, export_dir=raw)


# ---------------------------------------------------------------------
# XLA recompile counter (jax.monitoring)
# ---------------------------------------------------------------------
# Plain module ints, counted whether or not span tracing is on: the
# recompile signal must reach benchmark records and assertions even in
# an untraced run (tracking two ints per compile is free at compile
# granularity). The tracer mirrors them as counters when enabled.
_xla_lock = threading.Lock()
_xla_installed = False
_xla_recompiles = 0
_xla_compile_s = 0.0

# Seconds of what happens once, kept since the process started and NOT
# zeroed by reset_xla_counters(): set-up runs before any profiler does,
# so spans alone would not reach a reader. jax's own tracing/lowering
# and persistent-cache seconds count only while the program is on the
# stack (``program_scope``): a benchmark's reference traces and
# compiles in the same process, and its seconds are not the program's.
_TRACE_LOWER_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                       "/jax/core/compile/jaxpr_to_mlir_module_duration")
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_program_depth = 0
# tracing nests (a jit traced inside a jit's trace reports its own
# duration too, inner first): the disjoint stretches seen so far, so that
# seconds are counted once, each (start, end, function, traces): the
# function whose event closed it, the outermost's since the inner ones
# merged into it, and 1 where that event was a trace, not a lowering.
# jax fires the trace event around a jaxpr it finds in its cache too (a
# call that missed the dispatch fast path: tens of microseconds, where
# tracing a program anew takes a thousand times that): under
# _TRACED_ANEW_S the seconds count and the trace does not
_TRACED_ANEW_S = 1e-3
_trace_lower_spans: list[tuple[float, float, str, int]] = []
_compile_s_by_function: dict[str, float] = {}
_cache_load_s = 0.0
_stage_s: dict[str, float] = {}
_counted: dict[str, dict] = {}


def _function_of(fun_name: str) -> str:
    """jax reports a trace under the function's own name and its
    lowering and compile under the module's, ``jit(<name>)``."""
    if fun_name.endswith(")") and "(" in fun_name:
        return fun_name[fun_name.index("(") + 1:-1]
    return fun_name


def _add_trace_lower(duration: float, function: str, traces: int) -> None:
    """Note the stretch that ends now and lasted ``duration``. Events
    arrive in order of their ends, so only the newest stretches can lie
    inside (or run into) this one: they merge into it. A function's
    lowering follows its trace: should the clocks' last digits make the
    two touch, the lowering keeps the trace it swallowed."""
    end = time.perf_counter()
    start = end - duration
    spans = _trace_lower_spans
    while spans and spans[-1][1] > start:
        start0, _, function0, traces0 = spans.pop()
        start = min(start, start0)
        if not traces and function0 == function:
            traces = traces0
    spans.append((start, end, function, traces))


def _on_xla_event(event: str, duration: float, fun_name: str = "",
                  **_kw) -> None:
    # the compile counters key on backend_compile specifically:
    # jaxpr tracing/lowering events fire even for programs that then
    # hit the compile cache, and internal array-building programs
    # compile too — only backend_compile counts real XLA work
    global _xla_recompiles, _xla_compile_s, _cache_load_s
    if "backend_compile" in event:
        with _xla_lock:
            _xla_recompiles += 1
            _xla_compile_s += duration
            if _program_depth:
                function = _function_of(fun_name)
                _compile_s_by_function[function] = (
                    _compile_s_by_function.get(function, 0.0) + duration)
        if _TRACER.enabled:
            _TRACER.count("xla/backend_compiles")
            _TRACER.count("xla/backend_compile_s", duration)
    elif _program_depth:
        if event in _TRACE_LOWER_EVENTS:
            with _xla_lock:
                _add_trace_lower(
                    duration, _function_of(fun_name),
                    int(event == _TRACE_LOWER_EVENTS[0]
                        and duration >= _TRACED_ANEW_S))
        elif event == _CACHE_LOAD_EVENT:
            with _xla_lock:
                _cache_load_s += duration


def install_xla_listener() -> bool:
    """Idempotently hook jax.monitoring's compile-duration events into
    the recompile counter. Returns False when jax (or the monitoring
    module) is unavailable — callers treat the counter as absent."""
    global _xla_installed
    with _xla_lock:
        if _xla_installed:
            return True
        try:
            from jax import monitoring
        except Exception:
            return False
        monitoring.register_event_duration_secs_listener(_on_xla_event)
        _xla_installed = True
        return True


def xla_recompiles() -> int:
    """Backend compiles observed since the last reset (0 until
    install_xla_listener() has run)."""
    return _xla_recompiles


def xla_compile_seconds() -> float:
    return _xla_compile_s


def reset_xla_counters() -> None:
    """Zero the compile counters (after warm-up, before a measured
    region — steady-state rounds are expected to stay at 0). The
    since-process-start seconds below are not touched."""
    global _xla_recompiles, _xla_compile_s
    with _xla_lock:
        _xla_recompiles = 0
        _xla_compile_s = 0.0


@contextlib.contextmanager
def program_scope():
    """Held (also as a decorator) by the program's entry calls — a
    Scenario's constructor, ``run()`` and ``evaluate()`` — so that the
    listener tells the program's tracing and cache loads from anyone
    else's in the process. Installs the listener."""
    global _program_depth
    install_xla_listener()
    with _xla_lock:
        _program_depth += 1
    try:
        yield
    finally:
        with _xla_lock:
            _program_depth -= 1


@contextlib.contextmanager
def stage(name: str):
    """A span that also adds its seconds to ``stage_seconds()[name]``,
    whether or not anything is tracing: for the stages of set-up."""
    t0 = time.perf_counter()
    try:
        with _TRACER.span(name):
            yield
    finally:
        dt = time.perf_counter() - t0
        with _xla_lock:
            _stage_s[name] = _stage_s.get(name, 0.0) + dt


def stage_seconds() -> dict[str, float]:
    """Seconds spent under each ``stage()`` name since process start."""
    with _xla_lock:
        return dict(_stage_s)


def note_counted(values: dict) -> None:
    """Fold one round's model counters into ``counted()``. ``values``:
    ``{name: [nodes, epochs, steps, ...]}`` host arrays that rode with
    the round's metrics fetch (what a model counts in a step: an expert
    layer's dropped pairs, its largest load over the mean, a layer). A
    layer that sees all nodes' rows together counts the same on every
    node, so the node axis is reduced by its largest; per name the
    running ``sum`` and ``max`` over steps (trailing axes kept: a
    layer) and the ``steps`` seen. Kept since the process started,
    whether or not anything is tracing."""
    import numpy as np

    for name, v in values.items():
        v = np.asarray(v, np.float64)
        v = v.max(axis=0).reshape((-1,) + v.shape[3:])  # [steps, ...]
        with _xla_lock:
            at = _counted.get(name)
            if at is None or at["sum"].shape != v.shape[1:]:
                # the first round, or another model's layers under the
                # same name (two scenarios in one process): a new record
                at = _counted[name] = {
                    "sum": np.zeros(v.shape[1:]), "steps": 0,
                    "max": np.full(v.shape[1:], -np.inf)}
            at["sum"] = at["sum"] + v.sum(axis=0)
            at["max"] = np.maximum(at["max"], v.max(axis=0))
            at["steps"] += v.shape[0]


def counted() -> dict[str, dict]:
    """``{name: {"sum", "max", "steps"}}`` of every model counter seen
    since process start (``note_counted``)."""
    with _xla_lock:
        return {k: dict(v) for k, v in _counted.items()}


def trace_lower_seconds() -> float:
    """Seconds inside the program's calls during which jax was tracing
    to a jaxpr or lowering one to MLIR, since process start: nested
    traces counted once, and whatever runs at trace time (the kernel
    gate's measurements) with them."""
    with _xla_lock:
        return sum(end - start for start, end, _, _ in _trace_lower_spans)


def trace_lower_by_function() -> dict[str, dict]:
    """``trace_lower_seconds()`` by the outermost jitted function:
    ``{function: {"s", "traces"}}``, the seconds of the stretches its
    events closed (whatever it traced inside itself with them) and how
    many times it was traced anew (``_TRACED_ANEW_S``); the ``s`` sum to
    ``trace_lower_seconds()``. A function that reached the backend's
    compiler inside the program's calls has ``compile_s`` too, the
    seconds of its ``backend_compile`` events (a load from the
    persistent cache among them)."""
    out: dict[str, dict] = {}
    with _xla_lock:
        for start, end, function, traces in _trace_lower_spans:
            at = out.setdefault(function, {"s": 0.0, "traces": 0})
            at["s"] += end - start
            at["traces"] += traces
        for function, seconds in _compile_s_by_function.items():
            out.setdefault(function, {"s": 0.0, "traces": 0})[
                "compile_s"] = seconds
    return out


def cache_load_seconds() -> float:
    """Seconds jax reported for retrieving executables from the
    persistent compilation cache inside the program's calls, since
    process start (each is also inside a backend_compile duration)."""
    return _cache_load_s
