"""The host's side of a run, from the program's ring and counters: what
the stall watch saw beside the rounds' waits, ``run()`` outside its
rounds, the parts of ``scenario.log``, and set-up's tracing by program.

Shared by the readers that PR 43 added (``driver.stall_s_in_wait_per_round``
and its neighbours, ``entry.round_trace_lower_s`` and its). ``spans.py``
does the nesting; here are the interval overlap and the program's names.
Every name is the program's own constant, looked up and not spelled
again: on a program without it (the parent of the PR that brought it)
the name is ``None`` and every function here returns ``None``.
"""

import statistics

import spans
from p2pfl_tpu.federation import scenario
from p2pfl_tpu.obs import trace as obs_trace
from p2pfl_tpu.parallel import transport

STALL = getattr(obs_trace, "STALL_SPAN", None)
RUN = getattr(scenario, "SPAN_RUN", None)
LOG_METRICS = getattr(scenario, "SPAN_LOG_METRICS", None)
LOG_RESOURCES = getattr(scenario, "SPAN_LOG_RESOURCES", None)
LOG_WRITE = getattr(scenario, "SPAN_LOG_WRITE", None)
ROUND_PROGRAM = getattr(transport, "ROUND_PROGRAM", None)
EVAL_PROGRAM = getattr(transport, "EVAL_PROGRAM", None)
WAIT = "scenario.wait"
LOG = "scenario.log"


def overlap_s(a, b):
    """Seconds that the intervals ``a`` and ``b`` share; each a list of
    ``(t0, dur)``, neither overlapping itself."""
    return sum(max(0.0, min(s + d, t + e) - max(s, t))
               for s, d in a for t, e in b)


def stalls(ring):
    """The stall watch's records as ``(t0, dur)``."""
    return [(t0, dur) for name, _, t0, dur, _ in ring if name == STALL]


def waits(ring, first_round):
    """The window's rounds' ``scenario.wait`` spans, a list a round."""
    return [[(c.t0, c.dur) for c in r.children if c.name == WAIT]
            for r in spans.window_rounds(ring, first_round)]


def last_run(ring):
    """The ring's last ``scenario.run`` (the window's), as a node with
    its children; ``None`` without one."""
    if RUN is None:
        return None
    runs = [n for n in spans.forest(ring) if n.name == RUN]
    return runs[-1] if runs else None


def watched(ring):
    """Whether the ring is a watched ``run()``'s: the program has the
    stall watch, which lives as long as a ``run()`` does."""
    return STALL is not None and last_run(ring) is not None


def stall_s_in_wait_per_round(ring, first_round):
    """What the watch saw stand still inside the window's waits for the
    device, over the rounds: 0 in a quiet run."""
    rounds = waits(ring, first_round)
    if not (rounds and watched(ring)):
        return None
    return sum(overlap_s(stalls(ring), w) for w in rounds) / len(rounds)


def wait_over_median_s_per_round(ring, first_round):
    """The window's mean ``scenario.wait`` a round less its median: the
    excess that a stall has to explain."""
    rounds = [sum(d for _, d in w) for w in waits(ring, first_round)]
    if not (rounds and watched(ring)):
        return None
    return sum(rounds) / len(rounds) - statistics.median(rounds)


def longest_stall_s(ring, evals):
    """The longest stall that touches the last ``run()`` or one of the
    last ``evals`` evaluations; 0 with none."""
    run = last_run(ring)
    if STALL is None or run is None:
        return None
    held = [(n.t0, n.dur)
            for n in [run] + spans.last_evaluations(ring, evals)]
    return max((d for t, d in stalls(ring)
                if overlap_s([(t, d)], held) > 0.0), default=0.0)


def run_outside_rounds_s(ring):
    """From the last ``run()``'s entry to the start of its closing
    evaluation (its end without one), less its rounds: paid once a
    ``run()``, and inside ``round_s``."""
    run = last_run(ring)
    if run is None:
        return None
    closing = [c.t0 for c in run.children if c.name == spans.EVALUATE]
    until = closing[-1] if closing else run.t0 + run.dur
    return until - run.t0 - run.child_s(spans.ROUND)


def log_part_s_per_round(ring, first_round, name):
    """Seconds a round of the ``scenario.log`` child ``name``."""
    rounds = spans.window_rounds(ring, first_round)
    parts = [p.dur for r in rounds for log in r.children if log.name == LOG
             for p in log.children if p.name == name]
    if name is None or not parts:
        return None
    return sum(parts) / len(rounds)


def trace_lower_of(program, key="s"):
    """``key`` of the program's record in
    ``obs.trace.trace_lower_by_function()``: its seconds of tracing and
    lowering since process start, or how often it was traced anew."""
    by_function = getattr(obs_trace, "trace_lower_by_function", None)
    if by_function is None or program is None:
        return None
    return by_function().get(program, {}).get(key)
