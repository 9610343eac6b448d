"""From the program's spans to host seconds by round and by evaluation.

Takes the RAW tuples of the program's ring, ``(name, lane, t0, dur_s,
args)`` as ``p2pfl_tpu.obs.trace.get_tracer().spans()`` hands them out,
and does all the arithmetic here, so that no reader depends on a
reduction the program could change: which spans lie inside which (by
their intervals, lane by lane), a span's self time (its duration less
its direct children), the window's rounds (the ``scenario.round`` spans
whose ``round`` argument is at least the window's first round) and the
window's evaluations (the last ``n`` ``scenario.evaluate`` spans).

A reader returns ``None`` where its span is absent: a program without
these spans (the parent of the PR that brought them) fills nothing.
"""

ROUND = "scenario.round"
EVALUATE = "scenario.evaluate"
#: children every whole round has. A round that lacks one has lost spans
#: to the ring's eviction (a round's children close, and are evicted,
#: before the round itself), and what is left of it would read as self
#: time: such a round is left out
WHOLE_ROUND = ("scenario.plan", "scenario.dispatch", "scenario.wait")


class Node:
    """One span with the spans directly inside it."""

    __slots__ = ("name", "t0", "dur", "args", "children")

    def __init__(self, name, t0, dur, args):
        self.name, self.t0, self.dur = name, t0, dur
        self.args = args or {}
        self.children = []

    def child_s(self, *names):
        """Seconds of the direct children with one of ``names``."""
        return sum(c.dur for c in self.children if c.name in names)

    def self_s(self):
        """Duration less what the direct children cover."""
        return self.dur - sum(c.dur for c in self.children)


def forest(spans):
    """Every span as a ``Node``, each hung under the innermost span of
    its lane that contains it; returns all nodes, in order of start. A
    span whose parent is not in the list (evicted, or still open when
    the ring was read) simply has none."""
    nodes = []
    lanes = {}
    for name, lane, t0, dur, args in sorted(
            spans, key=lambda s: (s[2], -s[3])):
        node = Node(name, t0, dur, args)
        stack = lanes.setdefault(lane, [])
        # a clock's last digit must not turn a child into a sibling
        while stack and stack[-1].t0 + stack[-1].dur < t0 + dur - 1e-9:
            stack.pop()
        if stack:
            stack[-1].children.append(node)
        stack.append(node)
        nodes.append(node)
    return nodes


def window_rounds(spans, first_round):
    """The whole ``scenario.round`` spans from ``first_round`` on."""
    out = []
    for node in forest(spans):
        if node.name != ROUND or node.args.get("round", -1) < first_round:
            continue
        have = {c.name for c in node.children}
        if all(n in have for n in WHOLE_ROUND):
            out.append(node)
    return out


def last_evaluations(spans, n):
    """The last ``n`` ``scenario.evaluate`` spans, wherever they hang."""
    evs = [node for node in forest(spans) if node.name == EVALUATE]
    return evs[-n:] if n > 0 else []


def per_round(spans, first_round, names, with_self=False):
    """Mean over the window's rounds of the seconds of their direct
    children named in ``names`` (plus, ``with_self``, the rounds' self
    time); ``None`` with no round."""
    rounds = window_rounds(spans, first_round)
    if not rounds:
        return None
    total = sum(r.child_s(*names) + (r.self_s() if with_self else 0.0)
                for r in rounds)
    return total / len(rounds)


def slowest_round_host_s(spans, first_round):
    """In the window's longest round, its duration less its wait for
    the device; ``None`` with no round."""
    rounds = window_rounds(spans, first_round)
    if not rounds:
        return None
    worst = max(rounds, key=lambda r: r.dur)
    return worst.dur - worst.child_s("scenario.wait")


def eval_host_s_per_eval(spans, evals):
    """Mean over the last ``evals`` evaluations of their duration less
    the device pass; ``None`` with no evaluation."""
    evs = last_evaluations(spans, evals)
    if not evs:
        return None
    return sum(e.dur - e.child_s("scenario.evaluate.device")
               for e in evs) / len(evs)

