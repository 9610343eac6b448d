"""Status-record key three-way sync pass (round 22).

``p2pfl_tpu.utils.monitor.STATUS_KEYS`` is the authoritative registry
of every key a status publisher may emit and a renderer or health rule
may read. The failure mode it exists for is silent: rename a gauge on
the publisher side and the monitor column renders "-" forever, the
health rule never fires, and nothing crashes. This pass fails (exit 1)
when any side drifts:

1. a **consumed** key (best-effort AST scan of the status readers —
   utils/monitor.py, webapp.py, obs/health.py — for ``rec.get("k")`` /
   ``rec["k"]`` reads inside functions that take a status record,
   snapshot, or status list) is not registered: the renderer is
   waiting on a key no publisher is contracted to emit;
2. an **emitted** key (AST scan of the publishers — p2p/launch.py,
   federation/scenario.py, obs/devprof.py, obs/cost_model.py — over
   ``publish_status`` dict literals, ``_*_status`` helper and gauge
   functions, and ``*.crossdev_last[...]`` / ``*.devprof_last[...]``
   stores) is not registered;
3. a **registered** key is never emitted anywhere (the envelope keys
   node/ts/seq come from ``publish_status`` itself): dead registry
   entries rot the contract in the other direction.

Dynamic keys (loop variables, f-strings) are out of scope by design —
they must be registered by hand, which checks 1/3 then police.
"""

from __future__ import annotations

import ast
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]

# publishers scanned for emitted keys
_EMIT_FILES = ("p2pfl_tpu/p2p/launch.py", "p2pfl_tpu/federation/scenario.py",
               "p2pfl_tpu/obs/devprof.py", "p2pfl_tpu/obs/cost_model.py")
# readers scanned for consumed keys
_READ_FILES = ("p2pfl_tpu/utils/monitor.py", "p2pfl_tpu/webapp.py",
               "p2pfl_tpu/obs/health.py")

# gauge builders whose dict literals feed status records without going
# through a ``_*_status``-named helper
_GAUGE_FNS = {"fit_gauges", "round_gauges", "memory_watermark"}
# attributes whose item-stores are splatted into status records
_LAST_ATTRS = {"crossdev_last", "devprof_last"}
# record-shaped parameters marking a function as a status reader
_READER_PARAMS = {"statuses", "snap", "rec"}
# receiver names bound to one status record inside a reader; bare
# subscript reads only count on ``rec`` (``r``/``s`` also name rendered
# row dicts, e.g. monitor's ``r["age"]``)
_REC_NAMES = {"rec", "r", "s", "status"}
_SUBSCRIPT_NAMES = {"rec"}
# keys publish_status/make_record stamp on every record itself
_ENVELOPE = {"node", "ts", "seq"}


def _dict_keys(d: ast.Dict) -> set[str]:
    return {k.value for k in d.keys
            if isinstance(k, ast.Constant) and isinstance(k.value, str)}


def _is_emitter(fn: ast.AST) -> bool:
    name = getattr(fn, "name", "")
    return ((name.startswith("_") and name.endswith("_status"))
            or name in _GAUGE_FNS)


def emitted_keys(tree: ast.Module) -> set[str]:
    """Constant keys a publisher file can put on a status record."""
    keys: set[str] = set()
    for node in ast.walk(tree):
        # publish_status(dir, node, {<literal>...})
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "publish_status"):
            for arg in node.args:
                if isinstance(arg, ast.Dict):
                    keys |= _dict_keys(arg)
        # self.crossdev_last["k"] = ... / self.devprof_last["k"] = ...
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if (isinstance(tgt, ast.Subscript)
                        and isinstance(tgt.value, ast.Attribute)
                        and tgt.value.attr in _LAST_ATTRS
                        and isinstance(tgt.slice, ast.Constant)
                        and isinstance(tgt.slice.value, str)):
                    keys.add(tgt.slice.value)
        # _*_status helpers and the devprof/cost_model gauge builders:
        # every dict literal and constant item-store inside builds (a
        # piece of) a status record
        if (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and _is_emitter(node)):
            for sub in ast.walk(node):
                if isinstance(sub, ast.Dict):
                    keys |= _dict_keys(sub)
                elif isinstance(sub, ast.Assign):
                    for tgt in sub.targets:
                        if (isinstance(tgt, ast.Subscript)
                                and isinstance(tgt.value, ast.Name)
                                and isinstance(tgt.slice, ast.Constant)
                                and isinstance(tgt.slice.value, str)):
                            keys.add(tgt.slice.value)
    return keys


def consumed_keys(tree: ast.Module) -> set[str]:
    """Constant keys a reader file looks up on a status record:
    ``rec.get("k")`` / ``rec["k"]`` where the receiver is a record
    name inside a function that takes a record/snapshot/status list."""
    keys: set[str] = set()
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        params = {a.arg for a in (fn.args.posonlyargs + fn.args.args
                                  + fn.args.kwonlyargs)}
        if not (params & _READER_PARAMS):
            continue
        for node in ast.walk(fn):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "get"
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in _REC_NAMES
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                keys.add(node.args[0].value)
            elif (isinstance(node, ast.Subscript)
                    and isinstance(node.ctx, ast.Load)
                    and isinstance(node.value, ast.Name)
                    and node.value.id in _SUBSCRIPT_NAMES
                    and isinstance(node.slice, ast.Constant)
                    and isinstance(node.slice.value, str)):
                keys.add(node.slice.value)
    return keys


def main() -> int:
    sys.path.insert(0, str(REPO))
    from p2pfl_tpu.utils.monitor import STATUS_KEYS

    registered = set(STATUS_KEYS)
    emitted: set[str] = set()
    for rel in _EMIT_FILES:
        emitted |= emitted_keys(ast.parse((REPO / rel).read_text()))
    consumed: set[str] = set()
    for rel in _READ_FILES:
        consumed |= consumed_keys(ast.parse((REPO / rel).read_text()))

    unregistered_reads = sorted(consumed - registered)
    unregistered_emits = sorted(emitted - registered)
    never_emitted = sorted(registered - emitted - _ENVELOPE)
    for k in unregistered_reads:
        print(f"status reader consumes a key missing from STATUS_KEYS: {k!r}")
    for k in unregistered_emits:
        print(f"publisher emits a key missing from STATUS_KEYS: {k!r}")
    for k in never_emitted:
        print(f"STATUS_KEYS entry no publisher emits: {k!r}")
    if unregistered_reads or unregistered_emits or never_emitted:
        return 1
    print(f"ok: {len(registered)} registered status keys, "
          f"{len(emitted)} emitted and {len(consumed)} consumed "
          "all in sync")
    return 0


if __name__ == "__main__":
    sys.exit(main())
