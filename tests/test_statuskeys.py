"""Status-key three-way sync (round 22): analysis.statuskeys keeps
``monitor.STATUS_KEYS`` (the registry), the publishers (launch /
scenario / devprof / cost_model), and the readers (monitor / webapp /
health) agreeing on the status-record vocabulary. The drift it gates
is silent by nature — a renamed gauge renders "-" forever and fails
nothing — so the repo gate runs from tier-1."""

import ast

from p2pfl_tpu.analysis import statuskeys


def test_repo_status_keys_three_way_sync(capsys):
    """The gate every future PR runs through: readers, publishers and
    the registry agree over the actual repo sources."""
    assert statuskeys.main() == 0
    out = capsys.readouterr().out
    assert "ok:" in out and "in sync" in out


def test_emitted_keys_sees_every_publisher_shape():
    src = (
        "def publish(d):\n"
        "    publish_status(d, 0, {'round': 1, 'loss': 0.5})\n"
        "def _foo_status(obj):\n"
        "    out = {'devprof_mfu': 0.1}\n"
        "    out['devprof_tflops'] = 2.0\n"
        "    return out\n"
        "def fit_gauges(ln):\n"
        "    return {'devprof_fit_s': 1.0}\n"
        "class C:\n"
        "    def run(self):\n"
        "        self.crossdev_last['crossdev_clients_per_s'] = 3\n"
    )
    keys = statuskeys.emitted_keys(ast.parse(src))
    assert keys == {"round", "loss", "devprof_mfu", "devprof_tflops",
                    "devprof_fit_s", "crossdev_clients_per_s"}


def test_consumed_keys_scopes_to_record_readers():
    src = (
        "def _cell(rec):\n"
        "    v = rec.get('devprof_mfu')\n"
        "    w = rec['trust']\n"
        "    return v, w\n"
        # `r` is a rendered-row dict, not a status record: bare
        # subscripts on it must NOT count (monitor's r['age'])
        "def _render(statuses):\n"
        "    for r in statuses:\n"
        "        print(r['age'], r.get('round'))\n"
        # a function with no record-shaped parameter is out of scope
        "def unrelated(cfg):\n"
        "    return cfg.get('nope')\n"
    )
    keys = statuskeys.consumed_keys(ast.parse(src))
    assert keys == {"devprof_mfu", "trust", "round"}


def test_drift_in_either_direction_is_reported(tmp_path, capsys,
                                               monkeypatch):
    """A consumed-but-unregistered key and a registered-but-never-
    emitted key must each fail the pass with a per-key diagnostic."""
    from p2pfl_tpu.utils import monitor

    monkeypatch.setattr(
        monitor, "STATUS_KEYS",
        tuple(monitor.STATUS_KEYS) + ("ghost_gauge",))
    assert statuskeys.main() == 1
    out = capsys.readouterr().out
    assert "no publisher emits: 'ghost_gauge'" in out
