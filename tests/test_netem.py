"""Network emulation (p2p.netem) + capped-fanout flooding.

The reference degrades links with tcset --rate/--delay/--loss from
config (fedstellar/base_node.py:82-85, participant.json.example:34-38)
— untestable without root. Here shaping is in-process and seeded, so
"does the federation survive a lossy 50 ms network" is a deterministic
test, and the control-flood fan-out cap (GOSSIP_MESSAGES_PER_ROUND
analog, gossiper.py:66-112) gets a 24-node exercise.
"""

import asyncio
import time

import numpy as np
import pytest

from p2pfl_tpu.config.schema import NetworkConfig, ProtocolConfig
from p2pfl_tpu.p2p.netem import LinkShaper, shaper_from_config

from tests.test_p2p import _PROTO, _run_federation


class _FakePeer:
    def __init__(self, idx):
        self.idx = idx
        self.writer = None


class _Recorder:
    """Stands in for write_message by monkeypatching."""

    def __init__(self):
        self.delivered = []

    async def write(self, writer, msg):
        self.delivered.append((time.monotonic(), msg))


def test_shaper_deterministic_loss(monkeypatch):
    async def main():
        rec = _Recorder()
        monkeypatch.setattr("p2pfl_tpu.p2p.netem.write_message", rec.write)

        def run_pattern():
            s = LinkShaper(src=3, loss_pct=30.0, seed=42)
            return [s._rng.random() < s.loss for _ in range(200)]

        assert run_pattern() == run_pattern()  # same seed, same schedule
        # and a different source gets a different schedule
        s2 = LinkShaper(src=4, loss_pct=30.0, seed=42)
        other = [s2._rng.random() < s2.loss for _ in range(200)]
        assert other != run_pattern()

    asyncio.run(main())


def test_shaper_loss_rate_and_counters(monkeypatch):
    async def main():
        rec = _Recorder()
        monkeypatch.setattr("p2pfl_tpu.p2p.netem.write_message", rec.write)
        s = LinkShaper(src=0, loss_pct=25.0, seed=7)
        peer = _FakePeer(1)
        for i in range(400):
            await s.send(peer, f"m{i}")
        # drain: no delay configured, worker delivers immediately
        for _ in range(100):
            if s.sent + s.dropped == 400:
                break
            await asyncio.sleep(0.01)
        assert s.sent + s.dropped == 400
        assert 0.15 < s.dropped / 400 < 0.35  # ~25%
        s.close()

    asyncio.run(main())


def test_shaper_fifo_under_jitter(monkeypatch):
    """Jitter must not reorder a link (TCP semantics)."""

    async def main():
        rec = _Recorder()
        monkeypatch.setattr("p2pfl_tpu.p2p.netem.write_message", rec.write)
        s = LinkShaper(src=0, delay_ms=5, jitter_ms=30, seed=1)
        peer = _FakePeer(1)
        t_send = time.monotonic()
        for i in range(50):
            await s.send(peer, i)
        deadline = time.monotonic() + 5
        while len(rec.delivered) < 50 and time.monotonic() < deadline:
            await asyncio.sleep(0.01)
        got = [m for _, m in rec.delivered]
        assert got == sorted(got), "link reordered messages"
        # delivery really was delayed by at least the base delay
        assert rec.delivered[0][0] - t_send >= 0.005
        s.close()

    asyncio.run(main())


def test_shaper_from_config_zero_is_none():
    assert shaper_from_config(0, None) is None
    assert shaper_from_config(0, NetworkConfig()) is None
    assert shaper_from_config(0, NetworkConfig(delay_ms=10)) is not None


def test_two_node_federation_with_small_delay():
    """Every-run netem-federation guard: 2 nodes, 10 ms +-3 ms delay,
    2% loss, one round — the emulated-link wiring through real
    federation traffic, at seconds not minutes."""

    async def main():
        net = NetworkConfig(delay_ms=10, jitter_ms=3, loss_pct=2, seed=4)
        fed, nodes = await _run_federation(
            ["aggregator"] * 2, rounds=1, samples=96, timeout=90,
            netem=net,
        )
        try:
            assert all(node.round == 1 for node in nodes)
        finally:
            for node in nodes:
                await node.stop()

    asyncio.run(main())


@pytest.mark.slowtier
def test_federation_converges_under_delay_and_loss():
    """8 nodes, fully connected, 50 ms +-10 ms delay, 5% loss: voting,
    gossip, the round barrier, and aggregation timeouts must carry the
    federation through 2 rounds anyway (the VERDICT r2 #5 acceptance
    scenario). Slow tier (~51 s of emulated delay):
    test_shaper_* cover the netem mechanics and
    test_two_node_federation_with_small_delay keeps an every-run
    netem-federation guard."""

    async def main():
        n = 8
        proto = ProtocolConfig(heartbeat_period_s=0.3,
                               aggregation_timeout_s=30.0,
                               vote_timeout_s=8.0)
        net = NetworkConfig(delay_ms=50, jitter_ms=10, loss_pct=5, seed=9)
        fed, nodes = await _run_federation(
            ["aggregator"] * n, rounds=2, proto=proto, samples=150,
            timeout=280, netem=net,
        )
        try:
            assert all(node.round == 2 for node in nodes)
            # liveness is the acceptance criterion; learning is checked
            # on the federation MEAN (per-node val splits are 15
            # samples — individually too noisy to threshold)
            accs = [node.learner.evaluate()["accuracy"] for node in nodes]
            assert sum(accs) / len(accs) > 0.4, accs
        finally:
            for node in nodes:
                await node.stop()

    asyncio.run(main())


@pytest.mark.slowtier
def test_24node_federation_with_fanout_cap():
    """VERDICT r2 #6: the socket path past 8 nodes. 24 nodes, fully
    connected, control-flood relays capped at 6 random peers
    (gossip_fanout) and a binding train-set cap — every node must
    finish 2 rounds within the timeout. Records nothing. Slow tier
    (~94 s): tests/test_simulation_scale.py guards the >8-node
    fan-out-capped behavior every run at 16 nodes in ~11 s."""

    async def main():
        n = 24
        proto = ProtocolConfig(heartbeat_period_s=0.5,
                               aggregation_timeout_s=60.0,
                               vote_timeout_s=10.0, train_set_size=8,
                               gossip_fanout=6)
        fed, nodes = await _run_federation(
            ["aggregator"] * n, rounds=2, proto=proto, samples=60,
            timeout=280,
        )
        try:
            assert all(node.round == 2 for node in nodes)
            # the train-set cap held: at most 8 contributors anywhere
            assert all(len(node.session.covered) <= 8 for node in nodes)
            # everyone ends on an aggregate (selected nodes covered it,
            # voted-out nodes adopted it)
            k0 = np.asarray(
                nodes[0].learner.get_parameters()["params"]["Dense_0"]["kernel"]
            )
            k9 = np.asarray(
                nodes[9].learner.get_parameters()["params"]["Dense_0"]["kernel"]
            )
            np.testing.assert_allclose(k0, k9, rtol=1e-4, atol=1e-5)
        finally:
            for node in nodes:
                await node.stop()

    asyncio.run(main())
