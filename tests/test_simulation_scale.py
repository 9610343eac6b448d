"""Socket-stack scale guard (VERDICT r3 #4): a >8-node federation in
the SUITE, not just the bench — 16 asyncio nodes in the in-process
simulation mode with fan-out-capped control floods
(gossiper.py:66-112's frec/fan-out role) and a binding vote cap, so
the scale behavior the 24-node bench measures has an in-suite
regression tripwire."""

from p2pfl_tpu.config.schema import (
    DataConfig,
    ProtocolConfig,
    ScenarioConfig,
    TrainingConfig,
)
from p2pfl_tpu.p2p.launch import run_simulation


def test_sixteen_node_simulation_fanout_capped():
    cfg = ScenarioConfig(
        name="sim16", n_nodes=16, topology="fully",
        data=DataConfig(dataset="mnist", samples_per_node=48),
        training=TrainingConfig(rounds=2, epochs_per_round=1,
                                learning_rate=0.05),
        protocol=ProtocolConfig(
            heartbeat_period_s=0.5,
            aggregation_timeout_s=60.0,
            vote_timeout_s=10.0,
            train_set_size=6,      # binding vote cap (< n)
            gossip_fanout=4,       # capped flood: no O(n^2) burst
        ),
    )
    res = run_simulation(cfg, timeout=240)
    assert res["n_nodes"] == 16
    assert res["rounds"] == 2
    assert res["mean_accuracy"] is not None
    assert 0.0 <= res["mean_accuracy"] <= 1.0
    # steady-state round time is finite and sane (this guards the
    # mechanism, not a number)
    assert res["round_s"] < 60.0
