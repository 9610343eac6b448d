"""Scenario runner CLI.

Successor of the reference's controller CLI + node launcher
(app/main.py:11-48 argparse; fedstellar/node_start.py): one command
builds a scenario (from a JSON file or from flags), renders the
topology PNG, runs the federation in-process on the device mesh, and
prints a JSON result line.

    python -m p2pfl_tpu.run scenario.json
    python -m p2pfl_tpu.run --federation DFL --topology ring --nodes 8 \
        --dataset mnist --model mnist-mlp --rounds 3
"""

from __future__ import annotations

import argparse
import json
import sys

from p2pfl_tpu.config.schema import (
    DataConfig,
    ModelConfig,
    ScenarioConfig,
    TrainingConfig,
)
from p2pfl_tpu.federation.scenario import Scenario
from p2pfl_tpu.utils import compile_cache


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="p2pfl_tpu.run",
        description="Run a federated learning scenario on the TPU mesh.",
    )
    p.add_argument("config", nargs="?", help="scenario JSON (optional)")
    p.add_argument("--federation", choices=["DFL", "CFL", "SDFL"],
                   default="DFL")  # app/main.py:13-14
    p.add_argument("--topology", choices=["fully", "ring", "random", "star"],
                   default="fully")  # app/main.py --topology
    p.add_argument("--nodes", type=int, default=2)
    p.add_argument("--dataset", default="mnist")
    p.add_argument("--model", default="mnist-mlp")
    p.add_argument("--partition", default="iid",
                   choices=["iid", "sorted", "dirichlet"])
    p.add_argument("--rounds", type=int, default=3)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--aggregator", default="fedavg")
    p.add_argument("--samples-per-node", type=int, default=None)
    p.add_argument("--target-accuracy", type=float, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-dir", default=None)
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=0)
    p.add_argument("--transport", choices=["auto", "dense", "sparse"],
                   default="auto",
                   help="weight-exchange collective schedule")
    p.add_argument("--tensorboard", action="store_true",
                   help="also write TensorBoard event files (needs --log-dir)")
    p.add_argument("--wandb", action="store_true",
                   help="mirror metrics to a Weights & Biases run")
    p.add_argument("--profile-dir", default=None,
                   help="jax.profiler trace of one steady-state round")
    p.add_argument("--save-config", default=None,
                   help="write the effective scenario JSON here and exit")
    p.add_argument("--platform", default=None,
                   help="force a JAX platform (e.g. cpu) before any "
                        "device use — for browser-launched or CI runs")
    return p


def config_from_args(args: argparse.Namespace) -> ScenarioConfig:
    if args.config:
        return ScenarioConfig.load(args.config)
    return ScenarioConfig(
        name=f"{args.dataset}-{args.model}-{args.federation.lower()}",
        federation=args.federation,
        topology=args.topology,
        n_nodes=args.nodes,
        data=DataConfig(dataset=args.dataset, partition=args.partition,
                        batch_size=args.batch_size,
                        samples_per_node=args.samples_per_node,
                        seed=args.seed),
        model=ModelConfig(model=args.model),
        training=TrainingConfig(rounds=args.rounds,
                                epochs_per_round=args.epochs,
                                learning_rate=args.lr),
        aggregator=args.aggregator,
        seed=args.seed,
        log_dir=args.log_dir,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        transport=args.transport,
        tensorboard=args.tensorboard,
        wandb=args.wandb,
        profile_dir=args.profile_dir,
    )


def main(argv: list[str] | None = None) -> int:
    compile_cache.enable()
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    if args.tensorboard and not args.log_dir and not args.config:
        # surface the misconfiguration before any compute is spent —
        # the logger would otherwise silently no-op the flag
        parser.error("--tensorboard requires --log-dir")
    cfg = config_from_args(args)
    if args.save_config:
        cfg.save(args.save_config)
        print(f"wrote {args.save_config}")
        return 0
    # Scenario renders the topology PNG itself when log_dir is set
    scenario = Scenario(cfg)
    result = scenario.run(target_accuracy=args.target_accuracy)
    scenario.close()
    out = {
        "scenario": cfg.name,
        "federation": cfg.federation,
        "topology": cfg.topology,
        "n_nodes": cfg.n_nodes,
        "rounds": result.rounds_run,
        "final_accuracy": round(result.final_accuracy, 4),
        "min_accuracy": round(result.min_accuracy, 4),  # alive nodes only
        "mean_round_time_s": round(
            sum(result.round_times_s) / max(len(result.round_times_s), 1), 4
        ),
        "rounds_to_target": result.rounds_to_target,
    }
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
