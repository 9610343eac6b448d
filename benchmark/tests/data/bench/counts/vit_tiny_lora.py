"""Fixture: the DeiT-Ti count with the base frozen. A frozen layer needs
its forward pass and its input gradient, no weight gradient, and the
first layer no backward pass at all; an adapter pair ``x @ A @ B`` is
trained, and its input's gradient belongs to the layer it sits on."""

import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "bench_count_vit_tiny",
    pathlib.Path(__file__).resolve().parents[4] / "counts" / "vit_tiny.py")
vit = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(vit)


def per_sample(config, scenario):
    arch = config["architecture"]
    whole = vit.per_sample(config, scenario)["forward"]
    t, d, r = arch["tokens"], arch["embed_dim"], scenario["lora"]["rank"]
    patch = 2 * t * arch["patch"] ** 2 * arch["input"][2] * d
    wide = arch["num_heads"] * arch["head_dim"]
    adapters = len(arch["lora_targets"]) * arch["depth"] * 2 * t * r * (d + wide)
    return {"forward": whole + adapters,
            "train": 2 * whole - patch + 3 * adapters}
