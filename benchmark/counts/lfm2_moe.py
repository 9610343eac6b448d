"""LFM2-8B-A1B as a frozen base under adapters: required FLOPs of a
sample (one sequence), and the required FLOPs and bytes of the three
scopes that have a roofline reader.

Counted from the configuration's sizes (``scenario.model.kwargs``) and
the cell's scenario (sequence length from the data set's name, adapter
rank). A frozen product needs its forward pass and its input gradient,
no weight gradient; the first layer's input projection reads the frozen
embedding and needs no input gradient either. An adapter pair needs its
forward pass and both its gradients (three times its forward).
Attention is its REQUIRED products only, whatever tiles compute them:
the causal half (``(T + 1) / 2`` keys a query), scores and values of
every query head, with a backward pass of twice the forward. The
convolution operator's own arithmetic (two products and ``taps``
multiply-adds a channel: ``2 taps + 1`` FLOPs a channel a position) is
counted with the frozen products' rule. The expert layer is the chosen
pairs a token that are held here (``top_k * held / experts``: all 4 in
the cell). The head is ``h E^T`` over the whole vocabulary.
Recomputation (remat, the expert layer's recomputed backward,
attention's tiles formed again on the way back) is not required work and
is not counted; nor are norms, the rotary embedding, softmax, the
router's sigmoid or the loss."""

CONV = "conv"


def sizes(config, scenario):
    z = dict(scenario["model"]["kwargs"])
    z["T"] = int(scenario["data"]["dataset"].split("-")[2])
    z["rank"] = scenario["lora"]["rank"]
    z["pairs"] = z["top_k"] * z["experts_held"] / z["n_experts"]
    return z


def per_token(z):
    """Forward FLOPs a token: (frozen products and the convolution
    chains, adapters, attention's own products over positions, the first
    layer's input projection)."""
    d, H, G, D, r = (z["hidden"], z["heads"], z["kv_heads"], z["head_dim"],
                     z["rank"])
    frozen = adapters = own = first_in = 0
    for n, kind in enumerate(z["layer_types"]):
        if kind == CONV:
            into = 2 * d * 3 * d
            frozen += into + 2 * d * d + (2 * z["taps"] + 1) * d
            adapters += 2 * r * ((d + 3 * d) + (d + d))
        else:
            into = 2 * d * (H * D + 2 * G * D)
            frozen += into + 2 * H * D * d
            adapters += 2 * r * (2 * (d + H * D) + 2 * (d + G * D))
            own += 2 * H * 2 * D * (z["T"] + 1) / 2
        if n == 0:
            first_in = into
        if n >= z["dense_layers"]:
            frozen += 2 * d * z["n_experts"] \
                + z["pairs"] * 6 * d * z["expert_width"]
        else:
            frozen += 6 * d * z["dense_width"]
    frozen += 2 * d * z["vocab"]
    return frozen, adapters, own, first_in


def per_sample(config, scenario):
    z = sizes(config, scenario)
    frozen, adapters, own, first_in = per_token(z)
    return {"forward": z["T"] * (frozen + adapters + own),
            "train": z["T"] * (2 * frozen - first_in + 3 * (adapters + own))}


def scope_work(config, scenario):
    """``{scope: {"forward": (flops, bytes), "train": (flops, bytes)}}``
    a token over all the layers that have the scope: what the roofline
    readers divide by the peaks. Bytes are what has to cross the chip's
    memory if nothing in between is kept there. ``lfm2.conv`` reads the
    input projection's three thirds and writes the gated output, 2 bytes
    each, a position a convolution layer, for ``2 taps + 1`` FLOPs a
    channel: bound by bytes; a pass back reads the output's gradient and
    the three thirds again and writes their gradients (counted as twice
    the forward again, as attention's is). ``gqa.attn`` reads q, k and v
    and writes the output once, 2 bytes each, a position (a pass back
    twice that again). ``moe.experts`` reads every held expert's weights
    once a pass of the federation's step (given a token: over the step's
    tokens) and reads and writes a row a pair: bound by FLOPs."""
    z = sizes(config, scenario)
    d, H, G, D, W = (z["hidden"], z["heads"], z["kv_heads"], z["head_dim"],
                     z["expert_width"])
    n_conv = sum(kind == CONV for kind in z["layer_types"])
    n_attn = len(z["layer_types"]) - n_conv
    n_moe = len(z["layer_types"]) - z["dense_layers"]
    conv = (n_conv * (2 * z["taps"] + 1) * d, n_conv * (3 * d + d) * 2)
    gqa = (n_attn * 2 * H * 2 * D * (z["T"] + 1) / 2,
           n_attn * (2 * H * D + 2 * G * D) * 2)
    step_tokens = scenario["n_nodes"] * scenario["data"]["batch_size"] * z["T"]
    weights = z["experts_held"] * 3 * d * W * 2 / step_tokens
    moe = (n_moe * z["pairs"] * 6 * d * W,
           n_moe * (weights + z["pairs"] * 2 * d * 2))
    twice = lambda w: (2 * w[0], 2 * w[1])
    thrice = lambda w: (3 * w[0], 3 * w[1])
    return {"lfm2.conv": {"forward": conv, "train": thrice(conv)},
            "gqa.attn": {"forward": gqa, "train": thrice(gqa)},
            "moe.experts": {"forward": moe, "train": twice(moe)}}
