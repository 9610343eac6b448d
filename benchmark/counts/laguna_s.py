"""Laguna-S-2.1 as a frozen base under adapters: required FLOPs of a
sample (one sequence), and the required FLOPs and bytes of the three
scopes that have a roofline reader.

Counted from the configuration's sizes (``scenario.model.kwargs``) and
the cell's scenario (sequence length from the data set's name, adapter
rank). A frozen product needs its forward pass and its input gradient,
no weight gradient; the first layer's q, k, v and gate projections read
the frozen embedding and need no input gradient either. An adapter pair
needs its forward pass and both its gradients (three times its
forward). Attention is its REQUIRED products only, whatever tiles
compute them: the causal half (``(T + 1) / 2`` keys a query) in a full
layer, the window's band (``W - W (W - 1) / 2T`` keys a query) in a
window layer, scores and values of every query head, with a backward
pass of twice the forward. The expert layer is the EXPECTED held pairs a
token (``top_k * held / experts`` = 2.5 in the cell). Recomputation
(remat, the expert layer's recomputed backward, attention's tiles formed
again on the way back) is not required work and is not counted; nor are
norms, gates, rotary embeddings, softmax or the loss."""

WINDOW = "sliding_attention"


def sizes(config, scenario):
    z = dict(scenario["model"]["kwargs"])
    z["T"] = int(scenario["data"]["dataset"].split("-")[2])
    z["rank"] = scenario["lora"]["rank"]
    z["pairs"] = z["top_k"] * z["experts_held"] / z["n_experts"]
    return z


def keys_seen(z, kind):
    """Keys a query sees, the mean over a sequence's positions."""
    T, W = z["T"], min(z["window"], z["T"])
    return W - W * (W - 1) / (2 * T) if kind == WINDOW else (T + 1) / 2


def per_token(z):
    """Forward FLOPs a token: (frozen products, adapters, attention's
    own products over positions, the first layer's input projections)."""
    d, G, D, r = z["hidden"], z["kv_heads"], z["head_dim"], z["rank"]
    frozen = adapters = own = first_in = 0
    for n, (kind, mlp, H) in enumerate(zip(
            z["layer_types"], z["mlp_layer_types"], z["heads"])):
        into = 2 * d * (H * D + 2 * G * D + H)  # q, k, v; the gate
        frozen += into + 2 * H * D * d
        adapters += 2 * r * (2 * (d + H * D) + 2 * (d + G * D))
        own += 2 * H * 2 * D * keys_seen(z, kind)
        if n == 0:
            first_in = into
        if mlp == "sparse":
            frozen += 2 * d * z["n_experts"] + 6 * d * z["shared_width"] \
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
    memory if nothing in between is kept there: ``swa.attn`` and
    ``gqa.attn`` read q, k and v and write the output once, 2 bytes
    each, a position (a pass back is counted as twice that again);
    ``moe.experts`` reads every held expert's weights once a pass of the
    federation's step (given a token: over the step's tokens) and reads
    and writes a row a pair."""
    z = sizes(config, scenario)
    d, G, D, W = z["hidden"], z["kv_heads"], z["head_dim"], z["expert_width"]
    attn = {WINDOW: [0, 0], "full_attention": [0, 0]}
    for kind, H in zip(z["layer_types"], z["heads"]):
        attn[kind][0] += 2 * H * 2 * D * keys_seen(z, kind)
        attn[kind][1] += (2 * H * D + 2 * G * D) * 2
    n_moe = sum(m == "sparse" for m in z["mlp_layer_types"])
    step_tokens = scenario["n_nodes"] * scenario["data"]["batch_size"] * z["T"]
    weights = z["experts_held"] * 3 * d * W * 2 / step_tokens
    moe = (n_moe * z["pairs"] * 6 * d * W,
           n_moe * (weights + z["pairs"] * 2 * d * 2))
    twice = lambda w: (2 * w[0], 2 * w[1])
    thrice = lambda w: (3 * w[0], 3 * w[1])
    swa, gqa = tuple(attn[WINDOW]), tuple(attn["full_attention"])
    return {"swa.attn": {"forward": swa, "train": thrice(swa)},
            "gqa.attn": {"forward": gqa, "train": thrice(gqa)},
            "moe.experts": {"forward": moe, "train": twice(moe)}}
