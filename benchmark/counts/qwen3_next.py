"""Qwen3-Next-80B-A3B as a frozen base under adapters: required FLOPs of
a sample (one sequence), and the required FLOPs and bytes of the three
scopes that have a roofline reader.

Counted from the configuration's sizes (``scenario.model.kwargs``) and
the cell's scenario (sequence length from the data set's name, adapter
rank). A frozen product needs its forward pass and its input gradient,
no weight gradient; the first layer's products that read the frozen
embedding (``in_proj_qkvz`` and ``in_proj_ba`` of a Gated DeltaNet
layer) need no input gradient either. An adapter pair needs its forward
pass and both its gradients (three times its forward). Attention is its
causal half, the delta rule its recurrence over the value heads (``k^T
S``, the rank-one update, ``q^T S``: 6 K V a value head a position; the
chunk-wise form's extra products are not required work), both with a
backward pass of twice the forward. The expert layer is the EXPECTED
held pairs a token (``top_k * held / experts`` = 1.25 in the cell) plus
its router and its gated shared expert. Recomputation (remat, the
expert layer's recomputed backward) is not required work and is not
counted; nor are norms, gates' activations, rotary embeddings, softmax
or the loss."""


def sizes(config, scenario):
    z = dict(scenario["model"]["kwargs"])
    z["T"] = int(scenario["data"]["dataset"].split("-")[2])
    z["rank"] = scenario["lora"]["rank"]
    first = z.get("first_layer", 0)
    z["kinds"] = ["attn" if (i + 1) % z["full_attention_interval"] == 0
                  else "gdn" for i in range(first, first + z["layers"])]
    z["pairs"] = z["top_k"] * z["experts_held"] / z["n_experts"]
    return z


def per_token(z):
    """Forward FLOPs a token: (frozen products, adapters, the mixers'
    own products over positions, the first layer's products that read
    the embedding)."""
    d, r, T = z["hidden"], z["rank"], z["T"]
    Hk, H, K = z["key_heads"], z["value_heads"], z["linear_head_dim"]
    A, G, D = z["heads"], z["kv_heads"], z["head_dim"]
    qkvz = 2 * (Hk + H) * K
    frozen = adapters = own = first_in = 0
    for n, mixer in enumerate(z["kinds"]):
        if mixer == "gdn":
            into = 2 * d * (qkvz + 2 * H)  # in_proj_qkvz, in_proj_ba
            frozen += into + 2 * H * K * d
            adapters += 2 * r * ((d + qkvz) + (H * K + d))
            own += 6 * K * K * H + 2 * z["conv"] * (2 * Hk + H) * K
        else:
            into = 2 * d * (2 * A * D + 2 * G * D)  # q and gate, k, v
            frozen += into + 2 * A * D * d
            adapters += 2 * r * ((d + 2 * A * D) + 2 * (d + G * D)
                                 + (A * D + d))
            own += 2 * A * 2 * D * (T + 1) / 2  # the causal half
        if n == 0:
            first_in = into
        frozen += 2 * d * z["n_experts"] + 6 * d * z["shared_width"] \
            + 2 * d + z["pairs"] * 6 * d * z["expert_width"]
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
    memory if nothing in between is kept there: ``gdn.scan`` reads q and
    k once a KEY head, v a value head (2 bytes), the decay and beta a
    value head (4 bytes each) and writes its output, a position;
    ``gattn.attn`` reads q, k and v and writes the output once, 2 bytes
    each, a position, for the causal half's score and value products;
    ``moe.experts`` reads every held expert's weights once a pass of the
    federation's step (given a token: over the step's tokens) and reads
    and writes a row a pair. Each pass back is counted as twice its
    forward."""
    z = sizes(config, scenario)
    d, W = z["hidden"], z["expert_width"]
    Hk, H, K = z["key_heads"], z["value_heads"], z["linear_head_dim"]
    A, G, D = z["heads"], z["kv_heads"], z["head_dim"]
    n_gdn = z["kinds"].count("gdn")
    n_attn = len(z["kinds"]) - n_gdn
    step_tokens = scenario["n_nodes"] * scenario["data"]["batch_size"] * z["T"]
    weights = z["experts_held"] * 3 * d * W * 2 / step_tokens
    moe = (len(z["kinds"]) * z["pairs"] * 6 * d * W,
           len(z["kinds"]) * (weights + z["pairs"] * 2 * d * 2))
    gdn = (n_gdn * 6 * K * K * H,
           n_gdn * (Hk * 2 * K * 2 + H * (K * 2 + 4 + 4 + K * 2)))
    attn = (n_attn * 2 * A * 2 * D * (z["T"] + 1) / 2,
            n_attn * (2 * A * D + 2 * G * D) * 2)
    twice = lambda w: (2 * w[0], 2 * w[1])
    thrice = lambda w: (3 * w[0], 3 * w[1])
    return {"moe.experts": {"forward": moe, "train": twice(moe)},
            "gdn.scan": {"forward": gdn, "train": thrice(gdn)},
            "gattn.attn": {"forward": attn, "train": thrice(attn)}}
