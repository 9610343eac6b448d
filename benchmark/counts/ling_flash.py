"""Ling-3.0-flash as a frozen base under adapters: required FLOPs of a
sample (one sequence), and the required FLOPs and bytes of the two
scopes that have a roofline reader.

Counted from the configuration's sizes (``scenario.model.kwargs``) and
the cell's scenario (sequence length from the data set's name, adapter
rank). A frozen product needs its forward pass and its input gradient,
no weight gradient; the first layer's mixer projections read the frozen
embedding and need no input gradient either. An adapter pair needs its
forward pass and both its gradients (three times its forward).
Attention is its causal half, the delta rule its recurrence (``k^T S``,
the rank-one update, ``q^T S``: 6 K V a head a position; the chunk-wise
form's extra products are not required work), both with a backward pass
of twice the forward. The expert layer is the EXPECTED held pairs a
token (``top_k * held / experts`` = 1 in the cell). Recomputation
(remat, the expert layer's recomputed backward) is not required work
and is not counted; nor are norms, gates, softmax or the loss."""


def sizes(config, scenario):
    z = dict(scenario["model"]["kwargs"])
    z["T"] = int(scenario["data"]["dataset"].split("-")[2])
    z["rank"] = scenario["lora"]["rank"]
    z["kinds"] = [("mla" if (i + 1) % z["layer_group"] == 0 else "kda",
                   i >= z["first_dense"])
                  for i in range(z["first_layer"], z["first_layer"] + z["layers"])]
    z["pairs"] = z["top_k"] * z["experts_held"] / z["n_experts"]
    return z


def per_token(z):
    """Forward FLOPs a token: (frozen products, adapters, the mixers'
    own products over positions, the first layer's input projections)."""
    d, H, K, r, T = z["hidden"], z["heads"], z["head_dim"], z["rank"], z["T"]
    N, R, Dv, C = z["nope"], z["rope"], z["v_dim"], z["kv_rank"]
    frozen = adapters = own = first_in = 0
    for n, (mixer, experts) in enumerate(z["kinds"]):
        if mixer == "kda":
            into = 2 * d * (4 * H * K + 2 * H)  # q, k, v, decay; beta, gate
            frozen += into + 2 * H * K * d
            adapters += 4 * 2 * r * (d + H * K)
            own += 6 * K * K * H + 2 * z["conv"] * 3 * H * K
        else:
            into = 2 * d * (H * (N + R) + C + R + H)  # q, kv-down, gate
            frozen += into + 2 * C * H * (N + Dv) + 2 * H * Dv * d
            adapters += 2 * r * ((d + H * (N + R)) + (d + C + R)
                                 + (C + H * (N + Dv)) + (H * Dv + d))
            own += 2 * H * (N + R + Dv) * (T + 1) // 2  # the causal half
        if n == 0:
            first_in = into
        if experts:
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
    memory if nothing in between is kept there: ``moe.experts`` reads
    every held expert's weights once a pass of the federation's step
    (given a token: over the step's tokens) and reads and writes a row
    a pair; ``kda.scan`` reads q, k, v (2 bytes), the decay (4 bytes)
    and beta and writes its output, a position a head."""
    z = sizes(config, scenario)
    d, H, K, W = z["hidden"], z["heads"], z["head_dim"], z["expert_width"]
    n_moe = sum(e for _, e in z["kinds"])
    n_kda = sum(m == "kda" for m, _ in z["kinds"])
    step_tokens = scenario["n_nodes"] * scenario["data"]["batch_size"] * z["T"]
    weights = z["experts_held"] * 3 * d * W * 2 / step_tokens
    moe = (n_moe * z["pairs"] * 6 * d * W,
           n_moe * (weights + z["pairs"] * 2 * d * 2))
    kda = (n_kda * 6 * K * K * H,
           n_kda * H * (3 * K * 2 + K * 4 + 4 + K * 2))
    twice = lambda w: (2 * w[0], 2 * w[1])
    thrice = lambda w: (3 * w[0], 3 * w[1])
    return {"moe.experts": {"forward": moe, "train": twice(moe)},
            "kda.scan": {"forward": kda, "train": thrice(kda)}}
