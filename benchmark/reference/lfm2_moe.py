"""Plain reference of LFM2-8B-A1B (``lfm2_moe``) as a frozen base under
rank-r adapters: float32, ``highest`` matmul precision, straight
``jax.numpy``; the convolution as three shifted products, attention a
block of queries at a time against whole keys with the mask written
out, the experts as a loop over all of them. It imports nothing of the
program and is written from the model's public ``config.json`` (sizes,
``layer_types``, ``conv_L_cache``, ``norm_eps``, ``rope_theta``, the
router's keys) and from the operator, attention, block and tying of the
family's dense sibling (``Lfm2ShortConv.slow_forward``, ``Lfm2Attention``
and ``Lfm2DecoderLayer`` of ``transformers`` 4.57.6).

Layers. ``h = x + Op(RMSNorm(x)); y = h + FFN(RMSNorm(h))``
(``operator_norm``, ``ffn_norm``), RMSNorm ``w x / sqrt(mean(x^2) +
1e-5)``, no biases, one RMSNorm after the last layer, logits ``h E^T``
over the embedding matrix ``E`` (tied).

- Convolution layer (``layer_types[l] == "conv"``): ``[B | C | X] = x
  W_in`` (``W_in`` [d, 3d], the thirds in this order); ``u = B * X``;
  ``c_t = sum_(i < 3) w_i u_(t - 2 + i)`` (depthwise over the ``d``
  channels, 3 taps ``w`` [3, d], zeros before position 0, no bias, no
  activation); ``out = (C * c) W_out``.
- Attention layer (``full_attention``): ``q = x W_q`` [T, 32, 64], ``k =
  x W_k``, ``v = x W_v`` [T, 8, 64]; an RMSNorm over each query and each
  key head with its own learned scale [64]; rotary embedding over the
  whole head, half-split pairs ``(x_i, x_(i + 32))``, ``inv_freq_i =
  1e6^(-2i/64)``; query head ``h`` reads key/value head ``h // 4``;
  scores ``q k^T / 8``, keys ``j <= i``, softmax; ``out = concat(o)
  W_o``. No gate, no window.
- Dense FFN (the leading layers): SwiGLU ``(silu(x W_1) * (x W_3)) W_2``
  (``W_1`` and ``W_3`` side by side in one ``gate_up`` matrix).
- Expert FFN: ``s = sigmoid(x W_r)`` over all 32; the choice is the 4
  largest of ``s + b`` (``b`` [32] for the choice only); weights ``s_i /
  sum_chosen s_j`` times ``routed_scaling_factor`` 1; each expert a
  SwiGLU; no shared expert.

Assumed, where the config has no key or ``lfm2_moe``'s own modelling
file is not at hand (the configuration file lists the same under
``assumed``):

- the head is tied to the embedding (the config does not carry
  ``tie_word_embeddings``; the sibling's default is true, and the
  published 8.3B parameters are the count with the embedding once);
- the router's scores are sigmoid (the config names no scoring function;
  a bias added for the choice only is the balancing scheme of sigmoid
  routers); the normaliser is the plain sum of the chosen scores, with
  no epsilon added to it;
- ``intermediate_size`` is the dense FFN's width as given (the dense
  sibling's ``block_auto_adjust_ff_dim`` keys are absent: off).

Departures of this file from that description:

- ``held_experts`` gathers, for each expert, the rows that chose it
  where they are at most ``ROWS_CAP`` times its even share, and takes
  every row through it (weight 0 where not chosen) where they are more:
  the same sum either way, no row dropped. An expert layer may hold a
  share of the experts (``experts_held`` from ``expert_offset``); the
  cell holds all 32, which is the whole layer;
- attention is computed ``QUERY_BLOCK`` queries at a time (memory), each
  block against all keys with the mask written out.

The harness tells a reference module nothing of the run, so the sizes
are read here from the configuration's own file: its
``scenario.model.kwargs`` where a TPU is attached and its
``rehearse.scenario.model.kwargs`` elsewhere (``PERF.md``, Open
questions). Tests call :func:`configure` with sizes of their own.
"""

import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
#: most rows an expert gathers, in even shares of the rows of one call
ROWS_CAP = 4
#: queries of one attention block
QUERY_BLOCK = 256
CONV = "conv"

SIZES: dict = {}
SHAPES: dict = {}
FROZEN_SHAPES: dict = {}


def configure(kwargs, lora):
    """Set the module's sizes from the model's keyword arguments (the
    program's ``model.kwargs``) and the scenario's ``lora`` keys."""
    z = dict(kwargs)
    z["rank"], z["alpha"] = lora["rank"], lora.get("alpha") or lora["rank"]
    d, H, G, D = z["hidden"], z["heads"], z["kv_heads"], z["head_dim"]
    # no head: the embedding serves twice
    frozen = {"embed": (z["vocab"], d), "final_norm": (d,)}
    sites = {}
    for i, kind in enumerate(z["layer_types"]):
        L = f"L{i}."
        frozen[L + "operator_norm"] = frozen[L + "ffn_norm"] = (d,)
        if kind == CONV:
            sites.update({L + "conv_in": (d, 3 * d), L + "conv_out": (d, d)})
            frozen[L + "conv_taps"] = (z["taps"], d)
        else:
            sites.update({L + "attn_q": (d, H * D), L + "attn_k": (d, G * D),
                          L + "attn_v": (d, G * D), L + "attn_o": (H * D, d)})
            frozen[L + "q_norm"] = frozen[L + "k_norm"] = (D,)
        if i >= z["dense_layers"]:
            E, W = z["experts_held"], z["expert_width"]
            frozen.update({
                L + "router": (d, z["n_experts"]),
                L + "router_bias": (z["n_experts"],),
                L + "experts_gate_up": (E, d, 2 * W),
                L + "experts_down": (E, W, d)})
        else:
            frozen.update({L + "ffn_gate_up": (d, 2 * z["dense_width"]),
                           L + "ffn_down": (z["dense_width"], d)})
    frozen.update(sites)
    trained = {}
    for name, (d_in, d_out) in sites.items():
        trained[name + ".A"] = (d_in, z["rank"])
        trained[name + ".B"] = (z["rank"], d_out)
    SIZES.clear(), SHAPES.clear(), FROZEN_SHAPES.clear()
    SIZES.update(z), SHAPES.update(trained), FROZEN_SHAPES.update(frozen)


def _configure_from_file():
    cfg = json.loads((pathlib.Path(__file__).resolve().parents[1]
                      / "configs" / "lfm2-8b-a1b.json").read_text())
    scen = cfg["scenario"]
    kwargs = dict(scen["model"]["kwargs"])
    if jax.default_backend() != "tpu":
        kwargs.update(cfg["rehearse"]["scenario"]["model"]["kwargs"])
    configure(kwargs, scen["lora"])


def init(key):
    """The trained leaves: both factors of every adapter seeded non-zero,
    so that both have a gradient at the first step."""
    out = {}
    for i, (name, shape) in enumerate(sorted(SHAPES.items())):
        fan_in = shape[0] if name.endswith(".A") else 4 * shape[0]
        out[name] = jax.random.normal(
            jax.random.fold_in(key, i), shape, F32) / math.sqrt(fan_in)
    return out


# --------------------------------------------------------------------------
# pieces


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def swiglu(gate_up):
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return jax.nn.silu(gate) * up


def make_dense(p, w, q):
    """``dense(name, x) = x W + (alpha / rank) (x A) B`` where the leaf
    has an adapter, ``x W`` elsewhere; ``q`` on every operand."""
    s = SIZES["alpha"] / SIZES["rank"]

    def dense(name, x):
        y = jnp.dot(q(x), q(w[name].astype(F32)), precision=HI)
        if name + ".A" in p:
            xa = jnp.dot(q(x), q(p[name + ".A"]), precision=HI)
            y = y + s * jnp.dot(q(xa), q(p[name + ".B"]), precision=HI)
        return y

    return dense


def short_conv(dense, L, taps, x):
    """The doubly gated short convolution on ``x`` [B, T, d]: the
    convolution is one product a tap, each with ``u`` shifted towards
    later positions and zeros in front."""
    b, c, xx = jnp.split(dense(L + "conv_in", x), 3, axis=-1)
    u = b * xx
    T, k = x.shape[1], taps.shape[0]
    later = lambda n: jnp.pad(u, ((0, 0), (n, 0), (0, 0)))[:, :T]
    conv = sum(taps[i].astype(F32) * later(k - 1 - i) for i in range(k))
    return dense(L + "conv_out", c * conv)


def rotary(x):
    """Half-split rotary embedding over the whole head of ``x`` [B, T, H,
    D], positions ``0 .. T - 1``; frequencies in float64, as a config's
    loader computes them once."""
    D = x.shape[-1]
    freq = SIZES["theta"] ** (-2.0 * np.arange(D // 2, dtype=np.float64) / D)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] \
        * jnp.asarray(freq, F32)[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def masked_attention(qh, kh, vh, q):
    """Softmax attention of ``qh`` [B, T, H, D] over ``kh``, ``vh`` [B, T,
    G, D], query head ``h`` reading key head ``h // (H / G)``, key ``j``
    allowed for query ``i`` where ``j <= i``. A block of queries at a
    time against all keys."""
    B, T, H, D = qh.shape
    G = kh.shape[2]
    blk = max(b for b in range(1, min(QUERY_BLOCK, T) + 1) if T % b == 0)
    grouped = qh.reshape(B, T // blk, blk, G, H // G, D)

    @jax.checkpoint
    def block(args):
        qb, start = args  # [B, blk, G, H / G, D], first position
        i = (start + jnp.arange(blk))[:, None]
        allowed = jnp.arange(T)[None, :] <= i
        s = jnp.einsum("bqgpd,bkgd->bgpqk", q(qb), q(kh),
                       precision=HI) * D ** -0.5
        pr = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
        return jnp.einsum("bgpqk,bkgd->bqgpd", q(pr), q(vh), precision=HI)

    o = jax.lax.map(block, (jnp.moveaxis(grouped, 1, 0),
                            jnp.arange(T // blk) * blk))
    return jnp.moveaxis(o, 0, 1).reshape(B, T, H, D)


def attention(dense, L, w, x, q):
    z = SIZES
    B, T, _ = x.shape
    H, G, D = z["heads"], z["kv_heads"], z["head_dim"]
    qh = dense(L + "attn_q", x).reshape(B, T, H, D)
    kh = dense(L + "attn_k", x).reshape(B, T, G, D)
    vh = dense(L + "attn_v", x).reshape(B, T, G, D)
    qh = rotary(rms_norm(qh, w[L + "q_norm"], z["eps"]))
    kh = rotary(rms_norm(kh, w[L + "k_norm"], z["eps"]))
    return dense(L + "attn_o",
                 masked_attention(qh, kh, vh, q).reshape(B, T, H * D))


def route(x, router, bias, q):
    """Sigmoid scores over all experts, the ``top_k`` largest of score
    plus bias, ``w = scale s / sum_chosen s``. Returns the weights as a
    dense [rows, experts] matrix, 0 where an expert is not chosen."""
    z = SIZES
    s = jax.nn.sigmoid(jnp.dot(q(x), q(router.astype(F32)), precision=HI))
    n, e = s.shape
    _, idx = jax.lax.top_k(s + bias.astype(F32), z["top_k"])
    chosen = jnp.zeros((n, e), bool).at[jnp.arange(n)[:, None], idx].set(True)
    w = jnp.where(chosen, s, 0.0)
    return z["route_scale"] * w / jnp.sum(w, axis=1, keepdims=True)


def held_experts(x, w_all, gate_up, down, q, offset):
    """``sum over the held experts chosen of w_i E_i(x)`` for rows ``x``
    [n, d]; ``E(x) = W_d (SiLU(W_g x) * W_u x)``. An expert at a time,
    over the rows that chose it."""
    n, d = x.shape
    held = gate_up.shape[0]
    w_held = w_all[:, offset:offset + held]
    share = -(-n * SIZES["top_k"] // w_all.shape[1])
    cap = min(n, ROWS_CAP * share)
    xz = jnp.concatenate([x, jnp.zeros((1, d), F32)])

    def through(rows, gu, dn):
        h = swiglu(jnp.dot(q(rows), q(gu.astype(F32)), precision=HI))
        return jnp.dot(q(h), q(dn.astype(F32)), precision=HI)

    def chosen_rows(w_e, gu, dn):
        rows = jnp.nonzero(w_e > 0, size=cap, fill_value=n)[0]
        we = jnp.concatenate([w_e, jnp.zeros((1,), F32)])[rows]
        return jnp.zeros((n + 1, d), F32).at[rows].add(
            through(xz[rows], gu, dn) * we[:, None])[:n]

    def every_row(w_e, gu, dn):
        return through(x, gu, dn) * w_e[:, None]

    @jax.checkpoint  # on the way back an expert at a time again
    def one(args):
        return jax.lax.cond(jnp.sum(args[0] > 0) > cap, every_row,
                            chosen_rows, *args)

    def expert(acc, args):
        return acc + one(args), None

    return jax.lax.scan(expert, jnp.zeros((n, d), F32),
                        (w_held.T, gate_up, down))[0]


def expert_ffn(w, L, x, q):
    B, T, d = x.shape
    rows = x.reshape(B * T, d)
    w_all = route(rows, w[L + "router"], w[L + "router_bias"], q)
    return held_experts(rows, w_all, w[L + "experts_gate_up"],
                        w[L + "experts_down"], q,
                        SIZES["expert_offset"]).reshape(B, T, d)


def layer(p, w, q, i, h):
    """Layer ``i`` of the kept stack on ``h`` [B, T, d]."""
    z = SIZES
    L = f"L{i}."
    dense = make_dense(p, w, q)
    hn = rms_norm(h, w[L + "operator_norm"], z["eps"])
    if z["layer_types"][i] == CONV:
        h = h + short_conv(dense, L, w[L + "conv_taps"], hn)
    else:
        h = h + attention(dense, L, w, hn, q)
    hn = rms_norm(h, w[L + "ffn_norm"], z["eps"])
    if i >= z["dense_layers"]:
        return h + expert_ffn(w, L, hn, q)
    return h + dense(L + "ffn_down", swiglu(dense(L + "ffn_gate_up", hn)))


def forward(p, x, q=lambda a: a, frozen=None):
    """Token ids [B, T] -> logits [B, T, vocab], float32."""
    z, w = SIZES, frozen
    embed = w["embed"].astype(F32)
    h = embed[x.astype(jnp.int32)]
    for i in range(len(z["layer_types"])):
        # one layer's activations at a time
        h = jax.checkpoint(lambda h, p, i=i: layer(p, w, q, i, h))(h, p)
    h = rms_norm(h, w["final_norm"], z["eps"])
    return jnp.dot(q(h), q(embed).T, precision=HI)


def loss(logits, y, mask):
    """Mean over the kept rows of each row's mean cross-entropy over its
    positions; ``y`` holds a label a position."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, y[..., None].astype(jnp.int32),
                             axis=-1)[..., 0]
    per_row = jnp.mean(lse - ll, axis=-1)
    m = mask.astype(F32)
    return jnp.sum(per_row * m) / jnp.maximum(jnp.sum(m), 1.0)


_configure_from_file()
