"""Plain reference of Laguna-S-2.1 (``laguna``) as a frozen base under
rank-r adapters: float32, ``highest`` matmul precision, straight
``jax.numpy``; attention a block of queries at a time with its mask
written out, the experts as a loop over the ones held. It imports
nothing of the program and is written from the model's public
``config.json`` (sizes, layer lists, rotary parameters) and, where that
names a mechanism without its equation, from the published description
of the family the key comes from (YaRN, arXiv:2309.00071; the Qwen2-MoE
expert keys).

Layers. ``x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))``, RMSNorm eps
1e-6, no biases, a final RMSNorm, an untied head.

- Attention, layer ``l``: ``H_l = heads[l]`` query heads of 128 over 8
  key/value heads; ``q = x W_q``, ``k = x W_k``, ``v = x W_v``; rotary on
  q and k; query head ``h`` reads key/value head ``h // (H_l / 8)``;
  scores ``q k^T / sqrt(128)``, softmax over the allowed keys: ``j <= i``
  in a ``full_attention`` layer, ``i - window < j <= i`` in a
  ``sliding_attention`` layer; a gate a head ``g = sigmoid(x W_g)``,
  ``o_h <- g_h o_h``; output ``concat(o) W_o``.
- Rotary, half-split pairs ``(x_i, x_(i + R/2))``. Window layers: the
  whole head, ``inv_freq_i = 10000^(-2i/R)``. Full layers: the first
  half of the head (the rest passes through), YaRN: ``extra_i =
  500000^(-2i/R)``, ``inter_i = extra_i / 128``, ``dim(n) = R ln(8192 /
  (2 pi n)) / (2 ln 500000)``, ``low = max(floor(dim(32)), 0)``, ``high =
  min(ceil(dim(1)), R - 1)``, ``ramp_i = clip((i - low) / (high - low),
  0, 1)``, ``inv_freq_i = inter_i ramp_i + extra_i (1 - ramp_i)``, cos
  and sin both times the attention factor 1.4852030263919618.
- Dense FFN (layer 0): SwiGLU. Expert FFN: ``p = softmax(x W_r)`` over
  all 256, the 10 largest, weights ``2.5 p_i / sum_chosen p_j``, each
  expert a SwiGLU, plus one shared SwiGLU expert added unweighted.

Assumed, where the config has no key (the configuration file lists the
same under ``assumed``):

- the gate reads the layer's normed input and multiplies before ``W_o``;
- no q/k norm;
- the rotary pairs are half-split (the Hugging Face convention);
- the router is softmax, top-k, renormalise, with no selection bias and
  no gate on the shared expert.

Departures of this file from that description:

- the expert layer computes the part of the experts HELD HERE (experts
  ``expert_offset ..`` of a deployment that spreads each layer over
  chips); it routes over all experts and leaves out what the absent ones
  would add;
- ``held_experts`` gathers, for each held expert, the rows that chose
  it where they are at most ``ROWS_CAP`` times its even share, and takes
  every row through it (weight 0 where not chosen) where they are more:
  the same sum either way, no row dropped;
- attention is computed ``QUERY_BLOCK`` queries at a time (memory); a
  full layer's block is set against all keys, a window layer's against
  the span of keys that holds its window (a tenth of the work at 8192);
  the mask is written out over the real positions either way.

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
FULL, WINDOW = "full_attention", "sliding_attention"

SIZES: dict = {}
SHAPES: dict = {}
FROZEN_SHAPES: dict = {}


def configure(kwargs, lora):
    """Set the module's sizes from the model's keyword arguments (the
    program's ``model.kwargs``) and the scenario's ``lora`` keys."""
    z = dict(kwargs)
    z["rank"], z["alpha"] = lora["rank"], lora.get("alpha") or lora["rank"]
    d, G, D = z["hidden"], z["kv_heads"], z["head_dim"]
    frozen = {"embed": (z["vocab"], d), "head": (d, z["vocab"]),
              "final_norm": (d,)}
    sites = {}
    for i, (mlp, H) in enumerate(zip(z["mlp_layer_types"], z["heads"])):
        L = f"L{i}."
        frozen[L + "attn_norm"] = frozen[L + "ffn_norm"] = (d,)
        sites.update({L + "attn_q": (d, H * D), L + "attn_k": (d, G * D),
                      L + "attn_v": (d, G * D), L + "attn_o": (H * D, d)})
        frozen[L + "attn_g"] = (d, H)
        if mlp == "sparse":
            E, W = z["experts_held"], z["expert_width"]
            frozen.update({
                L + "router": (d, z["n_experts"]),
                L + "experts_gate_up": (E, d, 2 * W),
                L + "experts_down": (E, W, d),
                L + "shared_gate_up": (d, 2 * z["shared_width"]),
                L + "shared_down": (z["shared_width"], d)})
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
                      / "configs" / "laguna-s-2.1.json").read_text())
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


def inv_freq(kind):
    """The rotary frequencies of a layer of ``kind`` (float64, as a
    config's loader computes them once) and the factor on its cos and
    sin."""
    z = SIZES
    if kind == WINDOW:
        R = int(z["head_dim"] * z["rotary_window"]) // 2 * 2
        i = np.arange(R // 2, dtype=np.float64)
        return z["theta_window"] ** (-2.0 * i / R), 1.0
    R = int(z["head_dim"] * z["rotary_full"]) // 2 * 2
    theta = z["theta_full"]
    i = np.arange(R // 2, dtype=np.float64)
    extra = theta ** (-2.0 * i / R)
    inter = extra / z["yarn_factor"]
    dim = lambda n: R * math.log(z["yarn_original"] / (2 * math.pi * n)) / (
        2 * math.log(theta))
    low = max(math.floor(dim(z["yarn_beta_fast"])), 0)
    high = min(math.ceil(dim(z["yarn_beta_slow"])), R - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp), z["yarn_attention_factor"]


def rotary(x, kind):
    """Half-split rotary embedding over the first ``R`` channels of each
    head of ``x`` [B, T, H, D], positions ``0 .. T - 1``."""
    freq, factor = inv_freq(kind)
    freq = jnp.asarray(freq, F32)
    half = freq.shape[0]
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * freq[None, :]
    cos = (factor * jnp.cos(ang))[None, :, None, :]
    sin = (factor * jnp.sin(ang))[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def masked_attention(qh, kh, vh, window, q):
    """Softmax attention of ``qh`` [B, T, H, D] over ``kh``, ``vh`` [B, T,
    G, D], query head ``h`` reading key head ``h // (H / G)``; key ``j``
    is allowed for query ``i`` where ``j <= i`` and, with a window, ``j >
    i - window``. A block of queries at a time."""
    B, T, H, D = qh.shape
    G = kh.shape[2]
    blk = max(b for b in range(1, min(QUERY_BLOCK, T) + 1) if T % b == 0)
    # the keys a block is set against: all, or the span that holds a
    # window layer's allowed keys
    span = T if window is None else min(T, blk + window)
    grouped = qh.reshape(B, T // blk, blk, G, H // G, D)

    @jax.checkpoint
    def block(args):
        qb, start = args  # [B, blk, G, H / G, D], first position
        first = jnp.clip(start + blk - span, 0, T - span)
        kb = jax.lax.dynamic_slice_in_dim(kh, first, span, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(vh, first, span, axis=1)
        i = (start + jnp.arange(blk))[:, None]
        j = (first + jnp.arange(span))[None, :]
        allowed = j <= i
        if window is not None:
            allowed = jnp.logical_and(allowed, j > i - window)
        s = jnp.einsum("bqgpd,bkgd->bgpqk", q(qb), q(kb),
                       precision=HI) * D ** -0.5
        pr = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
        return jnp.einsum("bgpqk,bkgd->bqgpd", q(pr), q(vb), precision=HI)

    o = jax.lax.map(block, (jnp.moveaxis(grouped, 1, 0),
                            jnp.arange(T // blk) * blk))
    return jnp.moveaxis(o, 0, 1).reshape(B, T, H, D)


def attention(dense, L, kind, heads, x, q):
    z = SIZES
    B, T, _ = x.shape
    G, D = z["kv_heads"], z["head_dim"]
    qh = rotary(dense(L + "attn_q", x).reshape(B, T, heads, D), kind)
    kh = rotary(dense(L + "attn_k", x).reshape(B, T, G, D), kind)
    vh = dense(L + "attn_v", x).reshape(B, T, G, D)
    o = masked_attention(qh, kh, vh,
                         z["window"] if kind == WINDOW else None, q)
    gate = jax.nn.sigmoid(dense(L + "attn_g", x))
    return dense(L + "attn_o", (o * gate[..., None]).reshape(B, T, heads * D))


def route(x, router, q):
    """Softmax over all experts, the ``top_k`` largest, ``w = scale p /
    sum_chosen p``. Returns the weights as a dense [rows, experts]
    matrix, 0 where an expert is not chosen."""
    z = SIZES
    p = jax.nn.softmax(jnp.dot(q(x), q(router.astype(F32)), precision=HI),
                       axis=-1)
    n, e = p.shape
    _, idx = jax.lax.top_k(p, z["top_k"])
    chosen = jnp.zeros((n, e), bool).at[jnp.arange(n)[:, None], idx].set(True)
    w = jnp.where(chosen, p, 0.0)
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


def expert_ffn(dense, w, L, x, q):
    z = SIZES
    B, T, d = x.shape
    rows = x.reshape(B * T, d)
    w_all = route(rows, w[L + "router"], q)
    y = held_experts(rows, w_all, w[L + "experts_gate_up"],
                     w[L + "experts_down"], q, z["expert_offset"])
    shared = dense(L + "shared_down", swiglu(dense(L + "shared_gate_up", x)))
    return y.reshape(B, T, d) + shared


def layer(p, w, q, i, h):
    """Published layer ``i`` of the kept stack on ``h`` [B, T, d]."""
    z = SIZES
    L = f"L{i}."
    dense = make_dense(p, w, q)
    hn = rms_norm(h, w[L + "attn_norm"], z["eps"])
    h = h + attention(dense, L, z["layer_types"][i], z["heads"][i], hn, q)
    hn = rms_norm(h, w[L + "ffn_norm"], z["eps"])
    if z["mlp_layer_types"][i] == "sparse":
        return h + expert_ffn(dense, w, L, hn, q)
    return h + dense(L + "ffn_down", swiglu(dense(L + "ffn_gate_up", hn)))


def forward(p, x, q=lambda a: a, frozen=None):
    """Token ids [B, T] -> logits [B, T, vocab], float32."""
    z, w = SIZES, frozen
    h = w["embed"].astype(F32)[x.astype(jnp.int32)]
    for i in range(len(z["layer_types"])):
        # one layer's activations at a time
        h = jax.checkpoint(lambda h, p, i=i: layer(p, w, q, i, h))(h, p)
    h = rms_norm(h, w["final_norm"], z["eps"])
    return make_dense(p, w, q)("head", h)


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
