"""Plain reference of Qwen3-Next-80B-A3B (``qwen3_next``) as a frozen
base under rank-r adapters: float32, ``highest`` matmul precision,
straight ``jax.numpy``; the Gated DeltaNet state as the recurrence it is
defined by (a ``lax.scan`` over positions, exact for any decay), gated
attention a block of queries at a time against the keys before them, the
experts as a loop over the ones held. It imports nothing of the program
and is written from the model's public ``config.json`` (sizes,
``full_attention_interval``, ``partial_rotary_factor``, ``norm_topk_prob``,
the ``linear_*`` keys) and the Qwen3-Next and Gated DeltaNet descriptions.

Layers. ``x += Mixer(N0(x)); x += MoE(N0(x))`` with the zero-centred
RMSNorm ``N0(x) = x / sqrt(mean(x^2) + 1e-6) * (1 + w)``, no biases, one
``N0`` after the last layer, an untied head. Published layer ``i``
(0-indexed) has gated attention where ``(i + 1) % 4 == 0`` and Gated
DeltaNet otherwise; every layer has the expert FFN.

- Gated DeltaNet: ``[q | k | v | z] = x W_qkvz`` (q, k 16 heads of 128,
  v, z 32 heads of 128), ``[b | a] = x W_ba`` (32 each); ``[q, k, v] =
  SiLU(conv4([q, k, v]))``, one causal depthwise convolution of 4 taps
  over the 8192 channels of the three, no bias; q and k L2-normalised a
  head (1e-6 under the root), q scaled by ``128^-0.5``; ``beta =
  sigmoid(b)``, ``g = -exp(A_log_h) softplus(a + dt_bias_h)`` a value
  head and position; value head ``h`` reads key head ``h // 2``; ``S_t =
  e^(g_t) (I - b_t k_t k_t^T) S_(t-1) + b_t k_t v_t^T``, ``o_t = S_t^T
  q_t``; output ``(RMSNorm_128(o) * w_n * SiLU(z)) W_out``, ``w_n`` one
  plain weight of 128 shared by the heads.
- Gated attention: ``x W_q`` [T, 16, 256 + 256], each head's query then
  its gate; ``k = x W_k``, ``v = x W_v`` [T, 2, 256]; ``N0`` of 256 on
  each query and key head; the rotary embedding over the first 64 of the
  256 dimensions, pairs ``(x_i, x_(i + 32))``, base 1e7; ``softmax(q k^T /
  16 + causal) v`` with query head ``h`` reading kv head ``h // 8``;
  ``(o * sigmoid(gate)) W_o``.
- Experts: ``p = softmax(x W_r)`` over all 512; the 10 largest, weights
  ``p_i / sum_chosen p_j``; each expert a SwiGLU of 512; plus the shared
  SwiGLU expert of 512 times ``sigmoid(x w_sg)``.

Assumed (the configuration file lists the same under ``assumed``): the
columns of ``W_qkvz`` and ``W_ba`` in the order written above (the
published checkpoint groups them by key head: a permutation of the same
columns, which seeded weights cannot tell apart); ``A_log = log U(0,
16)`` and ``dt_bias = 1`` (the published layer's initialisation); the
zero-centred norms' weights seeded ``N(0, 0.1)`` and not 0; the
multi-token-prediction layer left out (``described_as`` "MTP 1": no key
of the config, not on the fine-tuning path).

Departures of this file from that description:

- the expert layer computes the part of the experts HELD HERE
  (``experts_held`` from ``expert_offset``, a deployment that spreads
  each layer over chips): it routes over all experts and leaves out what
  the absent ones would add; ``held_experts`` gathers, for each held
  expert, the rows that chose it into the smallest of a ladder of static
  sizes that holds them (its even share of the rows times a power of
  ``LADDER``, every row the last): the same sum as the dense sum over
  held experts with the unchosen weights 0, whatever the size;
- ``gdn_recurrence`` nests the scan (blocks of 64 positions under
  ``jax.checkpoint``) so that its backward pass keeps T/64 states and
  not T: the same recurrence, recomputed; every layer is recomputed on
  the way back; a position's two products are one read of the state
  before the step, ``k^T (a S) = (a k)^T S`` and ``S_t^T q = (a q)^T S +
  (q . k) u``, ``a = e^(g_t)`` (time: the same sums, the state read
  once);
- attention is computed ``QUERY_BLOCK`` queries at a time (memory), each
  block against every key with the mask written out; keys and values are
  repeated to the query heads.

The harness tells a reference module nothing of the run, so the sizes
are read here from the configuration's own file: its
``scenario.model.kwargs`` where a TPU is attached and its
``rehearse.scenario.model.kwargs`` elsewhere. Tests call
:func:`configure` with sizes of their own.
"""

import functools
import json
import math
import pathlib

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
#: queries of one attention block
QUERY_BLOCK = 512
#: positions of the recurrence a step of its loop
UNROLL = 4
#: the ratio of one size of an expert's gathered rows to the next: each
#: size is one more branch of the expert loop's ``switch`` to compile
#: (at the cell's sizes about 9 s of XLA:TPU's time a branch and a layer),
#: and a larger ratio gathers more unchosen rows
LADDER = 16

SIZES: dict = {}
SHAPES: dict = {}
FROZEN_SHAPES: dict = {}


def configure(kwargs, lora):
    """Set the module's sizes from the model's keyword arguments (the
    program's ``model.kwargs``) and the scenario's ``lora`` keys."""
    z = dict(kwargs)
    z["rank"], z["alpha"] = lora["rank"], lora.get("alpha") or lora["rank"]
    d, Hk, H, K = (z["hidden"], z["key_heads"], z["value_heads"],
                   z["linear_head_dim"])
    A, G, D = z["heads"], z["kv_heads"], z["head_dim"]
    first = z.get("first_layer", 0)
    z["kinds"] = [(i, "attn" if (i + 1) % z["full_attention_interval"] == 0
                   else "gdn") for i in range(first, first + z["layers"])]
    frozen = {"embed": (z["vocab"], d), "head": (d, z["vocab"]),
              "final_norm": (d,)}
    sites = {}
    for i, mixer in z["kinds"]:
        L = f"L{i}."
        frozen[L + "input_norm"] = frozen[L + "post_attention_norm"] = (d,)
        if mixer == "gdn":
            sites[L + "in_proj_qkvz"] = (d, 2 * (Hk + H) * K)
            sites[L + "out_proj"] = (H * K, d)
            frozen.update({L + "in_proj_ba": (d, 2 * H),
                           L + "conv1d": (z["conv"], 2 * Hk * K + H * K),
                           L + "A_log": (H,), L + "dt_bias": (H,),
                           L + "norm": (K,)})
        else:
            sites.update({L + "q_proj": (d, 2 * A * D), L + "k_proj": (d, G * D),
                          L + "v_proj": (d, G * D), L + "o_proj": (A * D, d)})
            frozen[L + "q_norm"] = frozen[L + "k_norm"] = (D,)
        E, W, S = z["experts_held"], z["expert_width"], z["shared_width"]
        frozen.update({
            L + "router": (d, z["n_experts"]),
            L + "experts_gate_up": (E, d, 2 * W),
            L + "experts_down": (E, W, d),
            L + "shared_gate_up": (d, 2 * S), L + "shared_down": (S, d),
            L + "shared_gate": (d, 1)})
    frozen.update(sites)
    trained = {}
    for name, (d_in, d_out) in sites.items():
        trained[name + ".A"] = (d_in, z["rank"])
        trained[name + ".B"] = (z["rank"], d_out)
    SIZES.clear(), SHAPES.clear(), FROZEN_SHAPES.clear()
    SIZES.update(z), SHAPES.update(trained), FROZEN_SHAPES.update(frozen)


def _configure_from_file():
    cfg = json.loads((pathlib.Path(__file__).resolve().parents[1]
                      / "configs" / "qwen3-next-80b-a3b.json").read_text())
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


def n0(x, w):
    """The zero-centred RMSNorm: ``x / rms(x) * (1 + w)``."""
    return rms_norm(x, 1.0 + w.astype(F32), SIZES["eps"])


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


def gdn_recurrence(q, k, v, g, beta, block=64):
    """``S_t = e^(g_t) (I - b_t k_t k_t^T) S_(t-1) + b_t k_t v_t^T``, ``o_t
    = S_t^T q_t``, a position at a time. ``q, k`` [B, T, H, K] (each value
    head's own key head), ``v`` [B, T, H, V], ``g``, ``beta`` [B, T, H]."""
    B, T, H, K = q.shape
    pad = -T % block
    if pad:  # k = 0, b = 0, no decay: the state passes
        longer = lambda a: jnp.pad(
            a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        q, k, v, g, beta = map(longer, (q, k, v, g, beta))

    def position(S, xs):
        # with a = e^g: k^T (a S) = (a k)^T S, and S_t^T q = (a q)^T S +
        # (q . k) u: both products read the state before the step, once
        q_t, k_t, v_t, g_t, b_t = xs
        a = jnp.exp(g_t)[..., None]  # [B, H, 1]
        kS, qS = jnp.moveaxis(jnp.einsum(
            "bhnk,bhkv->bhnv", jnp.stack([a * k_t, a * q_t], axis=2), S,
            precision=HI), 2, 0)
        u = b_t[..., None] * (v_t - kS)
        o = qS + jnp.sum(q_t * k_t, axis=-1, keepdims=True) * u
        return a[..., None] * S + k_t[..., None] * u[..., None, :], o

    @jax.checkpoint
    def span(S, xs):  # UNROLL positions a step of the loop: the same sums
        return jax.lax.scan(position, S, xs, unroll=UNROLL)

    by_block = lambda a: jnp.moveaxis(a, 1, 0).reshape(
        (-1, block) + a.shape[:1] + a.shape[2:])
    _, o = jax.lax.scan(span, jnp.zeros((B, H, K, v.shape[-1]), F32),
                        tuple(map(by_block, (q, k, v, g, beta))))
    o = o.reshape((-1,) + o.shape[2:])  # [T, B, H, V]
    return jnp.moveaxis(o, 0, 1)[:, :T]


def gdn_inputs(dense, w, L, x):
    """What the recurrence takes, and the norm's gate ``z``: ``q, k``
    after the convolution and SiLU, of unit length a head and repeated to
    the value heads, ``v``, the log-decay and beta a value head."""
    z = SIZES
    B, T, _ = x.shape
    Hk, H, K = z["key_heads"], z["value_heads"], z["linear_head_dim"]
    qkvz = dense(L + "in_proj_qkvz", x)
    qkv, gate = qkvz[..., :2 * Hk * K + H * K], qkvz[..., 2 * Hk * K + H * K:]
    taps = w[L + "conv1d"].astype(F32)
    n = taps.shape[0]
    yp = jnp.pad(qkv, ((0, 0), (n - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(yp[:, i:i + T] * taps[i] for i in range(n)))
    qq = qkv[..., :Hk * K].reshape(B, T, Hk, K)
    kk = qkv[..., Hk * K:2 * Hk * K].reshape(B, T, Hk, K)
    vv = qkv[..., 2 * Hk * K:].reshape(B, T, H, K)
    unit = lambda a: a * jax.lax.rsqrt(
        jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    to_values = lambda a: jnp.repeat(a, H // Hk, axis=2)
    qq, kk = to_values(unit(qq) * K ** -0.5), to_values(unit(kk))
    ba = dense(L + "in_proj_ba", x)
    b, a = ba[..., :H], ba[..., H:]
    g = -jnp.exp(w[L + "A_log"].astype(F32)) * jax.nn.softplus(
        a + w[L + "dt_bias"].astype(F32))
    return qq, kk, vv, g, jax.nn.sigmoid(b), gate.reshape(B, T, H, K)


def gdn_output(dense, w, L, o, gate):
    B, T, H, K = o.shape
    o = rms_norm(o, w[L + "norm"], SIZES["eps"]) * jax.nn.silu(gate)
    return dense(L + "out_proj", o.reshape(B, T, H * K))


def rope(x):
    """The rotary embedding over the first ``partial_rotary`` of each
    head of ``x`` [B, T, H, D], pairs ``(x_i, x_(i + R/2))``, positions
    ``0 .. T - 1``; the rest passes."""
    z = SIZES
    R = int(z["head_dim"] * z["partial_rotary"])
    inv = z["theta"] ** (-jnp.arange(0, R, 2, dtype=F32) / R)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :R // 2], x[..., R // 2:R]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., R:]], axis=-1)


def masked_attention(qh, kh, vh, scale, q):
    """Softmax attention of ``qh`` [B, T, H, D] over ``kh`` [B, T, H, D],
    ``vh`` [B, T, H, Dv], key ``j`` allowed for query ``i`` where ``j <=
    i``. A block of queries at a time, each against every key with the
    mask written out: one program for every block (blocks against key
    prefixes of their own would be one program a prefix, at the cell's
    sizes a quarter of the reference's compile)."""
    B, T, H, D = qh.shape
    blk = max(b for b in range(1, min(QUERY_BLOCK, T) + 1) if T % b == 0)
    kq, vq = q(kh), q(vh)

    @jax.checkpoint
    def block(args):
        qb, start = args  # [B, blk, H, D], first position
        allowed = jnp.arange(T)[None, :] <= (start + jnp.arange(blk))[:, None]
        s = jnp.einsum("bqhd,bkhd->bhqk", q(qb), kq, precision=HI) * scale
        pr = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", q(pr), vq, precision=HI)

    o = jax.lax.map(block, (jnp.moveaxis(qh.reshape(B, T // blk, blk, H, D),
                                         1, 0), jnp.arange(T // blk) * blk))
    return jnp.moveaxis(o, 0, 1).reshape(B, T, H, vh.shape[-1])


def gated_attention(dense, w, L, x, q):
    z = SIZES
    B, T, _ = x.shape
    A, G, D = z["heads"], z["kv_heads"], z["head_dim"]
    qg = dense(L + "q_proj", x).reshape(B, T, A, 2 * D)
    qh, gate = qg[..., :D], qg[..., D:]
    kh = dense(L + "k_proj", x).reshape(B, T, G, D)
    vh = dense(L + "v_proj", x).reshape(B, T, G, D)
    qh, kh = rope(n0(qh, w[L + "q_norm"])), rope(n0(kh, w[L + "k_norm"]))
    to_queries = lambda a: jnp.repeat(a, A // G, axis=2)
    o = masked_attention(qh, to_queries(kh), to_queries(vh), D ** -0.5, q)
    o = o * jax.nn.sigmoid(gate)
    return dense(L + "o_proj", o.reshape(B, T, A * D))


def route(x, router, q):
    """Softmax over all experts, the ``top_k`` largest, ``w = p /
    sum_chosen p``. Returns the weights as a dense [rows, experts]
    matrix, 0 where an expert is not chosen."""
    z = SIZES
    p = jax.nn.softmax(jnp.dot(q(x), q(router.astype(F32)), precision=HI),
                       axis=-1)
    n, e = p.shape
    _, idx = jax.lax.top_k(p, z["top_k"])
    chosen = jnp.zeros((n, e), bool).at[jnp.arange(n)[:, None], idx].set(True)
    w = jnp.where(chosen, p, 0.0)
    return w / jnp.sum(w, axis=1, keepdims=True)


def held_experts(x, w_all, gate_up, down, q, offset):
    """``sum over the held experts chosen of w_i E_i(x)`` for rows ``x``
    [n, d]; ``E(x) = W_d (SiLU(W_g x) * W_u x)``. An expert at a time,
    over the rows that chose it, gathered into the smallest of the sizes
    its even share times 1, ``LADDER``, ``LADDER^2``, .. (every row the
    last) that holds them."""
    n, d = x.shape
    held = gate_up.shape[0]
    w_held = w_all[:, offset:offset + held]
    share = -(-n * SIZES["top_k"] // w_all.shape[1])
    caps = [share * LADDER ** i for i in range(n.bit_length())
            if share * LADDER ** i < n] + [n]
    xz = jnp.concatenate([x, jnp.zeros((1, d), F32)])

    def through(rows, gu, dn):
        h = swiglu(jnp.dot(q(rows), q(gu.astype(F32)), precision=HI))
        return jnp.dot(q(h), q(dn.astype(F32)), precision=HI)

    def chosen_rows(cap, w_e, gu, dn):
        rows = jnp.nonzero(w_e > 0, size=cap, fill_value=n)[0]
        we = jnp.concatenate([w_e, jnp.zeros((1,), F32)])[rows]
        return jnp.zeros((n + 1, d), F32).at[rows].add(
            through(xz[rows], gu, dn) * we[:, None])[:n]

    @jax.checkpoint  # on the way back an expert at a time again
    def one(args):
        short = jnp.sum(jnp.sum(args[0] > 0) > jnp.asarray(caps[:-1]))
        return jax.lax.switch(short, [functools.partial(chosen_rows, cap)
                                      for cap in caps], *args)

    def expert(acc, args):
        return acc + one(args), None

    return jax.lax.scan(expert, jnp.zeros((n, d), F32),
                        (w_held.T, gate_up, down))[0]


def expert_ffn(dense, w, L, x, q):
    B, T, d = x.shape
    rows = x.reshape(B * T, d)
    w_all = route(rows, w[L + "router"], q)
    y = held_experts(rows, w_all, w[L + "experts_gate_up"],
                     w[L + "experts_down"], q, SIZES["expert_offset"])
    shared = dense(L + "shared_down", swiglu(dense(L + "shared_gate_up", x)))
    return y.reshape(B, T, d) + jax.nn.sigmoid(
        dense(L + "shared_gate", x)) * shared


def forward(p, x, q=lambda a: a, frozen=None):
    """Token ids [B, T] -> logits [B, T, vocab], float32."""
    z, w = SIZES, frozen

    def make_layer(L, mixer):
        def ffn(h, dense):
            return h + expert_ffn(dense, w, L,
                                  n0(h, w[L + "post_attention_norm"]), q)

        def attn_layer(h, p):
            dense = make_dense(p, w, q)
            hn = n0(h, w[L + "input_norm"])
            return ffn(h + gated_attention(dense, w, L, hn, q), dense)

        @jax.checkpoint  # on the way back what the recurrence takes is
        # formed anew, and only it is kept across the recurrence's own
        def inputs_of(hn, p):
            return gdn_inputs(make_dense(p, w, q), w, L, hn)

        def gdn_layer(h, p):
            dense = make_dense(p, w, q)
            hn = n0(h, w[L + "input_norm"])
            *inputs, gate = inputs_of(hn, p)
            o = gdn_recurrence(*inputs)
            return ffn(h + gdn_output(dense, w, L, o, gate), dense)

        # one layer's activations at a time
        return jax.checkpoint(attn_layer if mixer == "attn" else gdn_layer)

    h = w["embed"].astype(F32)[x.astype(jnp.int32)]
    for i, mixer in z["kinds"]:
        h = make_layer(f"L{i}.", mixer)(h, p)
    h = n0(h, w["final_norm"])
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
