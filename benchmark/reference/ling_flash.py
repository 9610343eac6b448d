"""Plain reference of Ling-3.0-flash (``bailing_hybrid``) as a frozen
base under rank-r adapters: float32, ``highest`` matmul precision,
straight ``jax.numpy``; the KDA state as the recurrence it is defined by
(a ``lax.scan`` over positions), the experts as a loop over the ones
held. It imports nothing of the program and is written from the
published descriptions: DeepSeek-V2/V3 for latent attention and the
router, Kimi Linear for Kimi Delta Attention, the model's public
``config.json`` for the sizes and switches.

Layers. ``x += Mixer(RMSNorm(x)); x += FFN(RMSNorm(x))``, RMSNorm eps
1e-6, no biases, a final RMSNorm, an untied head. Published layer ``i``
has an MLA mixer where ``(i + 1) % layer_group_size == 0``, else KDA; a
dense SwiGLU FFN where ``i < first_k_dense_replace``, else the expert
FFN.

Departures from the published description, and what the config leaves
open (the configuration file lists the same under ``assumed``):

- the multi-token-prediction layer is not built (its loss weight is 0);
- the layer rule for ``layer_group_size`` is the family's, not stated in
  the config;
- MLA: per-head RMSNorm on q and on k (over all 192 dims, the shared
  rope part joined to each head's) BEFORE the rotary embedding is where
  ``use_qk_norm`` is placed here; the config says only that it is on;
- KDA: the decay's pre-activation ``W_a x`` has no learned scale or
  bias (``g = kda_lower_bound * sigmoid(W_a x)``: the safe gate, full
  rank); q and k are L2-normalised with 1e-6 under the root; the output
  norm's weight is one vector of the head size;
- the expert layer computes the part of the experts HELD HERE (one
  routing group of a deployment that spreads each layer over chips); it
  routes over all experts and leaves out what the absent ones would add;
- the selection bias is a frozen, seeded vector;
- ``kda_recurrence`` nests the scan (blocks of 64 positions under
  ``jax.checkpoint``) so that its backward pass keeps T/64 states and
  not T: the same recurrence, recomputed, no chunk-wise algebra;
- ``held_experts`` gathers, for each held expert, the rows that chose
  it where they are at most ``ROWS_CAP`` times its even share, and takes
  every row through it (weight 0 where not chosen) where they are more:
  the same sum either way, no row dropped (a dense pass over all 64
  experts costs 64 times the layer; a seeded router loads single experts
  with 16 times their share);
- attention is computed a head at a time (memory), scores whole.

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

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
#: most rows an expert gathers, in even shares of the rows of one call
ROWS_CAP = 8

SIZES: dict = {}
SHAPES: dict = {}
FROZEN_SHAPES: dict = {}


def configure(kwargs, lora):
    """Set the module's sizes from the model's keyword arguments (the
    program's ``model.kwargs``) and the scenario's ``lora`` keys."""
    z = dict(kwargs)
    z["rank"], z["alpha"] = lora["rank"], lora.get("alpha") or lora["rank"]
    d, H, K = z["hidden"], z["heads"], z["head_dim"]
    N, R, Dv, C = z["nope"], z["rope"], z["v_dim"], z["kv_rank"]
    kinds = []
    for i in range(z["first_layer"], z["first_layer"] + z["layers"]):
        kinds.append((i, "mla" if (i + 1) % z["layer_group"] == 0 else "kda",
                      i >= z["first_dense"]))
    z["kinds"] = kinds
    frozen = {"embed": (z["vocab"], d), "head": (d, z["vocab"]),
              "final_norm": (d,)}
    sites = {}
    for i, mixer, experts in kinds:
        L = f"L{i}."
        frozen[L + "mixer_norm"] = frozen[L + "ffn_norm"] = (d,)
        if mixer == "kda":
            for n in "qkv":
                sites[L + "kda_" + n] = (d, H * K)
                frozen[L + n + "_conv"] = (z["conv"], H * K)
            sites[L + "kda_o"] = (H * K, d)
            frozen.update({L + "kda_a": (d, H * K), L + "kda_b": (d, H),
                           L + "kda_g": (d, H), L + "o_norm": (K,)})
        else:
            sites.update({L + "mla_q": (d, H * (N + R)),
                          L + "mla_dkv": (d, C + R),
                          L + "mla_ukv": (C, H * (N + Dv)),
                          L + "mla_o": (H * Dv, d)})
            frozen.update({L + "mla_g": (d, H), L + "c_norm": (C,),
                           L + "q_norm": (N + R,), L + "k_norm": (N + R,)})
        if experts:
            E, W = z["experts_held"], z["expert_width"]
            frozen.update({
                L + "router": (d, z["n_experts"]),
                L + "router_bias": (z["n_experts"],),
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
                      / "configs" / "ling-3.0-flash.json").read_text())
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


def kda_recurrence(q, k, v, g, beta, block=64):
    """``S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_(t-1) + b_t k_t v_t^T``,
    ``o_t = S_t^T q_t``, a position at a time. ``q, k, g`` [B, T, H, K],
    ``v`` [B, T, H, V], ``beta`` [B, T, H]."""
    B, T, H, K = q.shape
    pad = -T % block
    if pad:  # k = 0, b = 0, no decay: the state passes
        longer = lambda a: jnp.pad(
            a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        q, k, v, g, beta = map(longer, (q, k, v, g, beta))

    def position(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[..., None] * S
        u = b_t[..., None] * (v_t - jnp.einsum(
            "bhk,bhkv->bhv", k_t, S, precision=HI))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S, precision=HI)

    @jax.checkpoint
    def span(S, xs):
        return jax.lax.scan(position, S, xs)

    by_block = lambda a: jnp.moveaxis(a, 1, 0).reshape(
        (-1, block) + a.shape[:1] + a.shape[2:])
    _, o = jax.lax.scan(span, jnp.zeros((B, H, K, v.shape[-1]), F32),
                        tuple(map(by_block, (q, k, v, g, beta))))
    o = o.reshape((-1,) + o.shape[2:])  # [T, B, H, V]
    return jnp.moveaxis(o, 0, 1)[:, :T]


def kda_inputs(dense, w, L, x):
    """What the recurrence takes, and the output gate: ``q, k, v`` after
    the short convolution and SiLU, q and k of unit length a head, the
    log-decay a channel, beta and the gate a head."""
    z = SIZES
    B, T, _ = x.shape
    H, K = z["heads"], z["head_dim"]

    def conv_silu(name):
        y = dense(L + "kda_" + name, x)
        taps = w[L + name + "_conv"].astype(F32)
        n = taps.shape[0]
        yp = jnp.pad(y, ((0, 0), (n - 1, 0), (0, 0)))
        y = sum(yp[:, i:i + T] * taps[i] for i in range(n))
        return jax.nn.silu(y).reshape(B, T, H, K)

    unit = lambda a: a * jax.lax.rsqrt(
        jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    qq, kk, vv = conv_silu("q"), conv_silu("k"), conv_silu("v")
    qq, kk = unit(qq) * K ** -0.5, unit(kk)
    g = z["kda_lower_bound"] * jax.nn.sigmoid(
        dense(L + "kda_a", x)).reshape(B, T, H, K)
    beta = jax.nn.sigmoid(dense(L + "kda_b", x))
    gate = jax.nn.sigmoid(dense(L + "kda_g", x))
    return qq, kk, vv, g, beta, gate


def kda_output(dense, w, L, o, gate):
    B, T, H, K = o.shape
    o = rms_norm(o, w[L + "o_norm"], SIZES["eps"]) * gate[..., None]
    return dense(L + "kda_o", o.reshape(B, T, H * K))


def kda_mixer(dense, w, L, x, q):
    *inputs, gate = kda_inputs(dense, w, L, x)
    return kda_output(dense, w, L, kda_recurrence(*inputs), gate)


def rope_interleaved(x, theta):
    R = x.shape[-1]
    inv = theta ** (-jnp.arange(0, R, 2, dtype=F32) / R)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     axis=-1).reshape(x.shape)


def mla_mixer(dense, w, L, x, q):
    z = SIZES
    B, T, _ = x.shape
    H, N, R, Dv, C = z["heads"], z["nope"], z["rope"], z["v_dim"], z["kv_rank"]
    qh = dense(L + "mla_q", x).reshape(B, T, H, N + R)
    ckr = dense(L + "mla_dkv", x)
    c = rms_norm(ckr[..., :C], w[L + "c_norm"], z["eps"])
    kv = dense(L + "mla_ukv", c).reshape(B, T, H, N + Dv)
    kh = jnp.concatenate([kv[..., :N], jnp.broadcast_to(
        ckr[:, :, None, C:], (B, T, H, R))], axis=-1)
    qh = rms_norm(qh, w[L + "q_norm"], z["eps"])
    kh = rms_norm(kh, w[L + "k_norm"], z["eps"])
    turn = lambda a: jnp.concatenate(
        [a[..., :N], rope_interleaved(a[..., N:], z["theta"])], axis=-1)
    qh, kh, vh = turn(qh), turn(kh), kv[..., N:]
    causal = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def head(args):
        q1, k1, v1 = args  # [B, T, D]
        s = jnp.einsum("bqd,bkd->bqk", q(q1), q(k1),
                       precision=HI) * (N + R) ** -0.5
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", q(pr), q(v1), precision=HI)

    heads_first = lambda a: jnp.moveaxis(a, 2, 0)
    o = jax.lax.map(head, tuple(map(heads_first, (qh, kh, vh))))
    gate = jax.nn.sigmoid(dense(L + "mla_g", x))
    o = jnp.moveaxis(o, 0, 2) * gate[..., None]
    return dense(L + "mla_o", o.reshape(B, T, H * Dv))


def route(x, router, bias, q):
    """Sigmoid scores over all experts; the bias joins for the choice
    only; groups by the sum of their two largest; ``topk_group`` groups
    kept; the ``top_k`` largest chosen; ``w = scale s / sum_chosen s``.
    Returns the weights as a dense [rows, experts] matrix, 0 where an
    expert is not chosen."""
    z = SIZES
    s = jax.nn.sigmoid(jnp.dot(q(x), q(router.astype(F32)), precision=HI))
    sel = s + bias.astype(F32)
    n, e = sel.shape
    grouped = sel.reshape(n, z["n_group"], e // z["n_group"])
    top2 = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    kth = jax.lax.top_k(top2, z["topk_group"])[0][:, -1:]
    sel = jnp.where((top2 >= kth)[:, :, None], grouped, -jnp.inf).reshape(n, e)
    _, idx = jax.lax.top_k(sel, z["top_k"])
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

    @jax.checkpoint  # on the way back an expert at a time again: the
    # scan would keep both branches' intermediates for all 64 otherwise
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
    w_all = route(rows, w[L + "router"], w[L + "router_bias"], q)
    y = held_experts(rows, w_all, w[L + "experts_gate_up"],
                     w[L + "experts_down"], q, z["expert_offset"])
    shared = dense(L + "shared_down", swiglu(dense(L + "shared_gate_up", x)))
    return y.reshape(B, T, d) + shared


def forward(p, x, q=lambda a: a, frozen=None):
    """Token ids [B, T] -> logits [B, T, vocab], float32."""
    z, w = SIZES, frozen

    def make_layer(L, mixer, experts):
        def ffn(h, dense):
            hn = rms_norm(h, w[L + "ffn_norm"], z["eps"])
            if experts:
                return h + expert_ffn(dense, w, L, hn, q)
            return h + dense(L + "ffn_down",
                             swiglu(dense(L + "ffn_gate_up", hn)))

        def layer(h, p):
            dense = make_dense(p, w, q)
            hn = rms_norm(h, w[L + "mixer_norm"], z["eps"])
            return ffn(h + mla_mixer(dense, w, L, hn, q), dense)

        # a KDA layer in three parts: the recurrence keeps what its own
        # nested scan keeps (a state every 64 positions); inside the
        # layer's checkpoint it would run once more on the way back
        def before(h, p):
            hn = rms_norm(h, w[L + "mixer_norm"], z["eps"])
            return kda_inputs(make_dense(p, w, q), w, L, hn)

        def after(h, o, gate, p):
            dense = make_dense(p, w, q)
            return ffn(h + kda_output(dense, w, L, o, gate), dense)

        def kda_layer(h, p):
            *inputs, gate = jax.checkpoint(before)(h, p)
            return jax.checkpoint(after)(
                h, kda_recurrence(*inputs), gate, p)

        # one layer's activations at a time
        return jax.checkpoint(layer) if mixer == "mla" else kda_layer

    h = w["embed"].astype(F32)[x.astype(jnp.int32)]
    for i, mixer, experts in z["kinds"]:
        h = make_layer(f"L{i}.", mixer, experts)(h, p)
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
