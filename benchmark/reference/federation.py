"""Plain reference of a decentralised federated round, independent of
the program: per node one epoch of minibatch steps from its own state,
then the exchange and aggregation over the topology, then (at the end)
an evaluation on the shared test set. float32 arithmetic at ``highest``
matmul precision; values are rounded only where the configuration
states a storage type (parameters, optimizer moment, wire).

The one thing shared with the program by convention is the order of the
rows a node feeds: per epoch ``rng, k = split(rng)`` and
``permutation(k, rows)[:steps * batch]``, the node's rng being an input
made from the seed by the harness.

What belongs to a configuration comes from its model module: ``SHAPES``,
``init(key)`` (the TRAINED leaves, float32), ``forward(params, x, q)`` and,
where it has one, ``loss(outputs, y, mask) -> scalar``: the mean over the
kept rows of a batch, by the module's own rule for what a row holds (a
label a position, positions masked inside ``y``); else the cross-entropy
of one label a row. A label keeps whatever axes follow its row axis. A
configuration with a frozen part (``FROZEN_SHAPES``) is handed it once,
unstacked, in its stored type, and gets it back in every call as
``forward(params, x, q, frozen=...)``; only ``init``'s leaves are trained,
aggregated and compared.

``q`` (see :func:`quantizer`) turns the reference into the control;
``fault`` plants one of the faults the contract names.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as PS

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def quantizer(name):
    """Operand rounding for the control, per tensor, scaled to the
    tensor's largest magnitude, straight-through gradient — what a later
    PR that moves the GEMMs a step down would compute. ``fp8``: 4 bits
    of exponent and 3 of mantissa, the largest magnitude put on that
    grid's largest finite value, 240. Rounded with ``reduce_precision``:
    a convert to ``float8_e4m3fn`` and back is taken out by XLA on the
    TPU for one operand of a product and left for the other (PERF.md,
    Findings PR 31), and what was left read as a control."""
    if name in (None, "none"):
        return lambda a: a
    if name != "fp8":
        raise ValueError(f"unknown control precision {name!r}")

    def q(a):
        scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 240.0
        grid = jax.lax.reduce_precision(a / scale, 4, 3) * scale
        return a + jax.lax.stop_gradient(grid - a)

    return q


def stored(x, dtype):
    return x.astype(jnp.dtype(dtype))


def rounded(x, dtype):
    """``x`` rounded to ``dtype``'s grid and kept in its own type: what
    crosses a wire of that type. ``reduce_precision`` and not a pair of
    converts, which XLA takes out on the TPU (excess precision allowed):
    it did, and the wire's rounding was gone (PERF.md, Findings PR 31)."""
    to = jnp.finfo(jnp.dtype(dtype))
    if to.bits >= jnp.finfo(x.dtype).bits:
        return x
    return jax.lax.reduce_precision(x, to.nexp, to.nmant)


def adjacency(topology, n):
    a = np.zeros((n, n), bool)
    if topology == "ring":
        for i in range(n):
            a[i, (i + 1) % n] = a[i, (i - 1) % n] = True
    elif topology == "fully":
        a[:] = True
    else:
        raise ValueError(f"reference has no topology {topology!r}")
    np.fill_diagonal(a, False)
    return a


def masked_ce(logits, y, m):
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    m = m.astype(F32)
    return jnp.sum((lse - ll) * m) / jnp.maximum(jnp.sum(m), 1.0)


def leaf_norms(tree):
    """[leaves, nodes] L2 norms of a stacked tree, leaves by sorted name."""
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(tree[k].astype(F32)).reshape(
            tree[k].shape[0], -1), axis=1))
        for k in sorted(tree)])


class Federation:
    """spec keys: n_nodes, batch_size, epochs, optimizer{name, lr, ...},
    param_dtype, moment_dtype, wire_dtype, topology, aggregator{name,..},
    block_nodes, eval_batch."""

    def __init__(self, model, spec, q=None, fault=None, chips=1):
        self.model, self.spec = model, spec
        self.loss = getattr(model, "loss", masked_ce)
        self.q = quantizer(q)
        self.fault, self.chips = fault, chips
        # the nodes are spread over as many chips as the cell has, so that
        # a 256-node federation's float32 state fits; one chip is a mesh of 1
        self.mesh = Mesh(np.array(jax.devices()[:chips]), ("d",))
        self.by_node = NamedSharding(self.mesh, PS("d"))
        self.by_column = NamedSharding(self.mesh, PS(None, "d"))
        self.on_every_chip = NamedSharding(self.mesh, PS())
        self._epoch = jax.jit(jax.shard_map(
            self._all_nodes_epoch, mesh=self.mesh,
            in_specs=(PS("d"),) * 6 + (PS(),), out_specs=(PS("d"),) * 4,
            check_vma=False),
            donate_argnums=(0, 1))
        self._agg = jax.jit(self._aggregate, donate_argnums=(0,))
        self._eval = jax.jit(self._evaluate)

    # ---- state
    def init(self, key):
        """One node's weights from the seed, in the stored type; every
        node starts from them (the initial model's diffusion)."""
        p = self.model.init(key)
        return {k: stored(v, self.spec["param_dtype"]) for k, v in p.items()}

    def opt_init(self, params):
        opt = self.spec["optimizer"]
        mdt = self.spec.get("moment_dtype") or "float32"
        o = {"m": jax.tree.map(lambda v: jnp.zeros(v.shape, mdt), params)}
        if opt["name"] == "adam":
            o["v"] = jax.tree.map(lambda v: jnp.zeros(v.shape, F32), params)
            o["t"] = jnp.zeros((next(iter(params.values())).shape[0],), F32)
        return o

    def _forward(self, p32, x, frozen):
        if frozen:
            return self.model.forward(p32, x, self.q, frozen=frozen)
        return self.model.forward(p32, x, self.q)

    # ---- one node, one epoch
    def _update(self, p, o, g):
        opt = self.spec["optimizer"]
        pdt = self.spec["param_dtype"]
        mdt = self.spec.get("moment_dtype") or "float32"
        lr = opt["lr"]
        f = lambda t: jax.tree.map(lambda v: v.astype(F32), t)
        p32, m32 = f(p), f(o["m"])
        if opt["name"] == "sgd":
            m_new = jax.tree.map(lambda a, b: a + opt["momentum"] * b, g, m32)
            p_new = jax.tree.map(lambda a, b: a - lr * b, p32, m_new)
            o_new = {"m": jax.tree.map(lambda v: stored(v, mdt), m_new)}
        elif opt["name"] == "adam":
            b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
            t = o["t"] + 1.0
            m_new = jax.tree.map(lambda a, b: (1 - b1) * a + b1 * b, g, m32)
            v_new = jax.tree.map(
                lambda a, b: (1 - b2) * a * a + b2 * b, g, o["v"])
            c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
            p_new = jax.tree.map(
                lambda a, m, v: a - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
                p32, m_new, v_new)
            o_new = {"m": jax.tree.map(lambda v: stored(v, mdt), m_new),
                     "v": v_new, "t": t}
        else:
            raise ValueError(f"reference has no optimizer {opt['name']!r}")
        return jax.tree.map(lambda v: stored(v, pdt), p_new), o_new

    def _node_epoch(self, p, o, rng, x, y, m, frozen):
        s = x.shape[0]
        bsz = min(self.spec["batch_size"], s)
        steps = s // bsz
        rng, k = jax.random.split(rng)
        perm = jax.random.permutation(k, s)[: steps * bsz]
        bx = x[perm].reshape((steps, bsz) + x.shape[1:])
        by = y[perm].reshape((steps, bsz) + y.shape[1:])
        bm = m[perm].reshape(steps, bsz)
        if self.fault == "half_batch":
            bm = jnp.logical_and(bm, jnp.arange(bsz)[None, :] < bsz // 2)

        def loss_fn(p32, xb, yb, mb):
            return self.loss(self._forward(p32, xb, frozen), yb, mb)

        def step(carry, batch):
            p, o, tot = carry
            p32 = jax.tree.map(lambda v: v.astype(F32), p)
            loss, g = jax.value_and_grad(loss_fn)(p32, *batch)
            p, o = self._update(p, o, g)
            return (p, o, tot + loss), None

        (p, o, tot), _ = jax.lax.scan(step, (p, o, F32(0)), (bx, by, bm))
        return p, o, rng, tot / steps

    def _all_nodes_epoch(self, P, O, rngs, x, y, m, frozen):
        def one(args):
            p, o, rng, x_, y_, m_ = args
            for _ in range(self.spec.get("epochs", 1)):
                p, o, rng, loss = self._node_epoch(
                    p, o, rng, x_, y_, m_, frozen)
            return p, o, rng, loss

        with jax.default_matmul_precision("highest"):
            return jax.lax.map(one, (P, O, rngs, x, y, m),
                               batch_size=self.spec.get("block_nodes", 8))

    # ---- exchange and aggregation
    def _aggregate(self, P, n_samples):
        n = n_samples.shape[0]
        pdt, wdt = self.spec["param_dtype"], self.spec["wire_dtype"]
        agg = self.spec["aggregator"]
        a = adjacency(self.spec["topology"], n) | np.eye(n, dtype=bool)
        if self.fault == "no_exchange":
            blk = np.arange(n) // (n // self.chips)
            a = a & (blk[:, None] == blk[None, :])
        wire = lambda v: rounded(v.astype(F32), wdt)
        if agg["name"] == "fedavg":
            w = jnp.asarray(a, F32) * n_samples.astype(F32)[None, :]
            wn = w / jnp.sum(w, axis=1, keepdims=True)

            def mix(v):
                # each chip mixes its share of the columns over all nodes
                flat = v.reshape(n, -1)
                d = flat.shape[1]
                pad = -d % self.chips
                flat = jax.lax.with_sharding_constraint(
                    jnp.pad(flat, ((0, 0), (0, pad))), self.by_column)
                out = stored(jnp.dot(wn, wire(flat), precision=HI), pdt)
                out = jax.lax.with_sharding_constraint(out, self.by_node)
                return out[:, :d].reshape(v.shape)

            return jax.tree.map(mix, P)
        if agg["name"] == "krum":
            if not a.all():
                raise ValueError("reference Krum is for a fully connected net")
            flat = jnp.concatenate(
                [wire(P[k]).reshape(n, -1) for k in sorted(P)], axis=1)
            sq = jnp.sum(flat * flat, axis=1)
            d2 = sq[:, None] + sq[None, :] - 2.0 * jnp.dot(
                flat, flat.T, precision=HI)
            d2 = jnp.where(jnp.eye(n, dtype=bool), jnp.inf, d2)
            closest = jnp.sort(d2, axis=1)[:, : n - agg["f"] - 2]
            _, best = jax.lax.top_k(-jnp.sum(closest, axis=1), agg["m"])
            pick = lambda v: stored(rounded(
                jnp.mean(v[best], axis=0), wdt), pdt)
            return jax.tree.map(
                lambda v: jnp.broadcast_to(pick(wire(v))[None], v.shape), P)
        raise ValueError(f"reference has no aggregator {agg['name']!r}")

    # ---- evaluation
    def _evaluate(self, P, x, y, frozen):
        bsz = min(self.spec.get("eval_batch", 512), x.shape[0])
        steps = math.ceil(x.shape[0] / bsz)
        pad = steps * bsz - x.shape[0]
        xp = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
        yp = jnp.concatenate([y, jnp.zeros((pad,) + y.shape[1:], y.dtype)])
        mp = jnp.arange(steps * bsz) < x.shape[0]
        shp = lambda v: v.reshape((steps, bsz) + v.shape[1:])

        def node(p):
            p32 = jax.tree.map(lambda v: v.astype(F32), p)

            def batch(b):
                xb, yb, mb = b
                out = self._forward(p32, xb, frozen)
                return self.loss(out, yb, mb) * jnp.sum(mb.astype(F32))

            return jnp.sum(jax.lax.map(
                batch, (shp(xp), shp(yp), shp(mp)))) / x.shape[0]

        with jax.default_matmul_precision("highest"):
            return jax.lax.map(node, P)

    # ---- the whole comparison run
    def follow(self, *, key, rngs, x, y, mask, n_samples, x_test, y_test,
               eval_nodes, rounds, frozen=None):
        """Follow ``rounds`` rounds from the seed's weights; ``frozen``
        is the configuration's frozen part, device arrays by name. Returns
        numpy arrays: ``loss`` [rounds, n], ``moment`` [leaves, n] (first
        moment after round 1), ``change`` [leaves, n] (parameters' change
        after the last round), ``eval_loss`` [len(eval_nodes)], ``eval0_loss`` [1]
        (the test loss of the seed's weights, the same on every node),
        ``leaves`` (sorted names)."""
        n = n_samples.shape[0]
        put = lambda a: jax.device_put(np.asarray(a), self.by_node)
        # no copy where the arrays already lie there: on one chip, always
        frozen = jax.device_put(frozen or {}, self.on_every_chip)
        p0 = self.init(key)
        spread = jax.jit(lambda t: jax.tree.map(
            lambda v: jnp.broadcast_to(v[None], (n,) + v.shape), t),
            out_shardings=self.by_node)
        P = spread(p0)
        O = jax.jit(self.opt_init, out_shardings=self.by_node)(P)
        rngs, x, y, mask, ns = (put(a) for a in (rngs, x, y, mask, n_samples))
        norms_of = jax.jit(leaf_norms)
        eval0 = self._eval(jax.tree.map(lambda v: v[None], p0),
                           jnp.asarray(x_test), jnp.asarray(y_test), frozen)
        losses, moment = [], None
        for r in range(rounds):
            P, O, rngs, loss = self._epoch(P, O, rngs, x, y, mask, frozen)
            if r == 0:
                moment = np.asarray(norms_of(O["m"]))
            P = self._agg(P, ns)
            if self.fault == "state_unchanged":  # every round from the start
                P, O = spread(p0), jax.jit(
                    self.opt_init, out_shardings=self.by_node)(P)
            losses.append(np.asarray(loss))
        change = np.asarray(jax.jit(lambda a, b: leaf_norms(jax.tree.map(
            lambda u, v: u.astype(F32) - v.astype(F32)[None], a, b)))(P, p0))
        idx = np.asarray(eval_nodes)
        el = self._eval(jax.tree.map(lambda v: v[idx], P),
                        jnp.asarray(x_test), jnp.asarray(y_test), frozen)
        return {"loss": np.stack(losses), "moment": moment, "change": change,
                "eval_loss": np.asarray(el), "eval0_loss": np.asarray(eval0),
                "leaves": sorted(p0)}
