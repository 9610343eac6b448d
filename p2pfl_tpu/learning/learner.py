"""NodeLearner contract + JaxLearner.

``NodeLearner`` reproduces the reference's template interface
(fedstellar/learning/learner.py:24-177: set_model/set_data/
encode_parameters/decode_parameters/check_parameters/set_parameters/
get_parameters/set_epochs/fit/interrupt_fit/evaluate/get_num_samples/
init/close/finalize_round/create_trainer) so the federation layer is
decoupled from the ML stack exactly as in the reference.

``JaxLearner`` is the TPU instance (the reference's is
lightninglearner.py on PyTorch Lightning). Everything hot is built as
**pure jittable functions** (`make_step_fns`) over an explicit
``TrainState`` pytree; the class is a thin host-side shell. That split
is what lets the federation run N learners as one vmapped/shard_mapped
XLA program instead of N Lightning Trainers in N processes.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from flax import struct

from p2pfl_tpu.ops import pallas_gemm

from p2pfl_tpu.core.serialize import (
    check_parameters,
    decode_parameters,
    encode_parameters,
)
from p2pfl_tpu.learning.objectives import (
    NO_ACCURACY_OBJECTIVES,
    get_objective,
    masked_accuracy,
    next_token_loss,
    ocsvm_penalty,
)
from p2pfl_tpu.obs import devprof
from p2pfl_tpu.obs.trace import get_tracer


class TrainState(struct.PyTreeNode):
    """Carry for one node's training: params + opt state + rng + step."""

    params: Any
    opt_state: Any
    rng: jax.Array
    step: jnp.int32


def make_optimizer(name: str = "sgd", learning_rate: float = 0.1,
                   momentum: float = 0.9, weight_decay: float = 0.0,
                   momentum_dtype: str | None = None):
    """Optimizer factory (TrainingConfig.optimizer).

    ``momentum_dtype="bf16"`` stores the SGD momentum accumulator in
    bfloat16: each training step streams every node's full optimizer
    state through HBM (docs/perf.md §2 regime 1), so halving the
    accumulator bytes buys measured round time (~5% on the north-star
    config) for a tiny, SGD-tolerated precision loss. f32 default.
    """
    name = name.lower()
    if momentum_dtype in (None, "f32", "float32"):
        acc_dt = None
    elif momentum_dtype in ("bf16", "bfloat16"):
        acc_dt = jnp.bfloat16
    else:
        # an unrecognized value silently training in f32 would record
        # an optimization that never ran (bench config JSON carries
        # the string) — reject loudly instead
        raise ValueError(
            f"momentum_dtype must be None/'f32'/'bf16', got "
            f"{momentum_dtype!r}"
        )
    if name == "sgd":
        tx = optax.sgd(learning_rate, momentum=momentum,
                       accumulator_dtype=acc_dt)
    elif name == "adam":
        tx = optax.adam(learning_rate, mu_dtype=acc_dt)
    elif name == "adamw":
        tx = optax.adamw(learning_rate, weight_decay=weight_decay,
                         mu_dtype=acc_dt)
        return tx
    else:
        raise ValueError(f"unknown optimizer {name!r}")
    if weight_decay:
        tx = optax.chain(optax.add_decayed_weights(weight_decay), tx)
    return tx


@dataclasses.dataclass(frozen=True)
class StepFns:
    """The pure-function core of a learner — safe to vmap/shard_map.

    The ``prepare_epoch``/``forward``/``backward``/``apply_update``
    quartet is the SAME step split into its phases (obs.devprof's
    step-profiling pipeline): ``forward`` returns the ``jax.vjp``
    residual closure so ``backward`` is the true cotangent pass —
    no forward recompute inflating either span."""

    init: Callable  # (rng, sample_x) -> TrainState
    train_epochs: Callable  # (state, x, y, mask, epochs, gate=None)
    # -> (state, metrics); gate: per-node 1.0/0.0 update scale
    evaluate: Callable  # (params, x, y, mask) -> metrics dict
    tx: Any
    # devprof phase split (None on hand-built StepFns that predate it)
    prepare_epoch: Callable | None = None  # (state, x, y, mask)
    # -> (rng', (bx, by, bm))
    forward: Callable | None = None  # (params, bx, by, bm) -> (loss, vjp)
    backward: Callable | None = None  # (vjp) -> grads
    apply_update: Callable | None = None  # (state, grads, gate=None)


def make_step_fns(
    model,
    objective: str = "classification",
    optimizer: str = "sgd",
    learning_rate: float = 0.1,
    momentum: float = 0.9,
    weight_decay: float = 0.0,
    momentum_dtype: str | None = None,
    batch_size: int = 32,
    eval_batch_size: int = 512,
) -> StepFns:
    """Build jit-able init / train / eval for a flax model.

    Training an epoch is one ``lax.scan`` over batches: a fresh
    permutation of the shard each epoch, fixed batch count (drop
    remainder — the reference's DataLoader default), masked loss so
    padded rows are inert. Epochs themselves are an outer ``lax.scan``,
    so "fit(E epochs)" is a single XLA program — the moral opposite of
    the reference building a fresh Lightning Trainer per round
    (lightninglearner.py:167-193).
    """
    loss_fn = get_objective(objective)
    # decay applied to the explicit gradient below, NOT via an
    # add_decayed_weights chain: the chain turns zero (gated-off)
    # grads back into wd*params inside tx.update, silently feeding
    # momentum on frozen nodes. adamw keeps its decoupled decay — its
    # decay rides the updates, which the gate also zeroes.
    explicit_decay = weight_decay if optimizer.lower() != "adamw" else 0.0
    tx = make_optimizer(
        optimizer, learning_rate, momentum,
        weight_decay if optimizer.lower() == "adamw" else 0.0,
        momentum_dtype=momentum_dtype,
    )
    # plain SGD's update is a pure elementwise stream the Pallas
    # sgd_accum kernel can fuse — one pass over params/trace/grads per
    # step instead of optax's per-transform tree traversals. Only the
    # exact optax.sgd chain (trace + scale_by_learning_rate; decay is
    # already folded into explicit grads above) is replicated, so
    # anything else keeps tx.update untouched.
    fuse_sgd = optimizer.lower() == "sgd"

    def _fused_sgd_step(st, grads, gate, on):
        """Route SGD leaves the measured gate picks through the fused
        Pallas stream. Returns None whenever the fusion does not apply
        — unexpected optax state shape, or no leaf picked pallas
        (always the case off-TPU, where the gate forces xla) — so the
        caller falls back to the bit-identical ``tx.update`` path."""
        opt_state = st.opt_state
        if not (isinstance(opt_state, (tuple, list)) and len(opt_state) == 2
                and hasattr(opt_state[0], "trace")
                and hasattr(opt_state[0], "_replace")):
            return None
        plan = jax.tree.map(
            lambda p: pallas_gemm.choose(
                "sgd_accum",
                ((math.prod(p.shape[:-1]) if p.ndim > 1 else 1,
                  p.shape[-1] if p.ndim else 1),) * 2,
                p.dtype,
            ) == "pallas",
            st.params,
        )
        if not any(jax.tree.leaves(plan)):
            return None
        # the federation gate folds into the learning rate: a
        # gated-off node's update is exactly +/-0.0, keeping params
        # bit-exact while momentum decays — the ``where`` semantics
        # below without a second tree pass
        lr_eff = learning_rate if gate is None else learning_rate * gate

        def leaf(p, m, g, use_pallas):
            if use_pallas:
                return pallas_gemm.sgd_accum(p, m, g, lr_eff,
                                             momentum=momentum)
            # leaves the gate left on XLA replicate optax.sgd term by
            # term: f32 trace update, uncast update scaled by -lr,
            # stored trace cast to the accumulator dtype
            m_new = g + momentum * m
            u = m_new * -learning_rate
            if on is not None:
                u = jnp.where(on, u, jnp.zeros_like(u))
            return (p + u).astype(p.dtype), m_new.astype(m.dtype)

        out = jax.tree.map(leaf, st.params, opt_state[0].trace, grads, plan)
        params, new_trace = jax.tree.transpose(
            jax.tree.structure(st.params), jax.tree.structure((0, 0)), out)
        return params, (opt_state[0]._replace(trace=new_trace), opt_state[1])

    def init(rng, sample_x) -> TrainState:
        params = model.init(rng, sample_x)
        return TrainState(
            params=params,
            opt_state=tx.init(params),
            rng=jax.random.fold_in(rng, 1),
            step=jnp.int32(0),
        )

    def batch_loss(params, bx, by, bmask):
        """``(loss, counters)``: the counters are what the model itself
        counts in a step (an expert layer's dropped pairs and load), an
        empty dict for every model that counts nothing."""
        if objective == "next_token":
            return next_token_loss(model, params, bx, by, bmask)
        out = model.apply(params, bx)
        if objective == "autoencoder":
            return loss_fn(out, bx, bmask), {}
        if objective == "ocsvm":
            return loss_fn(out, by, bmask) + ocsvm_penalty(params), {}
        return loss_fn(out, by, bmask), {}

    def _shuffle(x, perm):
        """Per-epoch reshuffle of the shard. TPU row-gathers of small
        rows serialize badly (~27 ms/epoch for the 64-node north-star
        workload); a one-hot matmul does the same permutation on the
        MXU at memory speed (~4 ms measured). Exact for float inputs:
        each output row is 1.0 * one source row, and f32*1.0 followed
        by a sum of zeros is bit-exact. Integer/bool inputs (labels,
        masks, token ids) keep the gather — their rows are tiny.

        Precondition: finite inputs. 0.0 * Inf/NaN is NaN, so one
        non-finite sample row would poison its column in EVERY output
        row, where the gather kept corruption local to one sample. The
        dataset loaders normalize real files to finite pixel ranges;
        the exactness claim and this containment boundary are pinned
        by tests/test_learner_shuffle.py."""
        # one-hot is O(s^2) in shard size — a federated shard (<=4k
        # rows) wins big, but a single-node learner training a whole
        # 20k-row dataset would materialize a [20k,20k] matrix; the
        # gather is the right tool there
        if (not jnp.issubdtype(x.dtype, jnp.floating) or x.ndim < 2
                or x.shape[0] > 4096):
            return x[perm]
        oh = jax.nn.one_hot(perm, x.shape[0], dtype=x.dtype)
        flat = x.reshape(x.shape[0], -1)
        # HIGHEST precision: TPU matmuls default to bf16-truncated
        # inputs, which would silently round every pixel each epoch;
        # full-precision passes keep the claim above true at a cost
        # that is still far below the row-gather being replaced
        out = jax.lax.dot(oh, flat, precision=jax.lax.Precision.HIGHEST)
        return out.reshape((perm.shape[0],) + x.shape[1:])

    def apply_update(st: TrainState, grads, gate=None) -> TrainState:
        """The optimizer-update phase of one step: explicit decay,
        gating, fused-SGD routing, optax fallback — everything after
        the gradient. ``train_one_epoch``'s scan body calls this, and
        obs.devprof jits it standalone as the ``devprof.update`` span,
        so the profiled pipeline applies the production update."""
        if explicit_decay:
            grads = jax.tree.map(
                lambda g, p: g + explicit_decay * p, grads, st.params)
        on = None
        if gate is not None:
            # zero grads AND updates instead of where-selecting whole
            # trees afterward: params stay bit-exact for gated-off
            # nodes (x + 0 == x) without an extra full-tree memory
            # pass, and no real gradient leaks into momentum.
            # ``where``, not ``* gate``: 0.0 * NaN is NaN, and a
            # gated-off node whose shard produces a non-finite grad
            # must stay frozen, not poisoned
            on = gate > 0
            grads = jax.tree.map(
                lambda g: jnp.where(on, g, jnp.zeros_like(g)), grads)
        fused = (_fused_sgd_step(st, grads, gate, on)
                 if fuse_sgd else None)
        if fused is not None:
            params, opt_state = fused
        else:
            updates, opt_state = tx.update(grads, st.opt_state, st.params)
            if gate is not None:
                updates = jax.tree.map(
                    lambda u: jnp.where(on, u, jnp.zeros_like(u)),
                    updates)
            params = optax.apply_updates(st.params, updates)
        return st.replace(params=params, opt_state=opt_state,
                          step=st.step + 1)

    def prepare_epoch(state: TrainState, x, y, mask):
        """The data/host-gather phase: fresh permutation + batch
        layout for one epoch. ``train_one_epoch`` runs it inline;
        devprof jits it standalone as the ``devprof.data`` span."""
        s = x.shape[0]
        bsz = min(batch_size, s)  # shards smaller than a batch still train
        steps = s // bsz
        used = steps * bsz
        rng, perm_rng = jax.random.split(state.rng)
        perm = jax.random.permutation(perm_rng, s)[:used]
        bx = _shuffle(x, perm).reshape((steps, bsz) + x.shape[1:])
        # a label keeps the axes that follow its row axis (one a
        # position for token rows)
        by = y[perm].reshape((steps, bsz) + y.shape[1:])
        bm = mask[perm].reshape(steps, bsz)
        return rng, (bx, by, bm)

    def forward(params, bx, by, bm):
        """devprof forward phase: the primal pass, returning the vjp
        residual closure (a jit-able Partial pytree) so the backward
        phase is measured without recomputing the forward."""
        return jax.vjp(lambda p: batch_loss(p, bx, by, bm)[0], params)

    def backward(vjp_fn, loss):
        """devprof backward phase: the cotangent pass alone. ``loss``
        rides along only to shape/dtype the seed cotangent."""
        (grads,) = vjp_fn(jnp.ones_like(loss))
        return grads

    def train_one_epoch(state: TrainState, xym, gate):
        x, y, mask = xym
        rng, (bx, by, bm) = prepare_epoch(state, x, y, mask)
        steps = bx.shape[0]

        def step(carry, batch):
            st, loss_sum = carry
            xb, yb, mb = batch
            # named scopes are metadata: they put the step's two halves
            # into the device ops' names (flax names the modules itself)
            with jax.named_scope("fit.value_and_grad"):
                (loss, counted), grads = jax.value_and_grad(
                    batch_loss, has_aux=True)(st.params, xb, yb, mb)
            with jax.named_scope("fit.optimizer_update"):
                st = apply_update(st, grads, gate)
            return (st, loss_sum + loss), counted

        (state, loss_sum), counted = jax.lax.scan(
            step, (state, 0.0), (bx, by, bm))
        state = state.replace(rng=rng)
        return state, (loss_sum / steps, counted)

    def train_epochs(state: TrainState, x, y, mask, epochs: int, gate=None):
        """``gate`` (optional f32 scalar, 1.0/0.0) scales every SGD
        update — the federated layer's trains∧alive selection folded
        into the step so frozen nodes cost no extra tree traffic.
        Gated-off nodes keep params exactly; their momentum decays,
        matching the reference's per-round optimizer reset
        (lightninglearner.py:167-193 builds a fresh Trainer per fit)."""

        def body(st, _):
            return train_one_epoch(st, (x, y, mask), gate)

        state, (losses, counted) = jax.lax.scan(
            body, state, None, length=epochs)
        metrics = {"loss": losses[-1], "loss_per_epoch": losses}
        if counted:  # the model's own counters, [epochs, steps, ...]
            metrics["counted"] = counted
        return state, metrics

    def evaluate(params, x, y, mask):
        """Batched eval via scan (bounds device memory on big test sets)."""
        s = x.shape[0]
        # a row of tokens is a whole sequence: an evaluation batch is
        # what a training batch is, the size the step's memory is for
        bsz = min(batch_size if objective == "next_token"
                  else eval_batch_size, s)
        steps = (s + bsz - 1) // bsz
        pad = steps * bsz - s
        xp = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
        yp = jnp.concatenate([y, jnp.zeros((pad,) + y.shape[1:], y.dtype)])
        mp = jnp.concatenate([mask, jnp.zeros((pad,), mask.dtype)])
        bx = xp.reshape((steps, bsz) + x.shape[1:])
        by = yp.reshape((steps, bsz) + y.shape[1:])
        bm = mp.reshape(steps, bsz)

        def step(carry, batch):
            loss_sum, correct_sum, count = carry
            xb, yb, mb = batch
            w = mb.astype(jnp.float32)
            cnt = jnp.sum(w)
            if objective in NO_ACCURACY_OBJECTIVES:
                # outputs aren't class logits (or, for token rows, are
                # never whole): the training loss is the whole of it
                with jax.named_scope("eval.forward"):
                    loss, _ = batch_loss(params, xb, yb, mb)
                acc = jnp.float32(0.0)
            else:
                with jax.named_scope("eval.forward"):
                    out = model.apply(params, xb)
                loss = loss_fn(out, yb, mb)
                acc = masked_accuracy(out, yb, mb)
            return (loss_sum + loss * cnt, correct_sum + acc * cnt,
                    count + cnt), None

        (loss_sum, correct_sum, count), _ = jax.lax.scan(
            step, (jnp.float32(0), jnp.float32(0), jnp.float32(0)), (bx, by, bm)
        )
        count = jnp.maximum(count, 1.0)
        return {"loss": loss_sum / count, "accuracy": correct_sum / count}

    return StepFns(init=init, train_epochs=train_epochs, evaluate=evaluate,
                   tx=tx, prepare_epoch=prepare_epoch, forward=forward,
                   backward=backward, apply_update=apply_update)


class NodeLearner:
    """The learner template (learner.py:24-177 parity). Methods raise
    until a concrete learner implements them."""

    def set_model(self, model) -> None: raise NotImplementedError
    def set_data(self, data) -> None: raise NotImplementedError
    def encode_parameters(self, params=None, contributors=None, weight=1) -> bytes:
        raise NotImplementedError
    def decode_parameters(self, data: bytes): raise NotImplementedError
    def check_parameters(self, params) -> bool: raise NotImplementedError
    def set_parameters(self, params) -> None: raise NotImplementedError
    def get_parameters(self): raise NotImplementedError
    def set_epochs(self, epochs: int) -> None: raise NotImplementedError
    def create_trainer(self) -> None: raise NotImplementedError
    def fit(self) -> None: raise NotImplementedError
    def interrupt_fit(self) -> None: raise NotImplementedError
    def evaluate(self): raise NotImplementedError
    def get_num_samples(self) -> tuple[int, int]: raise NotImplementedError
    def init(self) -> None: raise NotImplementedError
    def close(self) -> None: raise NotImplementedError
    def finalize_round(self) -> None: raise NotImplementedError


class SharedTrainer:
    """One compiled trainer shared by many same-config learners.

    An in-process simulation runs N ``JaxLearner``s whose models are
    identical; letting each build its own ``make_step_fns`` closures
    would compile N copies of the same XLA program (jit caches key on
    the function object). Build one of these and pass it to every
    ``JaxLearner(trainer=...)`` — one compile serves the federation.
    """

    def __init__(self, model, objective="classification", optimizer="sgd",
                 learning_rate=0.1, momentum=0.9, weight_decay=0.0,
                 momentum_dtype=None, batch_size=32):
        # socket-plane learners run one node per program: the kernel
        # gate must measure that width, not the last federation's vmap
        pallas_gemm.set_nodes_hint(1)
        self.fns = make_step_fns(
            model, objective=objective, optimizer=optimizer,
            learning_rate=learning_rate, momentum=momentum,
            weight_decay=weight_decay, momentum_dtype=momentum_dtype,
            batch_size=batch_size,
        )
        self.train_jit = jax.jit(self.fns.train_epochs,
                                 static_argnames=("epochs",))
        self.eval_jit = jax.jit(self.fns.evaluate)
        self.init_jit = jax.jit(self.fns.init)


class JaxLearner(NodeLearner):
    """Single-node JAX learner (lightninglearner.py parity).

    Used standalone for one node on one device; federations instead
    vmap the same ``StepFns`` (see p2pfl_tpu.parallel.federated). Keeps
    the reference's FL-aware step bookkeeping: ``global_step`` grows by
    the number of local steps each round
    (lightninglearner.py:162-165 / statisticslogger.py:131-153).
    """

    def __init__(self, model=None, data=None, objective="classification",
                 optimizer="sgd", learning_rate=0.1, momentum=0.9,
                 weight_decay=0.0, momentum_dtype=None, batch_size=32,
                 seed=0, logger=None,
                 trainer: SharedTrainer | None = None):
        self.model = model
        self.data = data
        self.objective = objective
        self.optimizer_name = optimizer
        self.learning_rate = learning_rate
        self.momentum = momentum
        self.weight_decay = weight_decay
        self.momentum_dtype = momentum_dtype
        self.batch_size = batch_size
        self.seed = seed
        self.logger = logger
        self.epochs = 1
        self.state: TrainState | None = None
        self.fns: StepFns | None = None
        self._shared = trainer
        self.global_step = 0
        self.local_step = 0
        self.round = 0
        self._interrupted = False
        # last fit's devprof_* gauges (MFU/TFLOPs/HBM) for the status
        # publisher; empty until a fit completes with devprof enabled
        self.devprof_last: dict = {}

    # -- wiring ----------------------------------------------------------
    def set_model(self, model) -> None:
        self.model = model
        self.fns = None

    def set_data(self, data) -> None:
        self.data = data

    def create_trainer(self) -> None:
        """Build + jit the step functions (Trainer-construction analog).
        With a ``SharedTrainer`` the compiled programs are reused."""
        if self._shared is not None:
            self.fns = self._shared.fns
            self._train_jit = self._shared.train_jit
            self._eval_jit = self._shared.eval_jit
            self._init_jit = self._shared.init_jit
            return
        pallas_gemm.set_nodes_hint(1)  # as SharedTrainer: one node wide
        self.fns = make_step_fns(
            self.model, objective=self.objective,
            optimizer=self.optimizer_name, learning_rate=self.learning_rate,
            momentum=self.momentum, weight_decay=self.weight_decay,
            momentum_dtype=self.momentum_dtype,
            batch_size=self.batch_size,
        )
        self._train_jit = jax.jit(self.fns.train_epochs,
                                  static_argnames=("epochs",))
        self._eval_jit = jax.jit(self.fns.evaluate)
        self._init_jit = jax.jit(self.fns.init)

    def init(self) -> None:
        if self.fns is None:
            self.create_trainer()
        rng = jax.random.PRNGKey(self.seed)
        sample = jnp.asarray(self.data.x[:1])
        self.state = self._init_jit(rng, sample)

    # -- parameters ------------------------------------------------------
    def get_parameters(self):
        return self.state.params

    def set_parameters(self, params) -> None:
        check_parameters(params, self.state.params)
        params = jax.tree.map(
            lambda new, old: jnp.asarray(new, old.dtype), params,
            self.state.params,
        )
        self.state = self.state.replace(params=params)

    def check_parameters(self, params) -> bool:
        try:
            check_parameters(params, self.state.params)
            return True
        except Exception:
            return False

    def encode_parameters(self, params=None, contributors=None, weight=1) -> bytes:
        if params is None:
            params = self.get_parameters()
        return encode_parameters(params, tuple(contributors or ()), weight)

    def decode_parameters(self, data: bytes):
        return decode_parameters(data)

    # -- training --------------------------------------------------------
    def set_epochs(self, epochs: int) -> None:
        self.epochs = epochs

    def _fit_args(self):
        """fit()'s device-call arguments — one definition shared with
        warm_up() so the warmed shapes are exactly the ones fit hits."""
        x = jnp.asarray(self.data.x)
        y = jnp.asarray(self.data.y)
        return x, y, jnp.ones(len(self.data.x), bool)

    def _eval_args(self):
        """evaluate()'s device-call arguments (val split when present)."""
        x = jnp.asarray(
            self.data.x_val if len(self.data.x_val) else self.data.x
        )
        y = jnp.asarray(
            self.data.y_val if len(self.data.x_val) else self.data.y
        )
        return x, y, jnp.ones(len(x), bool)

    def fit(self) -> None:
        if self.epochs <= 0:
            return
        if self._interrupted:  # honor a pending interrupt_fit()
            self._interrupted = False
            return
        with get_tracer().span("learner.fit",
                               args={"round": self.round,
                                     "epochs": self.epochs}):
            self._fit_traced()
        # gauges AFTER the span closes: the once-per-shape FLOP probe
        # compiles a program, and that compile must not bill itself to
        # learner.fit (the devprof phase-sum gate checks against it)
        if devprof.enabled() and getattr(self, "_devprof_wall", 0):
            self.devprof_last = devprof.fit_gauges(
                self, self._devprof_wall, self._devprof_epochs)

    def _fit_traced(self) -> None:
        x, y, mask = self._fit_args()
        t0 = time.monotonic()
        self._devprof_wall = 0.0  # stays 0 on an interrupted fit
        # step-level devprof swaps in the phase-split pipeline (separate
        # jitted phase programs, each drained inside its span); the
        # default path runs the fused production program untouched
        step_prof = (devprof.step_enabled()
                     and self.fns.prepare_epoch is not None)

        def one_epoch():
            if step_prof:
                return devprof.profiled_epoch(self, x, y, mask)
            return self._train_jit(self.state, x, y, mask, epochs=1)

        if self.epochs == 1:
            self.state, metrics = one_epoch()
            epochs_run = 1
        else:
            # multi-epoch fits run one compiled epoch at a time so
            # interrupt_fit() takes effect at the next epoch boundary
            # (the reference stops its Trainer mid-epoch via
            # trainer.should_stop, lightninglearner.py:122-125; a
            # jitted epoch is one device program and cannot be cut,
            # but a 10-epoch fit must not be uninterruptible)
            metrics = None
            epochs_run = 0
            for _ in range(self.epochs):
                if self._interrupted:
                    self._interrupted = False
                    break
                self.state, metrics = one_epoch()
                epochs_run += 1
            if metrics is None:
                return
        if devprof.enabled():
            # drain before reading the clock: the fused epoch program
            # dispatches async, so an un-synced wall would time the
            # enqueue, not the step, and the MFU gauge would report
            # dispatch rate (a warm fit "measures" sub-millisecond)
            jax.block_until_ready(self.state)
        self._devprof_wall = time.monotonic() - t0
        self._devprof_epochs = epochs_run
        steps = max(len(self.data.x) // self.batch_size, 1) * epochs_run
        self.local_step = steps
        if self.logger is not None:
            self.logger.log_metrics(
                {"Train/loss": float(metrics["loss"]),
                 "Train/epoch_time_s": (time.monotonic() - t0) / epochs_run},
                step=self.global_step + steps, round=self.round,
            )

    def warm_up(self) -> None:
        """Populate the jit cache for fit's and evaluate's programs at
        THIS learner's data shapes — callers measuring steady-state
        rounds warm before starting the clock. AOT lower+compile: no
        device execution is queued (a real warm epoch would still be
        draining when the caller starts its timer), and the argument
        construction is the same `_fit_args`/`_eval_args` the live
        calls use (fit always dispatches epochs=1 programs —
        multi-epoch fits loop them)."""
        if self.fns is None:
            self.create_trainer()
        if self.state is None:
            self.init()

        def avals(args):
            # .lower() needs only shapes/dtypes — materializing every
            # node's whole shard on device just to read its aval would
            # double the federation's host->device traffic
            return tuple(
                jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args
            )

        self._train_jit.lower(self.state, *avals(self._fit_args()),
                              epochs=1).compile()
        self._eval_jit.lower(self.state.params,
                             *avals(self._eval_args())).compile()

    def interrupt_fit(self) -> None:
        """Best-effort stop (lightninglearner.py:122-125). A jitted
        epoch is a single device program, so interruption takes effect
        at the next epoch boundary of a multi-epoch fit (or the next
        fit call for single-epoch fits)."""
        self._interrupted = True

    def evaluate(self):
        with get_tracer().span("learner.evaluate",
                               args={"round": self.round}):
            x, y, mask = self._eval_args()
            metrics = self._eval_jit(self.state.params, x, y, mask)
            out = {k: float(v) for k, v in metrics.items()}
        if self.logger is not None:
            self.logger.log_metrics(
                {f"Val/{k}": v for k, v in out.items()},
                step=self.global_step + self.local_step, round=self.round,
            )
        return out

    def get_num_samples(self) -> tuple[int, int]:
        return (self.data.n_samples, len(self.data.x_val))

    # -- lifecycle -------------------------------------------------------
    def finalize_round(self) -> None:
        """Step bookkeeping parity (lightninglearner.py:159-165)."""
        self.global_step += self.local_step
        self.local_step = 0
        self.round += 1

    def close(self) -> None:
        self.state = None
