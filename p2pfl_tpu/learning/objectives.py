"""Loss / metric functions, all mask-aware.

Every function takes a boolean ``mask`` over the batch so padded rows
(from ragged federated shards, see datasets/data.py) contribute zero.
The reference's equivalents are the LightningModule ``training_step``s
(e.g. mnist/models/mlp.py:119-129 cross-entropy + MetricCollection);
the one-class SVM objective mirrors sklearn's SGDOneClassSVM used by
syscall/models/svm.py.
"""

from __future__ import annotations

from typing import Callable

import jax.numpy as jnp
import optax


def _mean(values: jnp.ndarray, mask: jnp.ndarray | None) -> jnp.ndarray:
    if mask is None:
        return jnp.mean(values)
    m = mask.astype(values.dtype)
    return jnp.sum(values * m) / jnp.maximum(jnp.sum(m), 1.0)


def cross_entropy_loss(logits, y, mask=None):
    losses = optax.softmax_cross_entropy_with_integer_labels(logits, y)
    return _mean(losses, mask)


def mse_loss(pred, x, mask=None):
    per_row = jnp.mean(
        jnp.square(pred - x.reshape(pred.shape)), axis=tuple(range(1, pred.ndim))
    )
    return _mean(per_row, mask)


def ocsvm_loss(scores, _y, mask=None, nu: float = 0.1):
    """Hinge part of the linear ν-one-class-SVM objective.

    With ``scores = w·x − ρ`` (models.syscall.OneClassSVM), the full
    SGDOneClassSVM objective is ``½‖w‖² − ρ + 1/ν · mean(max(0, −s))``;
    this returns the data term — the caller adds :func:`ocsvm_penalty`
    over the params (the learner does so when objective == "ocsvm").
    """
    hinge = jnp.maximum(0.0, -scores)
    return _mean(hinge, mask) / nu


def ocsvm_penalty(params) -> jnp.ndarray:
    """Parameter part of the ν-OCSVM objective: ``½‖w‖² − ρ``."""
    inner = params["params"] if "params" in params else params
    return 0.5 * jnp.sum(jnp.square(inner["w"])) - inner["rho"]


def masked_accuracy(logits, y, mask=None):
    correct = (jnp.argmax(logits, axis=-1) == y).astype(jnp.float32)
    return _mean(correct, mask)


def next_token_loss(model, params, x, y, mask=None):
    """Next-token objective of a language model: rows are whole
    sequences of token ids, ``y`` holds a label a position. The MODEL
    computes it (``loss(tokens, labels, mask) -> (loss, counters)``:
    the mean over the kept rows of each row's mean cross-entropy over its
    positions), because the head and the loss go a chunk of positions at
    a time: ``[tokens, vocabulary]`` logits are never whole."""
    return model.apply(params, x, y, mask, method="loss")


# scores aren't class logits / a label a position has no one "class"
NO_ACCURACY_OBJECTIVES = ("autoencoder", "ocsvm", "next_token")

_OBJECTIVES: dict[str, Callable] = {
    "classification": cross_entropy_loss,
    "autoencoder": mse_loss,
    "ocsvm": ocsvm_loss,
    "next_token": next_token_loss,
}


def get_objective(name: str) -> Callable:
    if name not in _OBJECTIVES:
        raise ValueError(f"unknown objective {name!r}; have {sorted(_OBJECTIVES)}")
    return _OBJECTIVES[name]
