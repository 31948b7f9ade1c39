"""Jitted local-SGD machinery shared by all client trainers.

TPU-first redesign of the reference's torch batch loops
(``ml/trainer/my_model_trainer_classification.py``): the client shard lives
on device once; per-epoch shuffles are index arrays; the (epochs x batches)
loop runs inside one jitted ``lax.scan`` so a whole local-training call is a
single XLA dispatch. Padding batches carry a validity mask instead of ragged
shapes (static shapes keep the MXU tiled).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ...core import telemetry as tel
from ...models.model_hub import FedModel
from ...utils.pytree import PyTree


def make_loss_fn(model: FedModel) -> Callable:
    """Masked softmax cross-entropy, handling [B] or [B, T] integer labels
    and multi-hot [B, C] float labels (stackoverflow_lr)."""

    def loss_fn(params: PyTree, x: jnp.ndarray, y: jnp.ndarray, mask: jnp.ndarray, rng: jax.Array):
        logits = model.module.apply({"params": params}, x, train=True, rngs={"dropout": rng})
        if y.dtype in (jnp.int32, jnp.int64):
            if y.ndim == logits.ndim - 1:  # [B] or [B, T]
                losses = optax.softmax_cross_entropy_with_integer_labels(logits, y)
                if losses.ndim == 2:  # per-token -> per-example
                    losses = losses.mean(axis=-1)
            else:
                raise ValueError(f"label shape {y.shape} vs logits {logits.shape}")
        else:  # multi-label
            losses = optax.sigmoid_binary_cross_entropy(logits, y).mean(axis=-1)
        denom = jnp.maximum(mask.sum(), 1.0)
        return (losses * mask).sum() / denom

    return loss_fn


def make_eval_fn(model: FedModel) -> Callable:
    """Returns jitted (loss_sum, correct, count) over one batch."""

    @jax.jit
    @functools.partial(tel.track_compiles, name="eval_batch")
    def eval_batch(params: PyTree, x: jnp.ndarray, y: jnp.ndarray):
        logits = model.module.apply({"params": params}, x, train=False)
        if y.dtype in (jnp.int32, jnp.int64) and y.ndim == logits.ndim - 1:
            losses = optax.softmax_cross_entropy_with_integer_labels(logits, y)
            pred = jnp.argmax(logits, axis=-1)
            correct = jnp.sum(pred == y)
            count = jnp.asarray(np.prod(y.shape), jnp.float32)
            return losses.sum(), correct.astype(jnp.float32), count
        losses = optax.sigmoid_binary_cross_entropy(logits, y).mean(axis=-1)
        pred = (logits > 0).astype(y.dtype)
        correct = jnp.sum(jnp.all(pred == y, axis=-1))
        return losses.sum(), correct.astype(jnp.float32), jnp.asarray(y.shape[0], jnp.float32)

    return eval_batch


def create_client_optimizer(args: Any) -> optax.GradientTransformation:
    """Client optimizer (reference: trainer creates torch SGD/Adam per call)."""
    name = str(getattr(args, "client_optimizer", "sgd")).lower()
    lr = float(getattr(args, "learning_rate", 0.03))
    wd = float(getattr(args, "weight_decay", 0.0))
    momentum = float(getattr(args, "momentum", 0.0))
    if name == "sgd":
        tx = optax.sgd(lr, momentum=momentum if momentum > 0 else None)
    elif name == "adam":
        tx = optax.adam(lr)
    else:
        raise ValueError(f"unknown client optimizer {name!r}")
    if wd > 0:
        tx = optax.chain(optax.add_decayed_weights(wd), tx)
    return tx


def epoch_index_array(n: int, batch_size: int, epochs: int, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """[E, nb, B] gather indices + [E, nb, B] masks; fresh shuffle per epoch
    (matches torch DataLoader(shuffle=True) semantics)."""
    nb = max(1, -(-n // batch_size))
    total = nb * batch_size
    idx = np.zeros((epochs, total), np.int32)
    mask = np.zeros((epochs, total), np.float32)
    rng = np.random.default_rng(seed)
    for e in range(epochs):
        perm = rng.permutation(n)
        # pad may exceed n (shard smaller than one batch): cycle the perm
        idx[e] = np.resize(perm, total)
        mask[e] = np.concatenate([np.ones(n, np.float32), np.zeros(total - n, np.float32)])
    return idx.reshape(epochs, nb, batch_size), mask.reshape(epochs, nb, batch_size)


class LocalTrainResult(NamedTuple):
    params: PyTree
    loss: jnp.ndarray        # mean loss over all local steps
    num_steps: jnp.ndarray   # total optimizer steps taken


def make_local_train_fn(model: FedModel, args: Any, *, grad_transform: Optional[Callable] = None):
    """Build the jitted whole-local-round function.

    ``grad_transform(grads, params, global_params, extras)`` lets algorithm
    variants (SCAFFOLD, FedDyn, Mime) correct gradients; ``extras`` is a
    pytree carried through the scan untouched. FedProx's proximal term is
    folded into the loss via ``args.fedprox_mu`` (reference:
    fedprox_trainer.py).
    """
    loss_fn = make_loss_fn(model)
    tx = create_client_optimizer(args)
    mu = float(getattr(args, "fedprox_mu", 0.0) or 0.0)

    def total_loss(params, global_params, x, y, mask, rng):
        l = loss_fn(params, x, y, mask, rng)
        if mu > 0.0:
            prox = sum(
                jnp.sum(jnp.square(a - b))
                for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(global_params))
            )
            l = l + 0.5 * mu * prox
        return l

    @jax.jit
    @functools.partial(tel.track_compiles, name="local_train")
    def local_train(params, x_all, y_all, idx, mask, rng, extras):
        """idx/mask: [E, nb, B]; x_all/y_all: full device-resident shard."""
        global_params = params
        opt_state = tx.init(params)

        def step(carry, inputs):
            params, opt_state, rng = carry
            batch_idx, batch_mask = inputs
            rng, sub = jax.random.split(rng)
            bx = jnp.take(x_all, batch_idx, axis=0)
            by = jnp.take(y_all, batch_idx, axis=0)
            loss, grads = jax.value_and_grad(total_loss)(params, global_params, bx, by, batch_mask, sub)
            if grad_transform is not None:
                grads = grad_transform(grads, params, global_params, extras)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state, rng), loss

        E, nb, B = idx.shape
        flat_idx = idx.reshape(E * nb, B)
        flat_mask = mask.reshape(E * nb, B)
        (params, _, _), losses = jax.lax.scan(step, (params, opt_state, rng), (flat_idx, flat_mask))
        return LocalTrainResult(params, losses.mean(), jnp.asarray(E * nb))

    return local_train
