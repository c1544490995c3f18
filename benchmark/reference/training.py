"""Plain training step for the reference networks: mean softmax cross-entropy,
its gradient, and Adam as Kingma & Ba write it.  float32 at HIGHEST; ``quant``
rounds the operands of every contraction (the low-precision control).  Blocks of
the network may be rematerialised so that a full batch fits: that changes no
number."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import nn


def loss_of(forward, model_cfg, params, images, labels, quant=None, rows=None):
    """Mean cross-entropy over the batch; ``rows`` (a count) keeps only the
    first rows, mean over those (the half-batch fault)."""
    if rows is not None:
        images, labels = images[:rows], labels[:rows]
    logits = forward(nn.Net(params, train=True, quant=quant), images, model_cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def adam_step(params, grads, mu, nu, count, *, learning_rate, b1, b2, eps):
    count = count + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    params = jax.tree.map(
        lambda p, m, v: p - learning_rate * (m / c1) / (jnp.sqrt(v / c2) + eps), params, mu, nu)
    return params, mu, nu, count


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in tree.items()}


def first_steps(forward, model_cfg, train_cfg, params, batches, *, quant=None, rows=None,
                frozen=False):
    """Follow ``batches`` (a list of (images, labels)) from ``params``.  Returns
    the losses, the per-leaf norms of the first gradient and of the parameters'
    change after the last step, and the first gradient itself (on the host).  ``frozen`` plants the fault of a step that
    returns its state unchanged."""
    opt = {k: float(train_cfg[k]) for k in ("learning_rate", "b1", "b2", "eps")}

    @jax.jit
    def step(p, mu, nu, count, images, labels):
        loss, grads = jax.value_and_grad(
            lambda q: loss_of(forward, model_cfg, q, images, labels, quant, rows))(p)
        new = adam_step(p, grads, mu, nu, count, **opt)
        return loss, leaf_norms(grads), new

    zeros = jax.tree.map(jnp.zeros_like, params)
    state = (params, zeros, zeros, jnp.zeros((), jnp.float32))
    losses, grad_norms, grad = [], None, None
    for images, labels in batches:
        loss, norms, new = step(*state, jnp.asarray(images), jnp.asarray(labels))
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = {k: float(v) for k, v in norms.items()}
            # Read as the program's is: Adam's first moment after one step.
            grad = {k: np.asarray(v) / (1.0 - opt["b1"]) for k, v in new[1].items()}
        if not frozen:
            state = new
    change = jax.jit(lambda a, b: leaf_norms(jax.tree.map(jnp.subtract, a, b)))(state[0], params)
    return losses, grad_norms, {k: float(v) for k, v in change.items()}, grad
