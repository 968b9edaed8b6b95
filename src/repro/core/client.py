"""FL client: M local SGD steps from a (possibly stale) base model.

``make_local_update_fn`` builds a jit-able function:

    (base_params, batches, key) -> (delta, metrics)

where ``delta = base - end`` is the *cumulative update* Delta_i of the paper
(sum over local steps of lr * grad, for plain SGD), ``batches`` is a
pytree whose leaves carry a leading (M, ...) local-step axis, and
``metrics`` are the loss's own metrics of each local step, stacked on that
axis.

``make_fresh_loss_fn`` evaluates the CURRENT global model on a fresh local
mini-batch — the P_i^t probe of eq. (4). In the real protocol the server
broadcasts x^t to the buffered clients, which reply with one scalar; the
simulator performs that exchange directly.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.optim.optimizers import apply_updates, sgd
from repro.utils.pytree import tree_sub


def make_local_update_fn(loss_fn: Callable, local_steps: int, local_lr: float,
                         momentum: float = 0.0,
                         prox_mu: float = 0.0) -> Callable:
    """loss_fn(params, batch) -> (scalar, metrics_dict).

    ``prox_mu > 0`` adds the FedProx proximal term mu/2 * ||w - w_base||^2
    to each local step — the standard heterogeneity mitigation the paper's
    related-work line cites; composes with any aggregation policy.
    """
    opt = sgd(local_lr, momentum=momentum)
    grad_fn = jax.grad(loss_fn, has_aux=True)

    @jax.named_scope("local_update")
    def local_update(base_params, batches, _key=None):
        opt_state = opt.init(base_params)

        def step(carry, batch):
            params, ostate = carry
            g, metrics = grad_fn(params, batch)
            if prox_mu:
                g = jax.tree.map(
                    lambda gi, p, b: gi + prox_mu * (p - b).astype(gi.dtype),
                    g, params, base_params)
            upd, ostate = opt.update(g, ostate, params)
            return (apply_updates(params, upd), ostate), metrics

        (end_params, _), metrics = jax.lax.scan(
            step, (base_params, opt_state), batches, length=local_steps)
        delta = tree_sub(base_params, end_params)  # Delta_i (gradient-like)
        return delta, metrics

    return local_update


@functools.lru_cache(maxsize=64)
def make_fresh_loss_fn(loss_fn: Callable) -> Callable:
    """(global_params, fresh_batch) -> scalar mean per-sample loss.

    Memoized on ``loss_fn`` so repeated server constructions share one
    probe function (and downstream, one compiled server pass)."""

    def fresh_loss(global_params, fresh_batch):
        loss, _ = loss_fn(global_params, fresh_batch)
        return loss.astype(jnp.float32)

    return fresh_loss
