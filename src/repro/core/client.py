"""ClientUpdate (paper Alg. 2) as a compiled local-training loop.

``local_update`` runs ``local_steps`` masked optimizer steps over the
client's batch stream under ``lax.scan`` and returns the weight *delta*
(zero, bit-exactly, for frozen units — property-tested).  The optimizer
is freshly initialized each round, matching the paper's per-round client
setup (FEDn clients re-create the optimizer on every round).

FedProx (Sahu et al. 2018) is available through ``prox_mu > 0`` — the
proximal term pulls only the round's *trained* (unmasked) layers toward
the global model: the freeze mask is applied inside the prox sum, so
frozen layers contribute neither loss nor gradient.

``norm_hook`` (DESIGN.md §11) accumulates per-unit squared gradient
norms across the local steps — the scored selection engine's live
telemetry.  The hook reads the gradients the step has already
materialized (no extra HBM round-trips, one extra (U,) carry slot);
with ``norm_hook=None`` (scoring off) the scan carries and traces are
byte-for-byte what they were before the hook existed.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..common import pytree as pt
from ..optim.masked import adam_init, adam_step, sgd_init, sgd_step
from .masking import NormHook, apply_mask, slot_gather, slot_merge

PyTree = Any


def local_update(loss_fn: Callable, global_params: PyTree, mask: PyTree,
                 batches: PyTree, *, lr: float = 1e-2,
                 optimizer: str = "adam", prox_mu: float = 0.0,
                 loss_kwargs: Optional[Dict] = None,
                 norm_hook: Optional[NormHook] = None
                 ) -> Tuple[PyTree, Dict[str, jnp.ndarray]]:
    """One client's round.  ``batches`` leaves have leading (steps,) dim.

    Returns (delta, metrics) where delta = trained - global (exact zeros
    on frozen units) and metrics hold the loss of every local step
    (``loss_steps``, (steps,)), their mean and the last.  With
    ``norm_hook``, metrics additionally carries ``unit_sqnorm`` — (U,)
    per-unit squared gradient norms summed over the local steps (frozen
    units: exact zeros).
    """
    loss_kwargs = loss_kwargs or {}
    opt_init, opt_step = ((adam_init, adam_step) if optimizer == "adam"
                          else (sgd_init, sgd_step))

    def total_loss(params, batch):
        loss, metrics = loss_fn(params, batch, **loss_kwargs)
        if prox_mu > 0.0:
            # prox pulls TRAINED layers only: mask the diffs so frozen
            # layers contribute neither loss nor gradient
            diffs = apply_mask(mask, jax.tree_util.tree_map(
                lambda a, b: (a - b).astype(jnp.float32),
                params, global_params))
            sq = sum(jnp.sum(jnp.square(d))
                     for d in jax.tree_util.tree_leaves(diffs))
            loss = loss + 0.5 * prox_mu * sq
        return loss, metrics

    def step(carry, batch):
        if norm_hook is None:
            params, opt_state = carry
        else:
            params, opt_state, nacc = carry
        (loss, metrics), grads = jax.value_and_grad(
            total_loss, has_aux=True)(params, batch)
        grads = apply_mask(mask, grads)
        if norm_hook is not None:
            nacc = nacc + norm_hook.fn(grads)
        params, opt_state = opt_step(grads, opt_state, params, lr=lr,
                                     mask=mask)
        carry = (params, opt_state) if norm_hook is None \
            else (params, opt_state, nacc)
        return carry, loss

    init = (global_params, opt_init(global_params))
    if norm_hook is not None:
        init = init + (jnp.zeros((norm_hook.n_units,), jnp.float32),)
    carry, losses = jax.lax.scan(step, init, batches)
    delta = pt.tree_sub(carry[0], global_params)
    metrics = {"loss_mean": losses.mean(), "loss_last": losses[-1],
               "loss_steps": losses}
    if norm_hook is not None:
        metrics["unit_sqnorm"] = carry[2]
    return delta, metrics


def packed_cohort_fn(loss_fn: Callable, assign, fl,
                     loss_kwargs: Optional[Dict] = None, *,
                     scoring: bool = False) -> Callable:
    """The vmapped packed local-training stage, shared verbatim by the
    sync round step, the async dispatch, and the chunked cohort engine
    (DESIGN.md §13).

    Returns ``cohort(global_params, rows, valid, batches) -> (pdeltas,
    metrics)`` with a leading client axis on everything but
    ``global_params`` — exactly the shape contract
    ``launch.mesh.shard_over_clients`` splits over the ``(client,)``
    mesh, which is how all three call sites shard the same trace.
    """
    from .masking import packed_norm_hook

    def cohort(global_params, rows, valid, batches):
        def one(rows_c, valid_c, b):
            return local_update_packed(
                loss_fn, global_params, assign, rows_c, valid_c, b,
                lr=fl.lr, optimizer=fl.optimizer, prox_mu=fl.prox_mu,
                loss_kwargs=loss_kwargs,
                norm_hook=packed_norm_hook(assign, rows_c)
                if scoring else None)

        return jax.vmap(one)(rows, valid, batches)

    return cohort


def local_update_packed(loss_fn: Callable, global_params: PyTree,
                        assign, rows: PyTree, valid: PyTree,
                        batches: PyTree, *, lr: float = 1e-2,
                        optimizer: str = "adam", prox_mu: float = 0.0,
                        loss_kwargs: Optional[Dict] = None,
                        norm_hook: Optional[NormHook] = None
                        ) -> Tuple[PyTree, Dict[str, jnp.ndarray]]:
    """Packed variant of :func:`local_update` (DESIGN.md §7).

    ``rows``/``valid`` come from ``masking.slot_plan``: the client's
    trained macro rows of every stacked leaf, gathered into fixed-shape
    ``(L, ...)`` slot buffers.  The scan carry — packed params plus
    freshly initialized optimizer moments — holds only those slots, so
    frozen stacked rows cost **zero optimizer memory**; the loss sees
    the full model reconstructed by scattering the slots into
    ``stop_gradient(global_params)``, so no cotangent flows into frozen
    rows and XLA can dead-code-eliminate their weight-gradient work.
    Scalar leaves (embed/head) are carried whole with masked grads —
    exactly the dense path, which keeps the two paths bit-comparable.

    Returns ``(packed_delta, metrics)``: stacked leaves carry ``(L,
    ...)`` slot deltas (exact zeros on pad slots — pads never receive an
    optimizer update), scalar leaves full-shape masked deltas.
    """
    loss_kwargs = loss_kwargs or {}
    opt_init, opt_step = ((adam_init, adam_step) if optimizer == "adam"
                          else (sgd_init, sgd_step))
    frozen = jax.lax.stop_gradient(global_params)
    packed0 = slot_gather(assign, global_params, rows)

    def total_loss(packed, batch):
        params = slot_merge(assign, frozen, packed, rows)
        loss, metrics = loss_fn(params, batch, **loss_kwargs)
        if prox_mu > 0.0:
            # prox over the packed representation: trained slots only
            diffs = apply_mask(valid, jax.tree_util.tree_map(
                lambda a, b: (a - b).astype(jnp.float32), packed, packed0))
            sq = sum(jnp.sum(jnp.square(d))
                     for d in jax.tree_util.tree_leaves(diffs))
            loss = loss + 0.5 * prox_mu * sq
        return loss, metrics

    def step(carry, batch):
        if norm_hook is None:
            packed, opt_state = carry
        else:
            packed, opt_state, nacc = carry
        (loss, metrics), grads = jax.value_and_grad(
            total_loss, has_aux=True)(packed, batch)
        grads = apply_mask(valid, grads)
        if norm_hook is not None:
            # norms reduce from the packed grads the step already
            # materialized — the telemetry never touches frozen rows
            nacc = nacc + norm_hook.fn(grads)
        packed, opt_state = opt_step(grads, opt_state, packed, lr=lr,
                                     mask=valid)
        carry = (packed, opt_state) if norm_hook is None \
            else (packed, opt_state, nacc)
        return carry, loss

    init = (packed0, opt_init(packed0))
    if norm_hook is not None:
        init = init + (jnp.zeros((norm_hook.n_units,), jnp.float32),)
    carry, losses = jax.lax.scan(step, init, batches)
    delta = pt.tree_sub(carry[0], packed0)
    metrics = {"loss_mean": losses.mean(), "loss_last": losses[-1],
               "loss_steps": losses}
    if norm_hook is not None:
        metrics["unit_sqnorm"] = carry[2]
    return delta, metrics
