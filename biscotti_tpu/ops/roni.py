"""RONI (Reject On Negative Influence) validation as a batched XLA kernel.

The reference scores one update at a time through the Python bridge:
score = err(w + δ) − err(w) on the verifier's local data, rejecting when
score > 0.02 (ref: ML/Pytorch/client_obj.py:100-112, threshold check
DistSys/main.go:203-231). Here the whole round's updates are scored in one
vmapped evaluation — n model evaluations batched into one XLA program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from biscotti_tpu.models.base import Model

RONI_THRESHOLD = 0.02  # ref: DistSys/main.go:203-231


def roni_scores(model: Model, flat_w: jax.Array, deltas: jax.Array,
                x_val: jax.Array, y_val: jax.Array, frozen=None) -> jax.Array:
    """scores[i] = err(w + δ_i) − err(w) on the validation split."""
    base = model.error_flat(flat_w, x_val, y_val, frozen)
    per = jax.vmap(lambda d: model.error_flat(flat_w + d, x_val, y_val,
                                              frozen))(deltas)
    return per - base


def roni_accept_mask(model: Model, flat_w: jax.Array, deltas: jax.Array,
                     x_val: jax.Array, y_val: jax.Array,
                     threshold: float = RONI_THRESHOLD,
                     frozen=None) -> jax.Array:
    """accept iff the update does not worsen validation error by more than
    the threshold (ref: main.go:203-231)."""
    return roni_scores(model, flat_w, deltas, x_val, y_val,
                       frozen) <= threshold


def make_roni_kernel(model: Model, threshold: float = RONI_THRESHOLD):
    """Build a jitted (flat_w, deltas[n,d], x_val, y_val) -> mask[n] kernel."""

    @jax.jit
    def kernel(flat_w, deltas, x_val, y_val):
        return roni_accept_mask(model, flat_w, deltas, x_val, y_val, threshold)

    return kernel
