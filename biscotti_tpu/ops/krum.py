"""Krum Byzantine-update filtering as a fused XLA kernel.

This is the flagship device kernel of the framework (SURVEY.md §2.3 row 18):
the reference computes the O(n²·d) pairwise-distance matrix in numpy on the
verifier's CPU behind an embedded-Python bridge
(ref: ML/Pytorch/client_obj.py:114-143, duplicate
ML/code/logistic_validator.py:36-65, invoked from DistSys/krum.go:100-166).
Here it is one jitted function: a single [n,d]·[d,n] matmul on the MXU plus a
top-k, fused by XLA — no host round-trip.

Semantics (kept bit-faithful to the reference):
  f          = floor(NumAdversaries · n), NumAdversaries = 0.5 (krum.go:27-28,110)
  groupsize  = n − f
  D_ij       = ‖x_i‖² + ‖x_j‖² − 2·x_i·x_j
  score_i    = Σ of the (groupsize − 2) smallest D_ij, j ≠ i
               (the reference sums sorted(D_i)[1 : groupsize−1], dropping the
               self-distance at index 0)
  accept     = the n − f lowest-scoring updates

Returned as both an index set and a dense mask — the mask form is what the
simulator's fully-jitted round step consumes (no dynamic shapes).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp


def pairwise_sq_dists(x: jax.Array) -> jax.Array:
    """D[i,j] = ‖x_i − x_j‖², computed as one MXU matmul (ref:
    client_obj.py:131-134). float32 accumulation keeps scores stable for
    bfloat16 inputs."""
    with jax.named_scope("krum_prepare"):  # parallel/sim.py STAGES
        x = x.astype(jnp.float32)
        sq = jnp.sum(x * x, axis=-1)
    with jax.named_scope("krum_scores"):
        d = sq[:, None] + sq[None, :] - 2.0 * (x @ x.T)
        return jnp.maximum(d, 0.0)  # clamp fp cancellation noise


@partial(jax.jit, static_argnames=("num_adversaries",))
def krum_scores(deltas: jax.Array, num_adversaries: int) -> jax.Array:
    """score_i = Σ of the (n − f − 2) nearest-neighbor distances
    (ref: client_obj.py:127-143)."""
    n = deltas.shape[0]
    groupsize = n - num_adversaries
    k = max(groupsize - 2, 0)
    d = pairwise_sq_dists(deltas)
    with jax.named_scope("krum_scores"):
        # exclude self-distance exactly (the reference's sorted[0] drop)
        d = d + jnp.diag(jnp.full((n,), jnp.inf, jnp.float32))
        if k == 0:
            return jnp.zeros((n,), jnp.float32)
        neg_nearest, _ = jax.lax.top_k(-d, k)
        return -jnp.sum(neg_nearest, axis=-1)


@partial(jax.jit, static_argnames=("num_adversaries",))
def krum_accept_mask(deltas: jax.Array, num_adversaries: int) -> jax.Array:
    """Dense bool mask of the n − f accepted updates (lowest Krum scores;
    ref: client_obj.py:119-124 argpartition). Large committees on TPU
    score through the fused Pallas kernel (ops/krum_pallas)."""
    from biscotti_tpu.ops.krum_pallas import krum_scores_auto

    n = deltas.shape[0]
    keep = n - num_adversaries
    scores = krum_scores_auto(deltas, num_adversaries)
    with jax.named_scope("krum_select"):
        _, idx = jax.lax.top_k(-scores, keep)
        return jnp.zeros((n,), jnp.bool_).at[idx].set(True)


def krum_select(deltas: jax.Array, num_adversaries: int) -> jax.Array:
    """Reference-shaped API: the accepted index set, ascending by score rank
    then index (ref: krum(deltas, clip) -> good_idx). Host-side helper; the
    jitted mask form is preferred inside compiled round steps."""
    mask = krum_accept_mask(jnp.asarray(deltas), num_adversaries)
    return jnp.nonzero(mask)[0]


def default_num_adversaries(n: int, frac: float = 0.5) -> int:
    """adversaryCount = int(0.5·n) (ref: krum.go:110)."""
    return int(frac * n)


def collusion_accept_override(peer_id: int, num_nodes: int,
                              poison_fraction: float) -> bool:
    """Colluding poisoners rubber-stamp each other's updates when they land
    on the verifier committee (ref: krum.go:47-58): poisoners are the node
    ids above ceil(N·(1−POISONING))."""
    if poison_fraction <= 0:
        return False
    poisoning_index = math.ceil(num_nodes * (1.0 - poison_fraction))
    return peer_id > poisoning_index
