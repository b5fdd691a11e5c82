"""One whole device round in float64 numpy: who is sampled, each sampled
peer's minibatch and clipped delta, the DP noise, Krum, the sum of the
accepted raw deltas, the stake ledger and the test error.

Imports nothing of biscotti_tpu. The round's random draws are part of what
the system promises (the same seed trains the same model), so the stream
is stated here and re-derived with `jax.random` alone:

    rkey             = fold_in(fold_in(PRNGKey(0), seed), it)
    ckey, bkey, nkey = split(rkey, 3)
    sampled          = choice(ckey, N, (S,), replace=False)
    batch of peer i  = choice(fold_in(bkey, i), rows, (B,), replace=False)
    noise of peer i  = (-1/B) * sigma * sqrt(B) * normal(fold_in(nkey, i), d)
    sigma            = sqrt(2 ln(1.25/delta)) / epsilon

(upstream: client_obj.py:59-67,97-98 noise; krum.go:296 arrival order,
modelled as a random subset).
"""

import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import krum as rkrum
from . import models as rm

PEER_BLOCK = 32  # peers whose gradients are computed together
THREADS = 4      # blocks in flight


def draws(seed, it, n, s, rows, batch, d, sigma):
    """(sampled ids [S], batch rows [S, B], noise [S, d] float32, or None
    where sigma is 0)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def go(seed, it):
        rkey = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(0), seed), it)
        ckey, bkey, nkey = jax.random.split(rkey, 3)
        cidx = (jnp.arange(n) if s >= n
                else jax.random.choice(ckey, n, (s,), replace=False))
        idx = jax.vmap(lambda i: jax.random.choice(
            jax.random.fold_in(bkey, i), rows, (batch,), replace=False))(cidx)
        if sigma == 0:
            return cidx, idx, None
        noise = jax.vmap(lambda i: (-1.0 / batch) * (
            sigma * math.sqrt(batch) * jax.random.normal(
                jax.random.fold_in(nkey, i), (d,), jnp.float32)))(cidx)
        return cidx, idx, noise

    cidx, idx, noise = go(jnp.asarray(seed, jnp.int32), it)
    return (np.asarray(cidx), np.asarray(idx),
            None if noise is None else np.asarray(noise))


def sigma_for(epsilon, delta):
    return math.sqrt(2.0 * math.log(1.25 / delta)) / epsilon if epsilon > 0 \
        else 0.0


def reference_round(spec, seed, it, w, stake, shard_rows, x_val, y_val,
                    precision=None, accept_from=None):
    """The round from weights `w` and ledger `stake`.

    spec: model, n, s, rows, batch, clip, epsilon, delta, noising,
    verification, stake_unit. shard_rows(peer, idx) -> (x [B, 784], y [B]).
    `accept_from`: an accept set to aggregate with in place of the oracle's
    own (the program's, once it has been held to the oracle beyond ties).
    `precision`: None is float64. "bfloat16" holds every operand and every
    result in bfloat16, the running sum of the accepted deltas and the kept
    weights too. "bfloat16_f32acc" is the milder control: the same
    bfloat16 operands and stored deltas, but Krum's scores, the sum of the
    accepted deltas and the kept weights stay in float32 (exact here).
    Returns a dict: sampled, scores, accept, agg, w_next, stake_next, err.
    """
    q = rm.quantizer(precision)
    model, d = spec["model"], rm.num_params(spec["model"])
    sigma = sigma_for(spec["epsilon"], spec["delta"]) \
        if spec["noising"] else 0.0
    cidx, idx, noise = draws(seed, it, spec["n"], spec["s"], spec["rows"],
                             spec["batch"], d, sigma)
    low = precision not in (None, "float64")
    low_sums = precision == "bfloat16"
    kept = np.asarray(w, np.float64)
    w = q(w)
    s = spec["s"]
    deltas = np.empty((s, d), np.float64)

    def block(at):
        batches = [shard_rows(int(peer), idx[j]) for j, peer
                   in enumerate(cidx[at:at + PEER_BLOCK], start=at)]
        deltas[at:at + PEER_BLOCK] = rm.local_delta(
            model, w, np.stack([x for x, _ in batches]),
            np.stack([y for _, y in batches]), spec["clip"], q)

    with ThreadPoolExecutor(THREADS) as pool:  # numpy drops the GIL
        list(pool.map(block, range(0, s, PEER_BLOCK)))
    noised = deltas
    if noise is not None:
        noised = deltas + (q(noise) if low else noise)
        if low:
            noised = q(noised)
    if spec["verification"]:
        scores, accept = rkrum.krum_oracle(noised, s // 2,
                                           q if low_sums else None)
    else:
        scores, accept = np.zeros(s), np.ones(s, bool)
    used = accept if accept_from is None else np.asarray(accept_from, bool)
    if low_sums:
        agg = np.zeros(d, np.float64)
        for row in deltas[used]:  # in order, as a low-precision sum runs
            agg = q(agg + row)
        w_next = q(w + agg)
    else:
        agg = deltas[used].sum(axis=0)
        w_next = kept + agg
    stake_next = np.array(stake, np.int64)
    np.add.at(stake_next, cidx,
              np.where(used, spec["stake_unit"], -spec["stake_unit"]))
    err = rm.error(model, w_next, x_val, y_val, q)
    return {"sampled": cidx, "scores": scores, "accept": accept, "agg": agg,
            "w_next": w_next, "stake_next": stake_next, "err": err}


def leaf_gap(model, got, ref):
    """Worst leaf of |got - ref| (L2) over the larger of that leaf's
    reference norm and the median leaf's."""
    return max(leaf_gaps(model, got, ref).values())


def leaf_gaps(model, got, ref):
    ref_leaves = rm.leaves(model, np.asarray(ref, np.float64))
    got_leaves = rm.leaves(model, np.asarray(got, np.float64))
    norms = [float(np.linalg.norm(r)) for _, r in ref_leaves]
    floor = float(np.median(norms))
    return {name: float(np.linalg.norm(g - r)) / max(nr, floor, 1e-300)
            for (name, g), (_, r), nr in zip(got_leaves, ref_leaves, norms)}
