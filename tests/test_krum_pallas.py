"""Equivalence tests for the fused Pallas Krum kernel (ops/krum_pallas).

On the CPU test mesh the kernel runs in interpreter mode — same kernel
body, same selection algebra — and must reproduce the XLA path's scores
(ops/krum.krum_scores) to float-reassociation tolerance, including the
adversarial tie cases (duplicate updates) that break approximate
selection schemes.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from biscotti_tpu.ops.krum import (  # noqa: E402
    default_num_adversaries,
    krum_accept_mask,
    krum_scores,
)
from biscotti_tpu.ops.krum_pallas import (  # noqa: E402
    krum_scores_auto,
    krum_scores_pallas,
)


def _rel_err(a, b):
    return np.max(np.abs(a - b) / (np.abs(a) + 1e-6))


@pytest.mark.parametrize("n,d", [(8, 16), (100, 64), (130, 50), (160, 96)])
def test_pallas_scores_match_xla(n, d):
    rng = np.random.default_rng(7)
    x = rng.normal(size=(n, d)).astype(np.float32)
    f = default_num_adversaries(n)
    ref = np.asarray(krum_scores(jnp.asarray(x), f))
    got = np.asarray(krum_scores_pallas(jnp.asarray(x), f))
    assert _rel_err(ref, got) < 1e-4


def test_pallas_scores_with_duplicate_updates_tie_handling():
    # colluding poisoners submit IDENTICAL updates: zero distances and
    # exact ties at the k-th threshold — the selection must count tied
    # copies like a sorted prefix would
    rng = np.random.default_rng(1)
    x = rng.normal(size=(96, 32)).astype(np.float32)
    x[10:40] = x[10]  # 30 identical rows
    f = default_num_adversaries(96)
    ref = np.asarray(krum_scores(jnp.asarray(x), f))
    got = np.asarray(krum_scores_pallas(jnp.asarray(x), f))
    assert _rel_err(ref, got) < 1e-4


def test_pallas_accept_set_matches_xla_on_poison_cluster():
    # a poisoned cluster far from the honest mass: the accept SET (what
    # the protocol consumes) must be identical, not just the scores
    rng = np.random.default_rng(3)
    n, d = 140, 48
    x = rng.normal(size=(n, d)).astype(np.float32)
    x[100:] += 25.0  # 40 outliers
    f = default_num_adversaries(n)
    keep = n - f
    ref_mask = np.asarray(krum_accept_mask(jnp.asarray(x), f))
    scores = krum_scores_pallas(jnp.asarray(x), f)
    _, idx = jax.lax.top_k(-scores, keep)
    got_mask = np.zeros((n,), bool)
    got_mask[np.asarray(idx)] = True
    assert np.array_equal(ref_mask, got_mask)
    assert not got_mask[100:].any()


def test_auto_dispatch_small_n_uses_xla_path():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(40, 16)).astype(np.float32))
    f = default_num_adversaries(40)
    ref = np.asarray(krum_scores(x, f))
    got = np.asarray(krum_scores_auto(x, f))
    assert np.allclose(ref, got, rtol=1e-5, atol=1e-6)


def test_auto_dispatch_boundaries(monkeypatch):
    # prove WHICH committee sizes reach the kernel: inside the window the
    # dispatcher offers it to the platform being lowered for (under jit
    # both branches are traced; the TPU one is lowered only for a TPU —
    # that half is tests/test_tpu_lowering.py's), outside it the kernel is
    # never traced
    import biscotti_tpu.ops.krum_pallas as kp

    traced = []
    real = kp.krum_scores_pallas

    def spy(deltas, num_adversaries):
        traced.append(deltas.shape[0])
        return real(deltas, num_adversaries)

    monkeypatch.setattr(kp, "krum_scores_pallas", spy)
    rng = np.random.default_rng(9)
    for n in (kp.PALLAS_MIN_N - 1, kp.PALLAS_MIN_N, kp.PALLAS_MAX_N,
              kp.PALLAS_MAX_N + 1):
        x = jnp.asarray(rng.normal(size=(n, 8)).astype(np.float32))
        got = np.asarray(jax.jit(kp.krum_scores_auto,
                                 static_argnums=1)(x, n // 2))
        # off-TPU every size RUNS the XLA path, bit for bit
        assert np.array_equal(got, np.asarray(krum_scores(x, n // 2)))
    assert traced == [kp.PALLAS_MIN_N, kp.PALLAS_MAX_N]
