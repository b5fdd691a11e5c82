"""The plain float64 references against the program's models at a small
size, on the CPU, and the precision control against both."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import krum as rkrum
from benchmark.reference import models as rm
from benchmark.reference import round as rround


@pytest.mark.parametrize("ref_name,zoo_name", [("softmax", "softmax")])
def test_delta_and_logits_match_the_zoo(ref_name, zoo_name):
    from biscotti_tpu.models.trainer import local_step_fn
    from biscotti_tpu.models.zoo import model_for_dataset

    model = model_for_dataset("mnist", zoo_name)
    assert model.num_params == rm.num_params(ref_name)
    w = rm.init_weights(ref_name, 5)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(10, 784)).astype(np.float32)
    y = rng.integers(0, 10, 10).astype(np.int32)
    step = local_step_fn(model, "grad", clip=100.0)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(step(jnp.asarray(w), jnp.asarray(x),
                              jnp.asarray(y)), np.float64)
        logits = np.asarray(model.apply_flat(jnp.asarray(w), jnp.asarray(x)))
    ref = rm.local_delta(ref_name, w, x, y, 100.0)
    # float32 against float64 on the CPU: 1.5e-7 seen
    assert rround.leaf_gap(ref_name, got, ref) < 5e-6
    assert np.abs(logits - rm.logits(ref_name, w, x)).max() < 1e-5
    # the control, bfloat16 throughout, is two to three orders further off
    low = rm.local_delta(ref_name, w, x, y, 100.0, rm.bf16)
    assert rround.leaf_gap(ref_name, low, ref) > 1e-3
    # a stack of peers gives each peer's own delta
    both = rm.local_delta(ref_name, w, np.stack([x, x[::-1]]),
                          np.stack([y, y[::-1]]), 100.0)
    assert np.allclose(both[0], ref, rtol=0, atol=1e-12)


def test_clip_holds_the_norm():
    g = np.arange(12, dtype=np.float64).reshape(2, 6) * 100.0
    out = rm.clip_by_global_norm(g, 100.0)
    assert np.linalg.norm(out[1]) == pytest.approx(100.0)
    assert np.allclose(out[0] / np.linalg.norm(out[0]),
                       g[0] / np.linalg.norm(g[0]))


def test_krum_oracle_matches_the_program_and_flags_what_ties_do_not_hide():
    from biscotti_tpu.ops.krum import krum_accept_mask, krum_scores

    rng = np.random.default_rng(3)
    x = rng.normal(size=(23, 400)).astype(np.float32)
    f = 23 // 2
    scores, accept = rkrum.krum_oracle(x, f)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(krum_scores(jnp.asarray(x), f), np.float64)
        mask = np.asarray(krum_accept_mask(jnp.asarray(x), f))
    assert np.max(np.abs(got - scores) / scores) < 1e-5
    assert rkrum.beyond_ties(scores, accept, mask, 2e-5) == []
    # an accept set with a far-off update swapped in is beyond any tie
    wrong = accept.copy()
    wrong[np.argmax(scores)], wrong[np.argmin(scores)] = True, False
    assert len(rkrum.beyond_ties(scores, accept, wrong, 2e-5)) == 2
    low_scores, _ = rkrum.krum_oracle(x, f, rm.bf16)
    assert np.max(np.abs(low_scores - scores) / scores) > 1e-4
