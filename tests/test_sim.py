"""Simulator tests: end-to-end convergence, defense behavior under poisoning,
determinism, stake evolution, and the sharded (multi-device) round step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from biscotti_tpu.config import BiscottiConfig, Defense
from biscotti_tpu.parallel.sim import Simulator, make_sharded_round_step


def _cfg(**kw):
    base = dict(dataset="mnist", num_nodes=8, batch_size=32, epsilon=0.0,
                noising=False, verification=False, defense=Defense.NONE,
                sample_percent=1.0, num_verifiers=0, num_miners=0,
                convergence_error=0.02)
    base.update(kw)
    return BiscottiConfig(**base)


def test_clean_run_converges():
    sim = Simulator(_cfg())
    w, stake, logs = sim.run(num_rounds=40)
    assert logs[-1].error < 0.1, [l.error for l in logs][-5:]


def test_run_deterministic():
    a = Simulator(_cfg()).run(num_rounds=5, stop_at_convergence=False)
    b = Simulator(_cfg()).run(num_rounds=5, stop_at_convergence=False)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert [l.error for l in a[2]] == [l.error for l in b[2]]


def test_scan_matches_loop():
    sim1 = Simulator(_cfg())
    w1, _, logs = sim1.run(num_rounds=6, stop_at_convergence=False)
    sim2 = Simulator(_cfg())
    w2, _, errs, _ = sim2.run_scan(num_rounds=6)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), rtol=1e-5)
    np.testing.assert_allclose([l.error for l in logs], errs, atol=1e-6)


def test_krum_blocks_poisoning():
    # 30% label-flip poisoners, Krum on: attack rate must stay low
    cfg = _cfg(poison_fraction=0.30, verification=True, defense=Defense.KRUM,
               num_nodes=10)
    sim = Simulator(cfg)
    w, stake, logs = sim.run(num_rounds=40, stop_at_convergence=False)
    defended_attack = sim.attack_rate(w)
    # same poisoning with no defense
    cfg2 = _cfg(poison_fraction=0.30, num_nodes=10)
    sim2 = Simulator(cfg2)
    w2, _, _ = sim2.run(num_rounds=40, stop_at_convergence=False)
    undefended_attack = sim2.attack_rate(w2)
    assert defended_attack < 0.15, f"krum failed: {defended_attack}"
    assert defended_attack < undefended_attack


def test_stake_rewards_accepted_updates():
    cfg = _cfg(num_nodes=6, verification=True, defense=Defense.KRUM)
    sim = Simulator(cfg)
    _, stake, _ = sim.run(num_rounds=5, stop_at_convergence=False)
    stake = np.asarray(stake)
    assert stake.sum() != 6 * cfg.default_stake or np.any(stake != cfg.default_stake)
    assert np.all(stake[stake > cfg.default_stake] % cfg.stake_unit == 0)


def test_contributor_sampling_static_shape():
    cfg = _cfg(num_nodes=10, sample_percent=0.5, num_verifiers=1, num_miners=1)
    sim = Simulator(cfg)
    w, stake = sim.init_state()
    w2, stake2, mask, err = sim.round_step(w, stake, 0)
    assert mask.shape[0] == cfg.num_samples == 5


def test_dp_noise_changes_trajectory_but_not_aggregation_target():
    clean = Simulator(_cfg()).run(num_rounds=5, stop_at_convergence=False)
    noisy = Simulator(_cfg(epsilon=1.0, noising=True, verification=True,
                           defense=Defense.KRUM)).run(
        num_rounds=5, stop_at_convergence=False)
    assert not np.allclose(np.asarray(clean[0]), np.asarray(noisy[0]))


def test_sharded_round_step_matches_semantics():
    n_dev = len(jax.devices())
    if n_dev < 2:
        pytest.skip("needs multi-device mesh")
    cfg = _cfg(num_nodes=8, verification=True, defense=Defense.KRUM)
    sim = Simulator(cfg)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:8]), ("peers",))
    step = make_sharded_round_step(sim, mesh)
    w = jnp.zeros((sim.num_params,), jnp.float32)
    for it in range(3):
        w, mask, err = step(w, it)
    assert mask.shape == (8,)
    assert int(mask.sum()) == 8 - 4  # n - f accepted
    assert float(err) < 0.9
    # convergence under sharding too
    for it in range(3, 25):
        w, mask, err = step(w, it)
    assert float(err) < 0.2


def test_sharded_seed_override_takes_effect():
    # regression (ADVICE r5): the sharded path used to read sim.root_key,
    # so run_scan-style seed overrides silently no-opped on sharded runs
    n_dev = len(jax.devices())
    if n_dev < 2:
        pytest.skip("needs multi-device mesh")
    sim = Simulator(_cfg(num_nodes=8))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:8]), ("peers",))
    step = make_sharded_round_step(sim, mesh)
    w = jnp.zeros((sim.num_params,), jnp.float32)
    w_a, _, _ = step(w, 0, seed=1)
    w_a2, _, _ = step(w, 0, seed=1)
    w_b, _, _ = step(w, 0, seed=2)
    np.testing.assert_array_equal(np.asarray(w_a), np.asarray(w_a2))
    assert not np.allclose(np.asarray(w_a), np.asarray(w_b)), \
        "seed override had no effect on the sharded path"
    # default seed = cfg.seed
    w_d, _, _ = step(w, 0)
    w_c, _, _ = step(w, 0, seed=sim.cfg.seed)
    np.testing.assert_array_equal(np.asarray(w_d), np.asarray(w_c))


def test_fault_drop_mask_mirrors_degraded_rounds():
    """The sim's cheap mirror of the live fault plane: with drop
    probability p, accepted updates shrink (lost miner-bound frames join
    no aggregate), dropped contributors' stake never moves, and the same
    fault seed reproduces the same degraded rounds."""
    from biscotti_tpu.runtime.faults import FaultPlan

    base = _cfg(num_nodes=8)
    dropped = _cfg(num_nodes=8,
                   fault_plan=FaultPlan(seed=5, drop=0.4))
    rounds = 8
    _, stake_clean, logs_clean = Simulator(base).run(
        num_rounds=rounds, stop_at_convergence=False)
    sim_a = Simulator(dropped)
    _, stake_a, logs_a = sim_a.run(num_rounds=rounds,
                                   stop_at_convergence=False)
    _, stake_b, logs_b = Simulator(dropped).run(num_rounds=rounds,
                                                stop_at_convergence=False)
    acc_clean = sum(l.accepted for l in logs_clean)
    acc_drop = sum(l.accepted for l in logs_a)
    assert acc_drop < acc_clean, "drop mask removed no contributions"
    assert acc_drop > 0, "40% drop must not kill every round"
    # determinism: same fault seed => same degraded schedule
    assert [l.accepted for l in logs_a] == [l.accepted for l in logs_b]
    np.testing.assert_array_equal(np.asarray(stake_a), np.asarray(stake_b))
    # dropped contributors are neither credited nor debited: total stake
    # movement is strictly smaller than the clean run's
    d_clean = np.abs(np.asarray(stake_clean) - base.default_stake).sum()
    d_drop = np.abs(np.asarray(stake_a) - base.default_stake).sum()
    assert d_drop < d_clean


def test_fault_drop_rejected_with_trimmed_mean():
    from biscotti_tpu.runtime.faults import FaultPlan

    cfg = _cfg(num_nodes=8, verification=True,
               defense=Defense.TRIMMED_MEAN, secure_agg=False,
               fault_plan=FaultPlan(seed=1, drop=0.2))
    with pytest.raises(ValueError, match="TRIMMED_MEAN"):
        Simulator(cfg)


def test_creditcard_logreg_sim():
    cfg = BiscottiConfig(dataset="creditcard", num_nodes=10, batch_size=32,
                         epsilon=0.0, noising=False, verification=False,
                         sample_percent=1.0, num_verifiers=0, num_miners=0)
    sim = Simulator(cfg)
    w, stake, logs = sim.run(num_rounds=100, stop_at_convergence=False)
    assert logs[-1].error < 0.2, logs[-1].error


# ------------------------------- the declared step and the walked peer axis


def _deltas(sim, block, w=None):
    """Round 0's raw and noised deltas and the dispatch's counts, with the
    peer axis walked in blocks of `block`."""
    sim.steps.block = block
    w = sim.init_state()[0] if w is None else w
    seed = jnp.asarray(sim.cfg.seed, jnp.int32)
    whole, noised = sim._build_round_step()  # traced with this block
    _, deltas, noisy = jax.jit(noised)(w, 0, seed, sim.x, sim.y, sim.frozen)
    counts = jax.jit(whole)(w, sim.init_state()[1], 0, seed, sim.x, sim.y,
                            sim.x_val, sim.y_val, sim.frozen)[4]
    return deltas, noisy, counts


@pytest.mark.parametrize("block", [1, 2, 6])
@pytest.mark.parametrize("kind", ["mnist_cnn", "laguna_tiny"])
def test_walked_peer_axis_gives_the_vmapped_deltas(kind, block):
    """6 sampled peers, stepped one, two and all at a time: the same
    deltas, the same noise, and (for the model that counts its expert
    dispatch) the same counts added up over the blocks."""
    if kind == "mnist_cnn":
        cfg = _cfg(num_nodes=6, batch_size=4, model_name="mnist_cnn")
    else:
        cfg = _cfg(dataset="lm_tokens_tiny", num_nodes=6, batch_size=2,
                   learning_rate=0.1, grad_clip=1.0)
    sim = Simulator(cfg)
    assert sim.peer_block == 6  # all at once is what the code works out
    w = sim.model.flat_init(jax.random.PRNGKey(1))
    whole, whole_noised, whole_counts = _deltas(sim, 6, w)
    deltas, noised, counts = _deltas(sim, block, w)
    assert deltas.shape == (6, sim.num_params)
    np.testing.assert_allclose(deltas, whole, rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(noised, whole_noised, rtol=2e-4, atol=1e-6)
    assert jax.tree.structure(counts) == jax.tree.structure(whole_counts)
    assert bool(counts) == (kind == "laguna_tiny")
    for name in counts:
        if name in ("tile_rows", "grouped_kernel", "buffer_rows"):
            continue  # a call's own: the row tiles IT visited, its side,
            # its buffer's rows
        np.testing.assert_array_equal(counts[name], whole_counts[name])
    if counts:  # every held row lies in a visited tile, however blocked
        assert (np.asarray(counts["tile_rows"])
                >= np.asarray(counts["load"]).sum(axis=-1)).all()
        assert not np.asarray(counts["grouped_kernel"]).any()  # tiny


def test_peer_block_is_worked_out_from_the_bytes():
    from biscotti_tpu.models.peer_step import (BLOCK_SHARE, DEVICE_BYTES,
                                               peer_block)

    assert peer_block(21, None, 10**9) == 21       # no activation size: all
    assert peer_block(21, 0, 10**9) == 21
    gib = 2**30
    # `free` such that BLOCK_SHARE of it is b GiB: the division truncates
    # (exact at a share of 0.5, a byte short at 0.6), so a byte more
    assert peer_block(21, gib, int(21 * gib / BLOCK_SHARE) + 1) == 21
    assert peer_block(21, gib, int(8 * gib / BLOCK_SHARE) + 1) == 7  # a divisor
    assert peer_block(21, gib, int(4 * gib / BLOCK_SHARE) + 1) == 3
    assert peer_block(21, gib, 10) == 1            # at least one peer
    assert peer_block(2368, 10, 10**12) == 2368
    # the published size: 21 peers of a 1,024-token window next to 6 GB, at
    # what the chip's runtime states. The model counts the attention's
    # scores on both sides of ops/attention.py's dispatch, also where the
    # kernel holds none (`laguna.step_bytes` says why): two float32 arrays
    # [heads, T, T] of the widest layer
    from biscotti_tpu.models import laguna
    from biscotti_tpu.models.zoo import model_for_dataset

    model = model_for_dataset("lm_tokens")
    assert model.info["attention"]["fused"] == 1
    per_peer = model.step_bytes(1)
    assert 0.9e9 < per_peer < 1.4e9
    assert peer_block(21, per_peer, DEVICE_BYTES - int(6.3e9)) == 3
    # (every other term is linear in the window's length)
    for name, length, fused in (("laguna_s_fedlora", 1024, 1),
                                ("laguna_tiny", 16, 0)):
        cfg = laguna.PRESETS[name]
        short, long = (laguna.laguna_model(name, cfg, t)
                       for t in (length, 2 * length))
        assert short.info["attention"]["fused"] == fused
        assert (long.step_bytes(1) - 2 * short.step_bytes(1)
                == 2 * 4 * max(cfg.heads) * 2 * length * length)


# (dataset, frozen parameters in bfloat16, the block the chip runs): the
# three published language models' cells, 21 of 30 peers sampled, 64
# windows of 1,024 int32 tokens and as many labels a peer
PUBLISHED_BLOCKS = [("lm_tokens", 3003393024, 3),
                    ("lm_tokens_dsv2", 5166269440, 3),
                    ("lm_tokens_granite", 3195459328, 1)]


@pytest.mark.parametrize("dataset,frozen,block", PUBLISHED_BLOCKS)
def test_the_published_cells_blocks_at_the_chips_stated_bytes(dataset, frozen,
                                                              block):
    """What `Simulator` works out on the chip, worked out here: `free` is
    DEVICE_BYTES (the runtime's `bytes_limit` of a v5e, not the data
    sheet's 16 GiB, with which DeepSeek-V2's cell read 3 here while it ran
    1 there) less the cell's `standing`, and the block holds with a tenth
    less free and a tenth more."""
    from biscotti_tpu.models import lm
    from biscotti_tpu.models.peer_step import DEVICE_BYTES, peer_block
    from biscotti_tpu.models.zoo import model_for_dataset

    model = model_for_dataset(dataset)
    assert lm.frozen_count(model) == frozen
    standing = (2 * frozen + 30 * 2 * 64 * 1024 * 4
                + 4 * (3 * 21 + 2) * model.num_params)
    free = DEVICE_BYTES - standing
    step = model.step_bytes(1)
    assert [peer_block(21, step, int(share * free))
            for share in (0.9, 1.0, 1.1)] == [block] * 3
    assert DEVICE_BYTES < 16 * 2**30


@pytest.mark.parametrize("model,dataset,rule,rate", [
    ("logreg", "creditcard", "sgd", 0.03),
    ("softmax", "mnist", "grad", 1.0),
    ("svm", "mnist", "grad", 1.0),
    ("mnist_cnn", "mnist", "grad", 1.0),
    ("laguna_tiny", "lm_tokens_tiny", "clipped_sgd", 0.25),
])
def test_the_step_rule_follows_the_models_declaration(model, dataset, rule,
                                                      rate):
    """Nothing reads a model's name: the rule is the model's own field, and
    the rate (of the step AND of its noise) the configuration's for it."""
    import dataclasses

    from biscotti_tpu.models.trainer import (clip_by_global_norm,
                                             local_step_fn, step_rule)
    from biscotti_tpu.models.zoo import model_for_dataset

    cfg = _cfg(dataset=dataset, model_name=model, num_nodes=4, batch_size=4,
               logreg_alpha=0.03, learning_rate=0.25, grad_clip=0.5,
               epsilon=1.0, noising=True)
    m = model_for_dataset(dataset, model)
    assert (m.step_rule, step_rule(m, cfg)) == (rule, (rule, rate))
    sim = Simulator(cfg)
    assert sim.mode == rule and sim._noise_alpha == rate
    # a model of another NAME with the same declaration steps the same
    renamed = dataclasses.replace(m, name="logreg" if model != "logreg"
                                  else "softmax")
    assert step_rule(renamed, cfg) == (rule, rate)
    w = m.flat_init(jax.random.PRNGKey(0))
    x, y = sim.x[0, :4], sim.y[0, :4]
    g = jax.grad(m.loss_flat)(w, x, y, sim.frozen)
    want = {"grad": -clip_by_global_norm(g, 0.5), "sgd": -0.03 * g,
            "clipped_sgd": -0.25 * clip_by_global_norm(g, 0.5)}[rule]
    got = local_step_fn(m, rule, clip=0.5, alpha=rate)(w, x, y, sim.frozen)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-8)


def test_an_unknown_step_rule_is_refused():
    from biscotti_tpu.models.base import make_model

    with pytest.raises(ValueError, match="unknown step rule"):
        make_model("x", 2, 2, lambda k: {"w": jnp.zeros(2)},
                   lambda p, x: x, lambda p, x, y: 0.0, step_rule="adam")


def test_the_live_plane_holds_the_frozen_base_once():
    """What was a refusal ("ROADMAP B0's remainder") until the stepper took
    models/peer_step.py's program: the stepper's frozen tree is the very
    one a co-hosted peer's Trainer holds, and the simulator's leaf for
    leaf (all three draw it from the run's seed)."""
    from biscotti_tpu.data import datasets as ds
    from biscotti_tpu.models.trainer import Trainer
    from biscotti_tpu.runtime.hive import HiveStepper

    cfg = _cfg(dataset="lm_tokens_tiny", num_nodes=4, batch_size=2,
               learning_rate=0.1, grad_clip=1.0)
    stepper = HiveStepper(cfg, [0, 1])
    trainer = Trainer(cfg.dataset, ds.shard_name(cfg.dataset, 0, False),
                      cfg=cfg, seed=0, light=True)
    ours, theirs = (jax.tree.leaves(t)
                    for t in (stepper._frozen, trainer.frozen))
    assert len(ours) > 50 and all(a is b for a, b in zip(ours, theirs))
    sim = Simulator(cfg)
    for a, b in zip(ours, jax.tree.leaves(sim.frozen)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
