"""In-process N-peer round simulator — peers mapped to the device.

This is the TPU-idiomatic replacement for the reference's process-per-peer
deployment when you want *round math* rather than *protocol transport*: the
reference can only simulate N peers by booting N OS processes exchanging RPC
(ref: DistSys/localTest.sh) or by a Python for-loop (ref:
ML/Pytorch/ml_main_mnist.py:24-60). Here one jitted XLA program executes the
whole round for all peers at once:

    deltas   = vmap(local_step)     — S contributors' SGD steps, batched matmuls
    noise    = vmap(threefry draw)  — DP noising committee equivalent
    mask     = Krum | RONI kernel   — verifier committee equivalent
    w'       = w + Σ maskᵢ·deltaᵢ   — miner aggregation (sum, ref honest.go:360-375)
    stake'   = ±STAKE_UNIT scatter  — ledger bookkeeping (ref honest.go:414-419)

Peers-as-devices: `make_sharded_round_step` shards the peer axis over a
`jax.sharding.Mesh` with `shard_map`; the only cross-peer communication is an
`all_gather` of the [S,d] noised deltas for Krum and a `psum` of the masked
aggregate — both ride ICI, replacing the reference's TCP fan-out.

Committee *identity* (who is verifier/miner this round) does not change the
round's math, only who executes it; the distributed runtime (runtime/peer.py)
models identities. The simulator reproduces the math at full fidelity,
including contributor sampling and stake evolution.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from biscotti_tpu.config import BiscottiConfig, Defense
from biscotti_tpu.data import datasets as ds
from biscotti_tpu.models.base import Model
from biscotti_tpu.models.trainer import local_step_fn, sample_batch
from biscotti_tpu.models.zoo import model_for_dataset
from biscotti_tpu.ops import dp_noise
from biscotti_tpu.ops.krum import default_num_adversaries, krum_accept_mask
from biscotti_tpu.ops.roni import roni_accept_mask
from biscotti_tpu.utils.profiling import PhaseClock

# The round's stages, as `jax.named_scope`s in the round program: THE
# vocabulary a device trace is read by (docs/OBSERVABILITY.md, "Device
# trace"). A scope is compile-time metadata: it lands in the `op_name` of
# every instruction traced inside it and changes no instruction. The
# one-chip step and `sharded_round_step_fn` open them in the helpers they
# share, so both programs carry the same names. The three `krum_*` scopes
# are opened in ops/krum.py and ops/krum_pallas.py. Each name is matched as
# a whole token, and none is a JAX primitive's or function's name.
STAGES = (
    "round_sample",     # contributor choice, key folding, minibatch indices
    "round_gather",     # x[cidx], y[cidx], then each peer's xi[idx], yi[idx]
    "round_grad",       # loss gradient and clip, vmapped over the peers
    "round_noise",      # noise keys, the normal draw, deltas + noise
    "krum_prepare",     # cast, pad, squared norms
    "krum_scores",      # Pallas kernel, or Gram matmul + distances + top_k
    "krum_select",      # top_k of the scores, the accept-mask scatter
    "round_aggregate",  # masked sum of the accepted deltas, w + agg
    "round_ledger",     # stake scatter, the fault plane's drop mask
    "round_eval",       # test error of the next weights
)


@dataclass
class RoundLog:
    """One reference-log row: `iteration,error,timestamp`
    (ref: eval parser usenix-eval/generateResults.py:23-52)."""

    iteration: int
    error: float
    timestamp: float
    accepted: int = 0

    def csv(self) -> str:
        return f"{self.iteration},{self.error:.6f},{self.timestamp:.6f}"


def defense_mask(defense: Defense, model: Model, w: jax.Array,
                 noised: jax.Array, x_val: jax.Array, y_val: jax.Array,
                 roni_threshold: float, num_adversaries: int) -> jax.Array:
    """Verifier-committee accept mask over the round's noised updates —
    shared by the single-chip (vmap) and sharded (shard_map) round steps so
    the two paths cannot drift. TRIMMED_MEAN has no per-update reject (it
    is an aggregation rule, not a mask — see masked_aggregate), so it
    accepts all like NONE."""
    n = noised.shape[0]
    if defense == Defense.KRUM:
        return krum_accept_mask(noised, num_adversaries)
    if defense == Defense.MULTIKRUM:
        from biscotti_tpu.ops.robust_agg import multikrum_accept_mask

        return multikrum_accept_mask(noised, num_adversaries)
    if defense == Defense.FOOLSGOLD:
        from biscotti_tpu.ops.robust_agg import foolsgold_accept_mask

        return foolsgold_accept_mask(noised)
    if defense == Defense.RONI:
        return roni_accept_mask(model, w, noised, x_val, y_val, roni_threshold)
    return jnp.ones((n,), jnp.bool_)


def masked_aggregate(mask: jax.Array, deltas: jax.Array, noised: jax.Array,
                     dp_in_model: bool, defense: Defense = Defense.KRUM,
                     trim_fraction: float = 0.35) -> jax.Array:
    """Miner aggregation: sum of accepted RAW deltas (the noised copies exist
    only for verification, ref: SURVEY §2.3 row 21) — except in dp_in_model
    mode where the noise IS part of the update (ref: honest.go:172-179).
    Under TRIMMED_MEAN the sum is replaced by the coordinate-wise trimmed
    aggregate (ops/robust_agg.py); the mask is all-ones there."""
    agg_src = noised if dp_in_model else deltas
    if defense == Defense.TRIMMED_MEAN:
        from biscotti_tpu.ops.robust_agg import trimmed_mean_aggregate

        return trimmed_mean_aggregate(agg_src, trim_fraction)
    return jnp.sum(jnp.where(mask[:, None], agg_src, 0.0), axis=0)


def _poisoned_ids(num_nodes: int, poison_fraction: float) -> set:
    """Top poison_fraction of node ids load bad shards
    (ref: DistSys/main.go:836-845, honest.go:102-118). THE formula lives
    in tools/verdicts.poisoned_ids — one definition shared with the live
    runtime, the campaign plane's attacker draw, and every verdict
    reader; this name stays as the sim-side alias."""
    from biscotti_tpu.tools.verdicts import poisoned_ids

    return poisoned_ids(num_nodes, poison_fraction)


class Simulator:
    """N peers on one chip (vmapped) or across a mesh (shard_map)."""

    def __init__(self, cfg: BiscottiConfig, model: Optional[Model] = None,
                 metrics=None):
        self.cfg = cfg
        # optional telemetry registry (telemetry.MetricsRegistry): run()
        # then feeds a per-round duration histogram and height/error
        # gauges — the simulator's rounds land on the same scrapeable
        # plane as the live runtime's (the CLI's --metrics-out wires this)
        self.metrics = metrics
        self.model = model or model_for_dataset(
            cfg.dataset, getattr(cfg, "model_name", ""))
        self.mode = "sgd" if self.model.name == "logreg" else "grad"
        self.num_params = self.model.num_params
        n = cfg.num_nodes

        # set-up and per-round host phases, also `biscotti:<name>` spans in
        # a profiler trace (sim.shards / sim.stack / sim.to_device /
        # sim.build; sim.round.args / sim.round.dispatch)
        self.phases = PhaseClock()

        poisoned = _poisoned_ids(n, cfg.poison_fraction)
        xs, ys = [], []
        with self.phases.phase("sim.shards"):
            for i in range(n):
                shard = ds.load_shard(
                    cfg.dataset, ds.shard_name(cfg.dataset, i, i in poisoned))
                xs.append(shard["x_train"])
                ys.append(shard["y_train"])
            test = ds.load_shard(cfg.dataset, f"{cfg.dataset}_test")
            attack = ds.load_shard(cfg.dataset, f"{cfg.dataset}_digit1")
        rows = min(len(x) for x in xs)
        with self.phases.phase("sim.stack"):
            x_host = np.stack([x[:rows] for x in xs])  # [N, rows, d]
            y_host = np.stack([y[:rows] for y in ys])  # [N, rows]
        # the hand-over only: the copy itself runs on the runtime's threads
        # after jnp.asarray returns, beside whatever the host does next
        # (tracing and fetching the first round). Waiting for it here was
        # tried (PERF.md, PR 24): 22 s at 3,383 peers, and 5-6 s more of
        # set-up than not waiting
        with self.phases.phase("sim.to_device"):
            self.x = jnp.asarray(x_host)
            self.y = jnp.asarray(y_host)
            self.x_val = jnp.asarray(test["x_test"])
            self.y_val = jnp.asarray(test["y_test"])
            self.x_attack = jnp.asarray(attack["x_test"])
            self.y_attack = jnp.asarray(attack["y_test"])
        self.rows = rows

        with self.phases.phase("sim.build"):
            self.root_key = jax.random.PRNGKey(cfg.seed)
            alpha = cfg.logreg_alpha
            self._step = local_step_fn(self.model, self.mode,
                                       clip=cfg.grad_clip, alpha=alpha)
            self._use_noise = cfg.noising or cfg.dp_in_model
            self._noise_eps = cfg.epsilon if self._use_noise else 0.0
            self._noise_scale = dp_noise.sigma_for(self._noise_eps, cfg.delta)
            self._dp_mechanism = cfg.dp_mechanism
            self._noise_alpha = alpha if self.mode == "sgd" else 1.0
            self._round_step_raw, noised_raw = self._build_round_step()
            self._round_step_jit = jax.jit(self._round_step_raw,
                                           donate_argnums=(0, 1))
            self._noised_jit = jax.jit(noised_raw)

        def round_step(w, stake, it):
            with self.phases.phase("sim.round.args"):
                seed = jnp.asarray(self.cfg.seed, jnp.int32)
            with self.phases.phase("sim.round.dispatch"):
                return self._round_step_jit(w, stake, it, seed,
                                            self.x, self.y,
                                            self.x_val, self.y_val)

        self.round_step = round_step

    # ------------------------------------------------------------------ build

    def _contributors(self, key: jax.Array) -> jax.Array:
        """Per-round contributor subset of static size NUM_SAMPLES. The
        reference's verifier acts on the first KRUM_UPDATETHRESH arrivals
        (ref: krum.go:296); arrival order is scheduling noise, which a random
        subset models."""
        n, s = self.cfg.num_nodes, self.cfg.num_samples
        if s >= n:
            return jnp.arange(n)
        return jax.random.choice(key, n, (s,), replace=False)

    def _peer_noise(self, key: jax.Array) -> jax.Array:
        """Fresh per-round draw, distribution-identical to the reference's
        presampled bank row (Σ_batch σ·N(0,1) scaled by −α/batch; ref:
        client_obj.py:59-67,97-98). Presampling a [N,iters,d] bank would cost
        GBs of HBM at CNN sizes for zero statistical difference."""
        b = self.cfg.batch_size
        if self._dp_mechanism == "mcmc13":
            # Song&Sarwate'13 mechanism: fresh exact draw from the
            # MCMC path's stationary density (dp_noise.knorm_draw; the
            # per-peer trainer runs the chain itself for emcee parity)
            draw = dp_noise.knorm_draw(key, self._noise_eps, 1,
                                       self.num_params)[0]
        else:
            draw = self._noise_scale * math.sqrt(b) * jax.random.normal(
                key, (self.num_params,), jnp.float32
            )
        return (-self._noise_alpha / b) * draw

    def _one_delta(self, w: jax.Array, key: jax.Array, xi: jax.Array,
                   yi: jax.Array) -> jax.Array:
        """One peer's raw delta: minibatch indices, the rows, the step.
        Vmapped over the peers by both round programs."""
        with jax.named_scope("round_sample"):
            idx = sample_batch(key, self.rows, self.cfg.batch_size)
        with jax.named_scope("round_gather"):
            xb, yb = xi[idx], yi[idx]
        with jax.named_scope("round_grad"):
            return self._step(w, xb, yb)

    def _peer_updates(self, w: jax.Array, bkey: jax.Array, nkey: jax.Array,
                      ids: jax.Array, x: jax.Array, y: jax.Array,
                      gather: bool = False):
        """Raw and noised [S, d] deltas of the peers `ids` — shared by the
        one-chip step and the sharded one, so the two draw the same
        streams under the same scopes. The rows of (x, y) are those peers'
        shards, or with `gather` the whole stack that `ids` picks them
        from (the one-chip step's sampled contributors)."""
        with jax.named_scope("round_sample"):
            bkeys = jax.vmap(lambda i: jax.random.fold_in(bkey, i))(ids)
        if gather:
            with jax.named_scope("round_gather"):
                x, y = x[ids], y[ids]
        deltas = jax.vmap(self._one_delta, in_axes=(None, 0, 0, 0))(
            w, bkeys, x, y)  # [S, d]
        with jax.named_scope("round_noise"):
            if self._use_noise:
                nkeys = jax.vmap(lambda i: jax.random.fold_in(nkey, i))(ids)
                noise = jax.vmap(self._peer_noise)(nkeys)
            else:
                noise = jnp.zeros_like(deltas)
            return deltas, deltas + noise

    def _build_round_step(self):
        cfg = self.cfg
        model = self.model
        defense = cfg.defense if cfg.verification else Defense.NONE
        # cheap mirror of the live fault plane (cfg.fault_plan, runtime/
        # faults.py): with drop probability p, each contributor's round
        # frame is lost with p — deterministically in (fault seed, it, i),
        # so same seed ⇒ same degraded rounds here AND in the live runtime
        # sense (fewer contributors, no stake movement for the lost ones).
        # Semantics match the live system's dominant drop outcome: the
        # worker computed and verifiers scored the update (defense_mask
        # still sees it), but the miner-bound frame died, so it joins no
        # aggregate and earns no stake. Per-link structure is not modeled
        # — this is the ROUND-level agreement knob, not a transport sim.
        drop_p = cfg.fault_plan.drop if cfg.fault_plan.enabled else 0.0
        if drop_p > 0.0 and defense == Defense.TRIMMED_MEAN:
            raise ValueError(
                "fault_plan.drop is not supported with defense=TRIMMED_MEAN "
                "in the simulator: the trimmed aggregate has no per-update "
                "mask to carry the drops (run the live runtime for that)")
        fault_base = jax.random.PRNGKey(cfg.fault_plan.seed)

        # data tensors are ARGUMENTS, not closure captures: a captured jnp
        # array is baked into the HLO as a constant, which at CNN sizes
        # makes the program itself hundreds of MB (the [N, rows, d] peer
        # stack) and slow to compile. As arguments they stay
        # device-resident buffers. The SEED
        # is an argument for the same reason: a baked-in PRNGKey constant
        # would force a fresh trace+compile per seed, making multi-seed
        # sweeps (eval_poison --seeds) pay the compile N times.
        seed_base = jax.random.PRNGKey(0)  # same constant for every sim

        def noised_updates(w, it, seed, x, y):
            """Round `it`'s contributor ids with their raw and noised
            deltas — everything the round does before the defence."""
            with jax.named_scope("round_sample"):
                rkey = jax.random.fold_in(
                    jax.random.fold_in(seed_base, seed), it)
                ckey, bkey, nkey = jax.random.split(rkey, 3)
                cidx = self._contributors(ckey)
            deltas, noised = self._peer_updates(w, bkey, nkey, cidx, x, y,
                                                gather=True)
            return cidx, deltas, noised

        def round_step(w, stake, it, seed, x, y, x_val, y_val):
            cidx, deltas, noised = noised_updates(w, it, seed, x, y)
            s = cidx.shape[0]
            mask = defense_mask(defense, model, w, noised, x_val,
                                y_val, cfg.roni_threshold,
                                default_num_adversaries(s))
            with jax.named_scope("round_ledger"):
                delta_stake = jnp.where(mask, cfg.stake_unit,
                                        -cfg.stake_unit)
                if drop_p > 0.0:
                    dkey = jax.random.fold_in(fault_base, it)
                    keep = jax.random.uniform(dkey, (s,)) >= drop_p
                    mask = mask & keep  # lost frames join no aggregate …
                    delta_stake = jnp.where(keep, delta_stake, 0)  # … or ledger
            with jax.named_scope("round_aggregate"):
                w_next = w + masked_aggregate(mask, deltas, noised,
                                              cfg.dp_in_model, defense,
                                              cfg.trim_fraction)
            with jax.named_scope("round_ledger"):
                stake_next = stake.at[cidx].add(delta_stake)
            with jax.named_scope("round_eval"):
                err = model.error_flat(w_next, x_val, y_val)
            return w_next, stake_next, mask, err

        return round_step, noised_updates

    def round_hlo(self) -> str:
        """The optimized HLO text of the round program at this simulator's
        own shapes, every instruction carrying its `op_name` with the
        STAGES scopes: what a device trace's instruction names (`fusion.3`)
        are joined against (docs/OBSERVABILITY.md, "Device trace").

        Lowered from shapes (no buffer is touched, nothing is donated) with
        `it` as the weakly typed Python int that run() passes, and compiled
        OUTSIDE the persistent compile cache: its key ignores scope
        metadata (`jax_compilation_cache_include_metadata_in_key` is off),
        so a cache filled by an older tree would hand back that tree's
        executable, and its text that tree's names. For the same reason it
        is traced anew, through a wrapper of its own: JAX keeps the
        executable it fetched on the memoized lowering of
        `_round_step_jit`, and would hand that back uncompiled. A compile
        costs seconds: call it after a timed window, never in one."""
        from jax.experimental.compilation_cache import compilation_cache

        def round_step(*args):  # the name the program and its scopes carry
            return self._round_step_raw(*args)

        w, stake = jax.eval_shape(self.init_state)
        it = jax.ShapeDtypeStruct((), jax.dtypes.canonicalize_dtype(int),
                                  weak_type=True)
        seed = jax.ShapeDtypeStruct((), jnp.int32)
        data = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                for a in (self.x, self.y, self.x_val, self.y_val)]
        lowered = jax.jit(round_step, donate_argnums=(0, 1)).lower(
            w, stake, it, seed, *data)
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()  # the switch is read once a process
        try:
            return lowered.compile().as_text()
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()

    # ------------------------------------------------------------------ run

    def init_state(self):
        w = jnp.zeros((self.num_params,), jnp.float32)
        stake = jnp.full((self.cfg.num_nodes,), self.cfg.default_stake, jnp.int32)
        return w, stake

    def run(self, num_rounds: Optional[int] = None, log_every: int = 1,
            stop_at_convergence: bool = True):
        """Python round loop over the jitted step; returns (w, stake, logs).
        Log rows mirror the reference's parsed node-0 output so eval tooling
        is directly comparable (BASELINE.md)."""
        if num_rounds is None:
            num_rounds = self.cfg.max_iterations
        w, stake = self.init_state()
        logs: List[RoundLog] = []
        m = self.metrics
        for it in range(num_rounds):
            t0 = time.perf_counter()
            w, stake, mask, err = self.round_step(w, stake, it)
            if m is not None:
                jax.block_until_ready(w)  # charge the round its device time
                m.histogram("biscotti_sim_round_seconds",
                            "simulator device-round wall clock").observe(
                    time.perf_counter() - t0)
                m.gauge("biscotti_sim_round_height",
                        "simulator rounds completed").set(it + 1)
            if it % log_every == 0 or it == num_rounds - 1:
                e = float(err)
                logs.append(RoundLog(it, e, time.time(), int(mask.sum())))
                if m is not None:
                    m.gauge("biscotti_sim_error",
                            "simulator latest test error").set(e)
                if stop_at_convergence and e < self.cfg.convergence_error:
                    break
        return w, stake, logs

    def run_scan(self, num_rounds: Optional[int] = None,
                 seed: Optional[int] = None):
        """Whole training as ONE compiled XLA program (`lax.scan` over
        rounds) — no host in the loop at all. Upper bound of the TPU design;
        nothing in the reference's architecture can express this. `seed`
        overrides cfg.seed without rebuilding the Simulator (it is a traced
        argument, so multi-seed sweeps reuse one compiled executable)."""
        if num_rounds is None:
            num_rounds = self.cfg.max_iterations
        w, stake = self.init_state()
        step = self._round_step_raw

        # cache the jitted scan per run length: a fresh @jax.jit wrapper
        # each call would empty the in-memory jit cache and re-trace the
        # whole N-round program per seed, defeating the seed-as-argument
        # design
        full = getattr(self, "_scan_cache", {}).get(num_rounds)
        if full is None:

            @jax.jit
            def full(w, stake, seed, x, y, x_val, y_val):
                def body(carry, it):
                    w, stake = carry
                    w, stake, mask, err = step(w, stake, it, seed, x, y,
                                               x_val, y_val)
                    return (w, stake), (err, jnp.sum(mask))

                return jax.lax.scan(body, (w, stake),
                                    jnp.arange(num_rounds))

            self._scan_cache = getattr(self, "_scan_cache", {})
            self._scan_cache[num_rounds] = full

        s = self.cfg.seed if seed is None else seed
        (w, stake), (errs, accepted) = full(
            w, stake, jnp.asarray(s, jnp.int32), self.x, self.y,
            self.x_val, self.y_val)
        return w, stake, np.asarray(errs), np.asarray(accepted)

    # ------------------------------------------------------------------ metrics

    def noised_updates(self, w, it: int) -> jax.Array:
        """The [S, d] noised deltas round `it` hands the verifier
        committee at weights `w` — the defence's actual input, for
        checking a scoring kernel against an oracle on it."""
        return self._noised_jit(w, it, jnp.asarray(self.cfg.seed, jnp.int32),
                                self.x, self.y)[2]

    def test_error(self, w) -> float:
        return float(self.model.error_flat(jnp.asarray(w), self.x_val, self.y_val))

    def attack_rate(self, w) -> float:
        return float(self.model.error_flat(jnp.asarray(w), self.x_attack,
                                           self.y_attack))

    def attack_success_rate(self, w) -> float:
        """Stricter source→target metric: fraction of attack-source samples
        predicted as exactly the attack target class (the 1→7 rate;
        trainer.attack_success_rate analogue — not inflated by benign
        confusion the way attack_rate's 1−accuracy is)."""
        target = ds.spec(self.cfg.dataset).attack_target
        logits = self.model.apply_flat(jnp.asarray(w), self.x_attack)
        pred = jnp.argmax(logits, axis=-1)
        return float(jnp.mean((pred == target).astype(jnp.float32)))


# ---------------------------------------------------------------- sharded path


def make_sharded_round_step(sim: Simulator, mesh: jax.sharding.Mesh,
                            axis: str = "peers"):
    """Peers-across-devices round step via shard_map.

    Every peer contributes (S = N — contributor sampling is a single-chip
    refinement); the peer axis of (x, y) is sharded over `axis`, the model is
    replicated. Cross-device traffic is exactly one all_gather of the [N,d]
    noised deltas (Krum needs the full set) and one psum of the masked local
    aggregate — the ICI-collective replacement for the reference's
    TCP update fan-out (ref: SURVEY §5.8).

    Randomness derives from the same seed-as-argument scheme as the
    single-chip round_step — fold_in(fold_in(PRNGKey(0), seed), it) — so
    `run_step(w, it, seed=...)` overrides behave identically on both paths
    (previously this path read sim.root_key and seed overrides silently
    no-opped on sharded runs; ADVICE round 5). The fault plane's drop-mask
    knob (cfg.fault_plan.drop) is mirrored here too — see _build_round_step.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    step = sharded_round_step_fn(sim, mesh, axis)
    sharding = NamedSharding(mesh, P(axis))
    x_sh = jax.device_put(sim.x, sharding)
    y_sh = jax.device_put(sim.y, sharding)

    def run_step(w, it, seed: Optional[int] = None):
        s = sim.cfg.seed if seed is None else seed
        return step(w, x_sh, y_sh, jnp.asarray(it),
                    jnp.asarray(s, jnp.int32))

    run_step.x = x_sh  # the sharded peer stack: lets a caller check placement
    return run_step


def sharded_round_step_fn(sim: Simulator, mesh: jax.sharding.Mesh,
                          axis: str = "peers"):
    """The jitted program behind make_sharded_round_step:
    `(w, x, y, it, seed) -> (w', mask, err)` with (x, y) sharded over
    `axis`. No data is placed, so it can also be lowered ahead of time for
    a mesh of devices this host does not have
    (tests/test_tpu_lowering.py)."""
    from jax.sharding import PartitionSpec as P

    cfg = sim.cfg
    model = sim.model
    n = cfg.num_nodes
    defense = cfg.defense if cfg.verification else Defense.NONE
    f = default_num_adversaries(n)
    seed_base = jax.random.PRNGKey(0)  # same constant as _build_round_step
    drop_p = cfg.fault_plan.drop if cfg.fault_plan.enabled else 0.0
    fault_base = jax.random.PRNGKey(cfg.fault_plan.seed)

    def local_deltas(w, x_loc, y_loc, it, seed):
        with jax.named_scope("round_sample"):
            pid = jax.lax.axis_index(axis)
            n_loc = x_loc.shape[0]
            gids = pid * n_loc + jnp.arange(n_loc)
            rkey = jax.random.fold_in(jax.random.fold_in(seed_base, seed),
                                      it)
            bkey, nkey = jax.random.split(rkey)
        return sim._peer_updates(w, bkey, nkey, gids, x_loc, y_loc)

    def sharded_step(w, x_loc, y_loc, it, seed):
        deltas, noised = local_deltas(w, x_loc, y_loc, it, seed)
        all_noised = jax.lax.all_gather(noised, axis, tiled=True)  # [N, d]
        mask = defense_mask(defense, model, w, all_noised, sim.x_val,
                            sim.y_val, cfg.roni_threshold, f)
        if drop_p > 0.0:
            # mirror of the live fault plane's frame drops: the accepted
            # update whose miner-bound frame is lost contributes nothing
            # (see _build_round_step for the exact shared semantics)
            with jax.named_scope("round_ledger"):
                dkey = jax.random.fold_in(fault_base, it)
                mask = mask & (jax.random.uniform(dkey, (n,)) >= drop_p)
        with jax.named_scope("round_aggregate"):
            pid = jax.lax.axis_index(axis)
            n_loc = deltas.shape[0]
            if defense == Defense.TRIMMED_MEAN:
                # order statistics need the FULL peer set: one more
                # all_gather (of the raw deltas) and the trimmed aggregate
                # is computed replicated — same collective budget class as
                # Krum's gather
                src = all_noised if cfg.dp_in_model else jax.lax.all_gather(
                    deltas, axis, tiled=True)
                agg = masked_aggregate(mask, src, src, cfg.dp_in_model,
                                       defense, cfg.trim_fraction)
            else:
                local_mask = jax.lax.dynamic_slice_in_dim(mask, pid * n_loc,
                                                          n_loc)
                local_agg = masked_aggregate(local_mask, deltas, noised,
                                             cfg.dp_in_model)
                agg = jax.lax.psum(local_agg, axis)
            w_next = w + agg
        with jax.named_scope("round_eval"):
            err = model.error_flat(w_next, sim.x_val, sim.y_val)
        return w_next, mask, err

    mapped = jax.shard_map(
        sharded_step, mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(mapped)


# ------------------------------------------------------------------- CLI


def main(argv=None) -> int:
    """Standalone federated simulation CLI — the reference's ml_main_* file
    family (ref: ML/Pytorch/ml_main_mnist.py:24-60, ml_main_diffpriv.py,
    _credit/_cifar/_lfw variants) as one parameterized entry point, with
    the whole round jitted instead of a Python peer loop."""
    import argparse
    import json as _json

    from biscotti_tpu.config import BiscottiConfig

    ap = argparse.ArgumentParser(description="in-process N-peer simulator")
    BiscottiConfig.add_args(ap)
    ap.add_argument("--rounds", type=int, default=0,
                    help="override max-iterations for the run")
    ap.add_argument("--scan", action="store_true",
                    help="compile the WHOLE training run as one XLA program")
    ap.add_argument("--csv", default="",
                    help="write iteration,error,timestamp rows here")
    ap.add_argument("--metrics-out", default="",
                    help="write a Prometheus text page of the run's "
                         "telemetry (round histogram, height/error gauges) "
                         "here; non-scan runs only")
    ns = ap.parse_args(argv)
    if ns.metrics_out and ns.scan:
        ap.error("--metrics-out requires a non-scan run (run_scan compiles "
                 "the whole training into one XLA program; there are no "
                 "per-round host observations to export)")
    from biscotti_tpu.utils import jaxenv

    jaxenv.configure_compile_cache()
    cfg = BiscottiConfig.from_args(ns)
    registry = None
    if ns.metrics_out:
        from biscotti_tpu.telemetry import MetricsRegistry

        registry = MetricsRegistry()
    sim = Simulator(cfg, metrics=registry)
    rounds = ns.rounds or cfg.max_iterations
    if ns.scan:
        w, stake, errs, accepted = sim.run_scan(rounds)
        logs = [RoundLog(i, float(e), time.time(), int(a))
                for i, (e, a) in enumerate(zip(errs, accepted))]
    else:
        w, stake, logs = sim.run(rounds)
    if ns.csv:
        with open(ns.csv, "w") as f:
            f.write("\n".join(l.csv() for l in logs) + "\n")
    if registry is not None:
        with open(ns.metrics_out, "w") as f:
            f.write(registry.render())
    summary = {
        "dataset": cfg.dataset, "nodes": cfg.num_nodes,
        "rounds_run": len(logs),
        "final_error": logs[-1].error if logs else float("nan"),
        "test_error": sim.test_error(w),
        "attack_rate": sim.attack_rate(w),
        **jaxenv.device_info(),
    }
    print(_json.dumps(summary))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
