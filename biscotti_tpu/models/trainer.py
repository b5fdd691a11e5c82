"""Per-peer trainer — the framework's internal replacement for the reference's
embedded-Python bridge API (init / privateFun / getNoise / roni / getTestErr /
get17AttackRate; ref: ML/Pytorch/client_obj.py, DistSys/honest.go:204-324).

Three step rules: the two reference stacks', and one for models that a
unit-rate step would send to infinity. A model DECLARES its own
(`Model.step_rule`, models/base.py) and `step_rule(model, cfg)` reads it:

  * torch-parity ("grad"): delta = −clip₁₀₀(∇CE(w; minibatch))
    (ref: client.py:38-65 — backward + clip_grad_norm(100), no optimizer.step,
    privateFun returns −grad, client_obj.py:73-77)
  * logreg-parity ("sgd"): delta = −α·∇f(w; minibatch), α=1e-2, f the
    L2-regularized logistic loss (ref: logistic_model.py:113-140)
  * "clipped_sgd": delta = −η·clip_C(∇f(w; minibatch)), η = cfg.learning_rate,
    C = cfg.grad_clip; the DP noise is scaled by the same η, as "sgd"
    scales it by α

Everything below `Trainer.__init__` is jitted XLA; the minibatch draw is a
threefry `random.choice` folded from (seed, iteration) so peers are
deterministic given their id — required by the chain-equality oracle.

`local_step_fn` is exposed standalone (pure); `block_step_fn` is the same
rule over a block of peers, which models/peer_step.py walks for
parallel/sim.py and the hive's stepper: vmapped, or, where the model says
how its peers go through it as one batch (`Model.peer_losses`), as that
one batch.
"""

from __future__ import annotations

import zlib
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from biscotti_tpu.data import datasets as ds
from biscotti_tpu.models.base import Model
from biscotti_tpu.models.zoo import model_for_dataset
from biscotti_tpu.ops import dp_noise

GRAD_CLIP = 100.0  # default, ref: client.py:56; overridable via cfg.grad_clip
LOGREG_ALPHA = 1e-2  # default α, ref: logistic_model.py:12; overridable via cfg.logreg_alpha


def clip_by_global_norm(g: jax.Array, max_norm: float) -> jax.Array:
    n = jnp.linalg.norm(g)
    return g * jnp.minimum(1.0, max_norm / jnp.maximum(n, 1e-12))


def step_rule(model: Model, cfg) -> Tuple[str, float]:
    """(mode, rate) of the local step `model` declares, under `cfg`:
    `mode` is `local_step_fn`'s, `rate` scales the step (its `alpha`) AND
    the DP noise (1.0 where the rule has no rate)."""
    mode = model.step_rule
    return mode, {"grad": 1.0, "sgd": cfg.logreg_alpha,
                  "clipped_sgd": cfg.learning_rate}[mode]


def _delta_rule(mode: str, clip: float, alpha: float) -> Callable:
    """gradient -> delta of one peer."""
    if mode == "grad":
        return lambda g: -clip_by_global_norm(g, clip)
    if mode == "sgd":
        # model.loss is already (1/B)Σ data + λ/2‖w‖², whose gradient is
        # the reference's (1/B)·Xᵀres + λw (ref: logistic_model.py:100-106)
        return lambda g: -alpha * g
    if mode == "clipped_sgd":
        return lambda g: -alpha * clip_by_global_norm(g, clip)
    raise ValueError(f"unknown step mode {mode!r}")


def local_step_fn(model: Model, mode: str = "grad", clip: float = GRAD_CLIP,
                  alpha: float = LOGREG_ALPHA) -> Callable:
    """Pure per-peer update rule:
    (flat_w, x_batch, y_batch, frozen=None) -> flat_delta."""
    rule = _delta_rule(mode, clip, alpha)

    def step(flat_w, x, y, frozen=None):
        return rule(jax.grad(model.loss_flat)(flat_w, x, y, frozen))

    return step


def block_step_fn(model: Model, mode: str = "grad", clip: float = GRAD_CLIP,
                  alpha: float = LOGREG_ALPHA) -> Callable:
    """The same rule over a block of peers that share the weights:
    (flat_w, x [P, B, ...], y [P, B, ...], frozen) -> (deltas [P, d],
    counts). A classifier's block is its step vmapped (counts `{}`). A
    model with `peer_losses` takes the block's rows as ONE batch: the
    weights get a peer axis, and the gradient of the SUM of the peers'
    losses with respect to them is each peer's own, since no peer's loss
    reads another's rows."""
    if model.peer_losses is None:
        step = local_step_fn(model, mode, clip, alpha)

        def block(flat_w, xb, yb, frozen=None):
            return jax.vmap(step, in_axes=(None, 0, 0, None))(
                flat_w, xb, yb, frozen), {}

        return block

    rule = _delta_rule(mode, clip, alpha)

    def block(flat_w, xb, yb, frozen=None):
        def total(stacked):
            losses, counts = model.peer_losses(
                jax.vmap(model.unravel)(stacked), xb, yb, frozen)
            return jnp.sum(losses), counts

        stacked = jnp.broadcast_to(flat_w, (xb.shape[0],) + flat_w.shape)
        grads, counts = jax.grad(total, has_aux=True)(stacked)
        with jax.named_scope("peer_clip"):
            return jax.vmap(rule)(grads), counts

    return block


def sample_batch(key: jax.Array, n: int, batch_size: int) -> jax.Array:
    """Minibatch without replacement (ref: logistic_model.py:121-125,
    torch DataLoader shuffle)."""
    return jax.random.choice(key, n, (min(batch_size, n),), replace=False)


# Compiled-function cache shared by every Trainer with the same
# (model, step rule) — N peers of one cluster reuse ONE XLA executable per
# function instead of tracing N closures that differ only in their captured
# shard constants. At N=100 the per-peer closures serialized ~100 identical
# mnist compilations behind the GIL and stalled the first round for minutes;
# passing the shard as an argument makes the trace shape-polymorphic-enough
# (same shapes → same executable) and startup O(1) compilations.
_FN_CACHE: dict = {}


def _compiled_fns(model: Model, mode: str, clip: float, alpha: float,
                  cache_key=None):
    if cache_key is not None and cache_key in _FN_CACHE:
        return _FN_CACHE[cache_key]
    step = local_step_fn(model, mode, clip=clip, alpha=alpha)

    from functools import partial

    @partial(jax.jit, static_argnames=("batch_size",))
    def _private(flat_w, it, x_train, y_train, batch_key, frozen,
                 batch_size):
        k = jax.random.fold_in(batch_key, it)
        idx = sample_batch(k, x_train.shape[0], batch_size)
        return step(flat_w, x_train[idx], y_train[idx], frozen)

    @jax.jit
    def _err(flat_w, x, y, frozen):
        return model.error_flat(flat_w, x, y, frozen)

    @jax.jit
    def _roni(flat_w, delta, x, y, frozen):
        # score = err(w+δ) − err(w) on the local train split
        # (ref: client_obj.py:100-112; rejected if > 0.02, main.go:203-231)
        before = model.error_flat(flat_w, x, y, frozen)
        after = model.error_flat(flat_w + delta, x, y, frozen)
        return after - before

    fns = (_private, _err, _roni)
    if cache_key is not None:
        _FN_CACHE[cache_key] = fns
    return fns


# Shared eval-split device arrays: the test and attack splits are
# IDENTICAL for every peer of a dataset (datasets.load_shard memoizes the
# numpy, but jnp.asarray re-uploaded a fresh device buffer per Trainer) —
# co-hosted clusters paid N copies of the same 6 MB test split. Keyed on
# the dataset name; jax arrays are immutable, so sharing is safe.
_EVAL_CACHE: dict = {}


# A model's frozen tree is the same for every peer of a run (drawn from the
# run's seed, models/base.py): co-hosted Trainers share ONE copy.
_FROZEN_CACHE: dict = {}


def shared_frozen(model: Model, seed: int, sharding=None):
    """`model`'s frozen tree for the run seeded `seed`: the empty tree for
    a classifier, else drawn once a process from `PRNGKey(seed)` (what
    parallel/sim.py draws too). With a `sharding` (the hive's mesh,
    replicated) the process's copy MOVES there, so that whoever asks
    afterwards shares it and no device holds the tree twice."""
    if model.init_frozen is None:
        return {}
    key = (model.name, model.num_params, int(seed))
    if key not in _FROZEN_CACHE:
        _FROZEN_CACHE[key] = model.frozen(jax.random.PRNGKey(seed))
    if sharding is not None:
        _FROZEN_CACHE[key] = jax.device_put(_FROZEN_CACHE[key], sharding)
    return _FROZEN_CACHE[key]


def _shared_eval_arrays(dataset: str):
    if dataset not in _EVAL_CACHE:
        test = ds.load_shard(dataset, f"{dataset}_test")
        attack = ds.load_shard(dataset, f"{dataset}_digit1")
        _EVAL_CACHE[dataset] = (
            jnp.asarray(test["x_test"]), jnp.asarray(test["y_test"]),
            jnp.asarray(attack["x_test"]), jnp.asarray(attack["y_test"]))
    return _EVAL_CACHE[dataset]


class Trainer:
    """One peer's ML state: shard on device, shared jitted step/metric
    functions (see _compiled_fns).

    `light=True` (the hive runtime's co-hosted mode, runtime/hive.py)
    skips the per-peer train-shard upload and the DP-noise presample
    bank: a hive-hosted peer's SGD and noise draws are served by the
    shared HiveStepper, so duplicating them per agent would only burn
    the memory budget the hive exists to fit N≥1000 peers into. The
    eval splits (shared device buffers either way) and the compiled
    metric functions stay, so test_error / RONI / attack metrics work;
    private_fun / get_noise / train_error / roni raise loudly."""

    def __init__(self, dataset: str, shard: str, cfg=None, model: Model = None,
                 seed: int = None, light: bool = False):
        from biscotti_tpu.config import BiscottiConfig

        self.cfg = cfg or BiscottiConfig(dataset=dataset)
        self.dataset = dataset
        self.model = model or model_for_dataset(
            dataset, getattr(self.cfg, "model_name", ""))
        self.mode, self._rate = step_rule(self.model, self.cfg)
        self.frozen = shared_frozen(self.model, self.cfg.seed)
        self.batch_size = self.cfg.batch_size
        # Every stream is keyed on (config seed, shard identity) so peers
        # built with default args still get independent DP noise and batch
        # draws — the shard name is the peer identity.
        if seed is None:
            seed = zlib.crc32(shard.encode())
        self.seed = seed
        # optional telemetry registry (telemetry.MetricsRegistry), armed
        # by the embedding runtime: SGD steps and DP noise draws are
        # counted so cluster scrapes can attribute compute to peers.
        # Thread-safe (registry locks internally) — private_fun runs off
        # the event loop via asyncio.to_thread.
        self.metrics = None

        self.light = bool(light)
        if self.light:
            self.x_train = self.y_train = None
        else:
            shard_data = ds.load_shard(dataset, shard)
            self.x_train = jnp.asarray(shard_data["x_train"])
            self.y_train = jnp.asarray(shard_data["y_train"])
        (self.x_test, self.y_test,
         self.x_attack, self.y_attack) = _shared_eval_arrays(dataset)

        self.num_params = self.model.num_params
        base = jax.random.fold_in(jax.random.PRNGKey(self.cfg.seed), self.seed)
        noise_key, batch_key = jax.random.split(base)
        eps_live = (self.cfg.epsilon
                    if self.cfg.noising or self.cfg.dp_in_model else 0.0)
        self.noise_accept_rate = None
        if self.light:
            self.noise_samples = None
        elif self.cfg.dp_mechanism == "mcmc13":
            # Song&Sarwate'13 branch (ref: client_obj.py:44-57); served
            # through the same noise_at/get_noise surface as the Gaussian
            self.noise_samples, acc = dp_noise.mcmc_presample(
                noise_key, eps_live, self.cfg.noise_presample_iters,
                self.num_params)
            self.noise_accept_rate = float(acc) if eps_live > 0 else None
        else:
            self.noise_samples = dp_noise.presample(
                noise_key, eps_live, self.cfg.delta, self.batch_size,
                self.cfg.noise_presample_iters, self.num_params,
            )

        alpha = self._rate
        self._batch_key = batch_key
        # share compiled functions across peers of the same (zoo model,
        # step-rule) family; a caller-supplied custom model skips the cache
        cache_key = ((dataset, self.model.name, self.mode,
                      self.cfg.grad_clip, alpha)
                     if model is None else None)
        self._private, self._err_fn, self._roni_fn = _compiled_fns(
            self.model, self.mode, self.cfg.grad_clip, alpha,
            cache_key=cache_key)

    # ---- reference bridge API (honest.go:204-324 surface) ----

    def init_weights(self) -> np.ndarray:
        """Zero init, matching the genesis global model (ref: block.go:46-52)."""
        return np.zeros(self.num_params, dtype=np.float64)

    def _require_full(self, what: str) -> None:
        if self.light:
            raise RuntimeError(
                f"Trainer(light=True) holds no {what}: the hive's shared "
                "stepper serves SGD/noise for co-hosted peers "
                "(runtime/hive.py); construct a full Trainer for "
                "per-agent dispatch")

    def private_fun(self, flat_w: np.ndarray, iteration: int) -> np.ndarray:
        self._require_full("train shard")
        if self.metrics is not None:
            self.metrics.counter("biscotti_trainer_steps_total",
                                 "local SGD steps computed").inc()
        return np.asarray(
            self._private(jnp.asarray(flat_w, jnp.float32), iteration,
                          self.x_train, self.y_train, self._batch_key,
                          self.frozen, batch_size=min(self.batch_size,
                                         int(self.x_train.shape[0]))),
            dtype=np.float64,
        )

    def get_noise(self, iteration: int) -> np.ndarray:
        self._require_full("noise bank")
        if self.metrics is not None:
            self.metrics.counter("biscotti_noise_draws_total",
                                 "DP noise vectors served/consumed").inc()
        return np.asarray(
            dp_noise.noise_at(self.noise_samples, iteration, self.batch_size,
                              self._rate),
            dtype=np.float64,
        )

    def train_error(self, flat_w: np.ndarray) -> float:
        self._require_full("train shard")
        return float(self._err_fn(jnp.asarray(flat_w, jnp.float32),
                                  self.x_train, self.y_train, self.frozen))

    def test_error(self, flat_w: np.ndarray) -> float:
        return float(self._err_fn(jnp.asarray(flat_w, jnp.float32),
                                  self.x_test, self.y_test, self.frozen))

    def attack_rate(self, flat_w: np.ndarray) -> float:
        """Reference-faithful metric: 1 − accuracy on the attack-source split
        (ref: client.py:163-172 get17AttackRate is literally
        1 − accuracy_score on the digit-1 loader). Counts *any*
        misclassification of source-class samples."""
        return float(self._err_fn(jnp.asarray(flat_w, jnp.float32),
                                  self.x_attack, self.y_attack, self.frozen))

    def attack_success_rate(self, flat_w: np.ndarray) -> float:
        """Stricter 1→7 metric: fraction of attack-source samples predicted
        as exactly the attack target class (not inflated by benign
        confusion the way `attack_rate` can be)."""

        target = ds.spec(self.dataset).attack_target
        logits = self.model.apply_flat(jnp.asarray(flat_w, jnp.float32),
                                       self.x_attack, self.frozen)
        pred = jnp.argmax(logits, axis=-1)
        return float(jnp.mean((pred == target).astype(jnp.float32)))

    def roni(self, flat_w: np.ndarray, delta: np.ndarray) -> float:
        self._require_full("train shard")
        return float(self._roni_fn(jnp.asarray(flat_w, jnp.float32),
                                   jnp.asarray(delta, jnp.float32),
                                   self.x_train, self.y_train, self.frozen))
