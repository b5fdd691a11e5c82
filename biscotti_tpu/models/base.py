"""Functional model abstraction.

The reference moves *flat* float vectors across every boundary (Go ⇄ Python,
peer ⇄ peer): models expose `reshape` to unflatten (ref:
ML/Pytorch/softmax_model.py:20-24, mnist_cnn_model.py:43-67). We keep that
contract — the framework's wire unit is a flat vector — but derive
flatten/unflatten automatically from the param pytree with
`jax.flatten_util.ravel_pytree`, so every model gets it for free and layouts
can never drift from the init.

All apply/loss functions are pure and jittable; `vmap` over the params axis
is how N peers train in one XLA program (see parallel/sim.py).

A model may hold a FROZEN pytree beside the trainable one (ROADMAP B0): a
base the round neither commits, noises, scores nor sums. `num_params`,
`flatten`, `unravel` and the wire vector see the trainable leaves only;
`apply`, `loss` and `error_flat` take the frozen tree as an argument, so a
jitted caller can hold it once on the device and pass it in. Every
classifier's frozen tree is empty (`{}`: no leaf, no argument of the
compiled program). A model also DECLARES what the round cannot see from
its parameter vector: its inputs (`token_input`), the local step its peers
take (`step_rule`, one of STEP_RULES; models/trainer.py turns it into the
update and the scale of the DP noise), and, where the peers of a round
should go through the model as one batch, how (`peer_losses`,
`step_bytes`). Nothing reads a model's name to decide any of it.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree


# delta of one local step from the minibatch gradient g (models/trainer.py):
#   "grad"         -clip_C(g)        upstream's torch stack (client.py:38-65)
#   "sgd"          -alpha * g        upstream's numpy logreg (cfg.logreg_alpha)
#   "clipped_sgd"  -eta * clip_C(g)  eta = cfg.learning_rate, C = cfg.grad_clip
STEP_RULES = ("grad", "sgd", "clipped_sgd")


@dataclass(frozen=True)
class Model:
    name: str
    d_in: int
    n_classes: int
    init: Callable[[jax.Array], Any]  # key -> trainable params pytree
    # (params, x, frozen) -> logits; x [B, d_in] float rows, or int32[B, T]
    # token windows where `token_input` (d_in is then T, n_classes the
    # vocabulary held, and y holds a label a position)
    apply: Callable[[Any, jax.Array, Any], jax.Array]
    loss: Callable[[Any, jax.Array, jax.Array, Any], jax.Array]  # mean scalar
    num_params: int  # of the trainable tree: the wire vector's length
    unravel: Callable[[jax.Array], Any] = field(repr=False, default=None)
    step_rule: str = "grad"  # one of STEP_RULES
    token_input: bool = False
    # key -> the frozen pytree, drawn leaf by leaf where it will live;
    # None: there is none (`frozen()` gives the empty tree)
    init_frozen: Optional[Callable[[jax.Array], Any]] = field(
        repr=False, default=None)
    # (params with a leading peer axis on every leaf, x [P, B, ...],
    # y [P, B, ...], frozen) -> (each peer's mean loss [P], counts): the
    # peers of a block as ONE batch through the model. None: the round
    # vmaps `loss` over the peers
    peer_losses: Optional[Callable] = field(repr=False, default=None)
    # batch rows -> bytes one peer's step holds live at its peak; None: the
    # round takes all its peers at once (models/peer_step.peer_block)
    step_bytes: Optional[Callable[[int], int]] = field(repr=False,
                                                       default=None)
    # what a caller may want to know of the model and cannot see from the
    # vector (models/laguna.py: `config`); no part of its identity
    info: Mapping[str, Any] = field(default_factory=dict, repr=False,
                                    compare=False)

    def frozen(self, key: jax.Array) -> Any:
        return {} if self.init_frozen is None else self.init_frozen(key)

    def flat_init(self, key: jax.Array) -> jax.Array:
        return ravel_pytree(self.init(key))[0].astype(jnp.float32)

    def flatten(self, params: Any) -> jax.Array:
        return ravel_pytree(params)[0].astype(jnp.float32)

    def apply_flat(self, flat_w: jax.Array, x: jax.Array,
                   frozen: Any = None) -> jax.Array:
        return self.apply(self.unravel(flat_w), x, frozen)

    def loss_flat(self, flat_w: jax.Array, x: jax.Array, y: jax.Array,
                  frozen: Any = None) -> jax.Array:
        return self.loss(self.unravel(flat_w), x, y, frozen)

    def error_flat(self, flat_w: jax.Array, x: jax.Array, y: jax.Array,
                   frozen: Any = None) -> jax.Array:
        """1 − accuracy (ref: ML/Pytorch/client.py:136-160); of a token
        model, over every position of every window."""
        pred = jnp.argmax(self.apply_flat(flat_w, x, frozen), axis=-1)
        return jnp.mean((pred != y).astype(jnp.float32))


def make_model(name, d_in, n_classes, init, apply, loss,
               step_rule: str = "grad", token_input: bool = False,
               init_frozen=None, peer_losses=None, step_bytes=None,
               info: Mapping[str, Any] = ()) -> Model:
    """Bind flatten/unflatten to a canonical zero-key init layout (from
    shapes alone: no parameter is drawn to learn it). Without
    `init_frozen` the model is a classifier written as `apply(params, x)`
    and `loss(params, x, y)`: it gets the uniform signature, with a frozen
    tree it never reads."""
    if step_rule not in STEP_RULES:
        raise ValueError(f"unknown step rule {step_rule!r}; have {STEP_RULES}")
    if init_frozen is None:
        plain_apply, plain_loss = apply, loss

        def apply(params, x, frozen=None):
            return plain_apply(params, x)

        def loss(params, x, y, frozen=None):
            return plain_loss(params, x, y)

    found = []

    def probe(key):
        flat, unravel = ravel_pytree(init(key))
        found.append(unravel)  # closes over shapes and offsets only
        return flat

    flat = jax.eval_shape(probe, jax.random.PRNGKey(0))
    return Model(
        name=name, d_in=d_in, n_classes=n_classes, init=init, apply=apply,
        loss=loss, num_params=int(flat.size), unravel=found[0],
        step_rule=step_rule, token_input=token_input,
        init_frozen=init_frozen, peer_losses=peer_losses,
        step_bytes=step_bytes, info=dict(info),
    )


def cross_entropy(logits: jax.Array, y: jax.Array) -> jax.Array:
    """Mean CE over the batch (ref: nn.CrossEntropyLoss, client.py:29)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None].astype(jnp.int32), axis=-1))


def multiclass_hinge(logits: jax.Array, y: jax.Array) -> jax.Array:
    """Crammer–Singer hinge for the SVM model (ref: ML/Pytorch/svm_model.py)."""
    yi = jnp.take_along_axis(logits, y[:, None].astype(jnp.int32), axis=-1)
    margins = jnp.maximum(0.0, 1.0 + logits - yi)
    margins = margins.at[jnp.arange(logits.shape[0]), y].set(0.0)
    return jnp.mean(jnp.sum(margins, axis=-1))
