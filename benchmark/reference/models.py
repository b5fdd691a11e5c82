"""Plain float64 numpy model: forward, loss and gradient of upstream's
softmax regression, and the gradient clip. Written from upstream's files
(ML/Pytorch/softmax_model.py, client.py:38-65); imports nothing of
biscotti_tpu. A later configuration's model is a new file beside this one.

Flat layout (the wire vector), stated here and tested against the program:
  softmax    [b (10), w (784 x 10) row-major]

`quant` is the precision control: every operand and every result of every
operation passes through it, and every sum inside one operation (a matmul,
a reduction) is exact. None is float64; `bf16` rounds to bfloat16: what a
bfloat16 implementation that accumulates in float32 would hold.
"""

import numpy as np


def bf16(a):
    import ml_dtypes

    return np.asarray(a).astype(ml_dtypes.bfloat16).astype(np.float64)


def _ident(a):
    return np.asarray(a, np.float64)


def quantizer(precision):
    if precision in (None, "float64"):
        return _ident
    if precision in ("bfloat16", "bfloat16_f32acc"):
        return bf16
    raise ValueError(f"no reference precision {precision!r}")


LAYOUTS = {
    "softmax": [("b", (10,)), ("w", (784, 10))],
}


def num_params(model):
    return sum(int(np.prod(s)) for _, s in LAYOUTS[model])


def unflatten(model, flat):
    out, at = {}, 0
    for name, shape in LAYOUTS[model]:
        n = int(np.prod(shape))
        out[name] = np.asarray(flat[at:at + n], np.float64).reshape(shape)
        at += n
    assert at == len(flat), (at, len(flat))
    return out


def leaves(model, flat):
    """(name, slice of the flat vector) per parameter leaf."""
    out, at = [], 0
    for name, shape in LAYOUTS[model]:
        n = int(np.prod(shape))
        out.append((name, flat[..., at:at + n]))
        at += n
    return out


def init_weights(model, seed):
    """The benchmark's seeded starting weights: fan-in scaled normal,
    float32 values (what the program is handed)."""
    rng = np.random.default_rng([int(seed), 0xB15C])
    parts = []
    for name, shape in LAYOUTS[model]:
        if name.endswith("b"):
            parts.append(np.zeros(shape).ravel())
        else:
            fan_in = int(np.prod(shape[:-1]))
            parts.append((rng.normal(size=shape) / np.sqrt(fan_in)).ravel())
    return np.concatenate(parts).astype(np.float32)


def _log_softmax(z):
    m = z.max(axis=-1, keepdims=True)
    return z - m - np.log(np.exp(z - m).sum(axis=-1, keepdims=True))


def logits(model, flat, x, q=_ident):
    p = {k: q(v) for k, v in unflatten(model, flat).items()}
    return q(q(q(x) @ p["w"]) + p["b"])


def error(model, flat, x, y, q=_ident):
    """1 - accuracy (upstream client.py:136-160)."""
    return float(np.mean(np.argmax(logits(model, flat, x, q), -1) != y))


def loss_and_grad(model, flat, x, y, q=_ident):
    """Mean cross-entropy of each peer's batch and its flat gradient, for
    a stack of peers at the same weights: x [P, B, 784], y [P, B] ->
    (loss [P], grad [P, d]). A single batch ([B, 784], [B]) gives
    (loss, grad [d])."""
    if np.ndim(x) == 2:
        loss, g = loss_and_grad(model, flat, np.asarray(x)[None],
                                np.asarray(y)[None], q)
        return float(loss[0]), g[0]
    p = {k: q(v) for k, v in unflatten(model, flat).items()}
    x = q(x)
    peers, b = x.shape[:2]
    rows = x.reshape(peers * b, -1)
    lg = q(q(rows @ p["w"]) + p["b"])
    logp = q(_log_softmax(lg))
    picked = logp[np.arange(peers * b), np.asarray(y).ravel()]
    loss = -picked.reshape(peers, b).mean(axis=1)
    dl = np.exp(logp)
    dl[np.arange(peers * b), np.asarray(y).ravel()] -= 1.0
    dl = q(dl / b)
    g = {"w": q(np.matmul(rows.reshape(peers, b, -1).transpose(0, 2, 1),
                          dl.reshape(peers, b, -1))),
         "b": q(dl.reshape(peers, b, -1).sum(axis=1))}
    flat_g = np.concatenate([g[name].reshape(peers, -1)
                             for name, _ in LAYOUTS[model]], axis=1)
    return loss, flat_g


def clip_by_global_norm(g, max_norm, q=_ident):
    """g [..., d] scaled so that no row's norm passes max_norm."""
    n = q(np.sqrt(q(np.sum(q(g * g), axis=-1, keepdims=True))))
    return q(g * np.minimum(1.0, max_norm / np.maximum(n, 1e-12)))


def local_delta(model, flat, x, y, clip, q=_ident):
    """Upstream's torch step: delta = -clip(grad CE(w; minibatch)); for a
    stack of peers ([P, B, 784]) one delta a peer."""
    _, g = loss_and_grad(model, flat, x, y, q)
    return -clip_by_global_norm(g, clip, q)
