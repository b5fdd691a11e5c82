#!/usr/bin/env python
"""Gradient-inversion privacy attack — reconstruct a peer's training
inputs from its submitted update, with and without DP noise.

This is the attack that motivates Biscotti's noising committee: a raw
gradient of the linear softmax model leaks the inputs (for batch 1 the
gradient row IS the input, scaled), and gradient-matching recovers them
for small batches. The reference demonstrates it in its prototype
(ref: CentralBlockML/code/inversion.py:1-8, plots
ML/code/inversion_compare.py); here the attack is a jitted optimization
(Adam on dummy inputs matching the observed delta) and the defense sweep
shows DP noise degrading reconstruction.

Metric: mean best-match cosine similarity between reconstructed and true
batch inputs, per ε ∈ {∞, 1.0, 0.1}. Artifact:
eval/results/inversion.json (+ .csv).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="mnist")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--out", default="eval/results")
    ap.add_argument("--platform", default="")
    args = ap.parse_args(argv)
    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform
    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    from biscotti_tpu.utils import jaxenv

    jaxenv.configure_compile_cache()
    import jax.numpy as jnp
    import numpy as np

    from biscotti_tpu.data import datasets as ds
    from biscotti_tpu.models.zoo import model_for_dataset
    from biscotti_tpu.ops import dp_noise

    model = model_for_dataset(args.dataset)
    shard = ds.load_shard(args.dataset, ds.shard_name(args.dataset, 0, False))
    x_true = jnp.asarray(shard["x_train"][: args.batch])
    y_true = jnp.asarray(shard["y_train"][: args.batch])
    w = jnp.zeros((model.num_params,), jnp.float32)

    grad_fn = jax.grad(model.loss_flat)
    g_clean = grad_fn(w, x_true, y_true)

    def reconstruct(observed, key):
        """Gradient matching (DLG-style): optimize dummy INPUTS whose
        gradient matches the observed update. Labels are assumed known —
        the attacker's best case (for CE they are recoverable from gradient
        sign structure anyway, iDLG), so the sweep isolates exactly what DP
        noise buys."""
        import optax

        d_in = x_true.shape[1]
        x0 = 0.01 * jax.random.normal(key, (args.batch, d_in))
        opt = optax.adam(0.1)
        state = opt.init(x0)

        def match_loss(x):
            g = grad_fn(w, x, y_true)
            diff = g - observed
            return jnp.sum(diff * diff)

        @jax.jit
        def step(x, s):
            loss, g = jax.value_and_grad(match_loss)(x)
            up, s = opt.update(g, s)
            return optax.apply_updates(x, up), s, loss

        x, loss = x0, jnp.inf
        for _ in range(args.steps):
            x, state, loss = step(x, state)
        return np.asarray(x), float(loss)

    def best_cosine(recon):
        xt = np.asarray(x_true)
        sims = []
        for i in range(xt.shape[0]):
            t = xt[i] / (np.linalg.norm(xt[i]) + 1e-12)
            best = max(
                float(np.abs(np.dot(t, r / (np.linalg.norm(r) + 1e-12))))
                for r in recon
            )
            sims.append(best)
        return float(np.mean(sims))

    sigma_ref = {
        "inf": 0.0,
        "1.0": dp_noise.sigma_for(1.0),
        "0.1": dp_noise.sigma_for(0.1),
    }
    rows = []
    key = jax.random.PRNGKey(7)
    for label, sigma in sigma_ref.items():
        nkey, rkey, key = jax.random.split(key, 3)
        observed = g_clean
        if sigma > 0:
            observed = g_clean + sigma * jax.random.normal(
                nkey, g_clean.shape) / args.batch
        recon, final_loss = reconstruct(observed, rkey)
        row = {"epsilon": label,
               "cosine_similarity": round(best_cosine(recon), 4),
               "match_loss": round(final_loss, 6)}
        rows.append(row)
        print(json.dumps(row))

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "inversion.csv"), "w") as f:
        f.write("epsilon,cosine_similarity\n")
        for r in rows:
            f.write(f"{r['epsilon']},{r['cosine_similarity']}\n")
    with open(os.path.join(args.out, "inversion.json"), "w") as f:
        json.dump({"experiment": "gradient_inversion",
                   "dataset": args.dataset, "batch": args.batch,
                   "steps": args.steps, "rows": rows,
                   "data_note": "synthetic shards (zero-egress env)"},
                  f, indent=1)
    # DP must measurably degrade reconstruction
    by = {r["epsilon"]: r["cosine_similarity"] for r in rows}
    ok = by["inf"] > by["0.1"]
    print(json.dumps({"summary": "dp_degrades_inversion", "ok": ok,
                      "clean": by["inf"], "eps0.1": by["0.1"]}))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
