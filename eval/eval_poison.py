#!/usr/bin/env python
"""Poisoning eval — label-flip attack rate vs poison fraction, defense sweep.

The reference's operating point is 30% label-flip poisoners with Krum and
`-ns=70 -ep=1.0` at 100 nodes (ref: eval/eval_poison/runEval.sh:9-16;
result figures poison_eval/posion_mnist_30_100*.pdf). This driver sweeps
the poison fraction with each requested defense, training each cell to
--rounds entirely on-device (`Simulator.run_scan`: the whole run is one
XLA program — the reference needed a 100-process fleet per cell), over
--seeds independent seeds (the seed is a traced argument, so every seed
reuses one compiled executable).

Per cell the artifact carries mean±std over seeds of: final_error,
attack_rate (the reference's 1−accuracy-on-source metric,
client.py:163-172), and the stricter attack_success_rate (fraction of
source-class samples predicted as exactly the target class — the true
1→7 rate, not inflated by benign confusion).

Defenses: KRUM (reference), MULTIKRUM / TRIMMED_MEAN (non-IID-robust
options, ops/robust_agg.py), RONI, NONE. TRIMMED_MEAN cells run with
secure_agg=False (config enforces the order-statistics-over-shares
incompatibility).

Artifacts: <stem>.csv (one row per seed×cell) and <stem>.json (aggregate
summary); stem is poison[/_<dataset>] or --tag.

Exit-code gate: the gate defense (first non-NONE in --defenses, or
--gate-defense) must separate from NONE at the 30% operating point —
with seeds>1, by more than the sum of their stds. Runs where the gate is
known to be uninformative (small n, @dir heterogeneity stress, robust
tasks where the attack doesn't bite) must say so EXPLICITLY with
--no-gate, which records gate_waived in the artifact instead of
silently passing (ADVICE r4).

Usage: python eval/eval_poison.py [--dataset mnist] [--nodes 100]
           [--rounds 100] [--seeds 3] [--defenses KRUM,NONE]
           [--no-gate] [--out eval/results]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# one verdict/outcome helper shared with the live attack matrix
# (eval/eval_attack_matrix.py) and the chaos harnesses — aggregation and
# the separation criterion must not fork between the sim sweep and the
# live matrix (tools/verdicts.py)
from biscotti_tpu.tools.verdicts import (agg_mean_std as _agg,  # noqa: E402
                                         separates)

POISON_FRACTIONS = [0.0, 0.10, 0.20, 0.30, 0.40]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="mnist")
    ap.add_argument("--nodes", type=int, default=100)
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--epsilon", type=float, default=1.0)
    ap.add_argument("--seeds", type=int, default=3,
                    help="independent seeds per cell; aggregates are "
                         "mean±std over seeds")
    ap.add_argument("--defenses", default="KRUM,NONE",
                    help="comma list of Defense members to sweep")
    ap.add_argument("--gate-defense", default="",
                    help="defense the exit-code gate checks against NONE "
                         "(default: first non-NONE in --defenses)")
    ap.add_argument("--trim-fraction", type=float, default=0.35)
    ap.add_argument("--noising", type=int, default=1,
                    help="1 = full-protocol sweep (committee DP noising at "
                         "--epsilon; verifiers judge NOISED copies — the "
                         "DistSys operating point, ref runEval.sh -ep=1.0). "
                         "0 = defense-geometry sweep: noising off, the "
                         "defense sees raw update geometry (the reference's "
                         "ML-layer poison evals, ml_main_mnist.py, run "
                         "without the noising protocol). At ε=1.0 and "
                         "d=7,850 the noise norm is ~14× the update norm, "
                         "so similarity/distance defenses are largely "
                         "masked in mode 1 — measured in the artifacts")
    ap.add_argument("--no-gate", action="store_true",
                    help="report-only run: record gate_waived instead of "
                         "gating (REQUIRED for small-n / @dir / "
                         "attack-robust configurations — the gate no "
                         "longer silently passes them)")
    ap.add_argument("--out", default="eval/results")
    ap.add_argument("--tag", default="",
                    help="artifact stem override (e.g. poison_digits_100), "
                         "so variant runs never clobber the canonical "
                         "artifacts")
    ap.add_argument("--platform", default="")
    args = ap.parse_args(argv)
    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform
    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    # persistent compile cache: cells with the same defense share one HLO
    # (data + seed are arguments), so the sweep compiles once per defense
    from biscotti_tpu.utils import jaxenv

    jaxenv.configure_compile_cache()

    from biscotti_tpu.config import BiscottiConfig, Defense
    from biscotti_tpu.parallel.sim import Simulator

    defenses = [Defense(d.strip()) for d in args.defenses.split(",") if d]
    if args.gate_defense and args.gate_defense not in [d.value
                                                       for d in defenses]:
        ap.error(f"--gate-defense {args.gate_defense!r} is not in "
                 f"--defenses {args.defenses!r}")
    seeds = list(range(1, args.seeds + 1))

    rows, seed_rows = [], []
    for poison in POISON_FRACTIONS:
        for defense in defenses:
            cfg = BiscottiConfig(
                dataset=args.dataset, num_nodes=args.nodes,
                poison_fraction=poison, defense=defense,
                verification=defense != Defense.NONE,
                secure_agg=defense != Defense.TRIMMED_MEAN,
                noising=bool(args.noising), epsilon=args.epsilon,
                sample_percent=0.70, seed=seeds[0],
                trim_fraction=args.trim_fraction,
            )
            sim = Simulator(cfg)
            errs, rates, succ, acc = [], [], [], []
            for s in seeds:
                w, stake, es, accepted = sim.run_scan(args.rounds, seed=s)
                errs.append(float(es[-1]))
                rates.append(sim.attack_rate(w))
                succ.append(sim.attack_success_rate(w))
                acc.append(float(accepted.mean()))
                seed_rows.append({
                    "poison": poison, "defense": defense.value, "seed": s,
                    "final_error": round(errs[-1], 4),
                    "attack_rate": round(rates[-1], 4),
                    "attack_success_rate": round(succ[-1], 4),
                    "mean_accepted": round(acc[-1], 1),
                })
            row = {"poison": poison, "defense": defense.value,
                   "seeds": len(seeds)}
            for name, vals in (("final_error", errs), ("attack_rate", rates),
                               ("attack_success_rate", succ),
                               ("mean_accepted", acc)):
                row[name], row[f"{name}_std"] = _agg(vals)
            rows.append(row)
            print(json.dumps(row))

    from biscotti_tpu.data.datasets import (dirichlet_alpha,
                                            disjoint_shard_capacity,
                                            spec as dataset_spec)

    os.makedirs(args.out, exist_ok=True)
    # mnist keeps the historical bare names; other datasets get a suffix so
    # real-data runs (digits/cancer) sit alongside the synthetic artifacts
    # (@dir heterogeneity suffixes become _dir in file stems)
    stem = args.tag or ("poison" if args.dataset == "mnist"
                        else f"poison_{args.dataset.replace('@', '_')}")
    cols = ["poison", "defense", "seed", "final_error", "attack_rate",
            "attack_success_rate", "mean_accepted"]
    with open(os.path.join(args.out, f"{stem}.csv"), "w") as f:
        f.write(",".join(cols) + "\n")
        for r in seed_rows:
            f.write(",".join(str(r[c]) for c in cols) + "\n")

    spec = dataset_spec(args.dataset)
    capacity = disjoint_shard_capacity(args.dataset)
    summary = {
        "experiment": "poison",
        "dataset": args.dataset, "nodes": args.nodes, "rounds": args.rounds,
        "seeds": len(seeds),
        "noising": bool(args.noising), "epsilon": args.epsilon,
        "defenses": [d.value for d in defenses],
        "trim_fraction": (args.trim_fraction
                          if Defense.TRIMMED_MEAN in defenses else None),
        "rows": rows,
        "data_note": ("REAL data (sklearn-bundled corpus)"
                      if spec.real
                      else "synthetic shards (zero-egress env)"),
        # each cell builds ONE Simulator (seed=seeds[0]) and varies only
        # the run_scan seed argument, so "seeds" vary the protocol RNG
        # (contributor sampling, DP noise, committee draws) over FIXED
        # shard data and poisoner assignment — the reported mean±std is
        # protocol-RNG variation, NOT full cross-seed (re-sharded)
        # variation, and the gate margin inherits that partial
        # correlation (ADVICE r5 #3)
        "seeds_note": (
            "seeds vary protocol RNG only (sampling/noise/committee "
            "draws); shard data and poisoner assignment are fixed at "
            f"seed={seeds[0]} across all replicates — mean±std "
            "understates full cross-seed variation"),
    }
    het_alpha = dirichlet_alpha(args.dataset)
    if het_alpha is not None:
        summary["heterogeneity"] = {
            "dirichlet_alpha": het_alpha,
            "note": (
                "deliberate non-IID stress case: vanilla Krum's separation "
                "weakens as per-peer skew grows — the all-source-class "
                "poisoned shards (reference semantics, parse_mnist.py "
                "generate_poisoned) form a mutually tight cluster, and "
                "once honest updates spread wider than it, Krum's "
                "closest-neighbour score favours the attackers. This is "
                "the defense's documented non-IID limitation, reproduced "
                "on purpose; TRIMMED_MEAN (ops/robust_agg.py) is the "
                "framework's robust option for this regime, and the "
                "homogeneous run (poison.json) is the reference's own "
                "near-IID operating regime"),
        }
    if capacity is not None and args.nodes > capacity:
        summary["shard_note"] = (
            f"corpus supports ~{capacity} disjoint shards; at nodes="
            f"{args.nodes} peers REUSE overlapping slices, so a poisoned "
            f"peer's shard may coincide with an honest peer's — defense "
            f"separation statistics are only meaningful at nodes<="
            f"{capacity} (see poison_{args.dataset}.json for the disjoint "
            f"run); this run validates protocol behavior at scale, not "
            f"defense statistics")

    # ---------------------------------------------------------------- gate
    gate_name = args.gate_defense or next(
        (d.value for d in defenses if d != Defense.NONE), "NONE")

    def cell(poison, defense):
        return next(r for r in rows
                    if r["poison"] == poison and r["defense"] == defense)

    gate: dict = {"summary": "defense_reduces_attack_rate",
                  "gate_defense": gate_name}
    if gate_name == "NONE" or not any(d.value == "NONE" for d in defenses):
        gate["gate_waived"] = "no defense/control pair in --defenses"
        gate_ok = True
    else:
        g30, n30 = cell(0.30, gate_name), cell(0.30, "NONE")
        clean = cell(0.0, "NONE")
        sep, margin = separates(
            g30["attack_rate"], g30["attack_rate_std"],
            n30["attack_rate"], n30["attack_rate_std"],
            n_samples=len(seeds))
        # diagnostic only (no longer a silent gate bypass): on robust
        # tasks the undefended attack barely moves the metric and
        # separation is unmeasurable — such runs should pass --no-gate
        attack_bites = (n30["attack_rate"] - clean["attack_rate"]) >= 0.10
        gate.update({
            "ok": sep, "separates": sep,
            "separation_margin_required": round(margin, 4),
            "attack_bites": attack_bites,
            "at_ref_scale": args.nodes >= 50,
            "defended": g30["attack_rate"],
            "defended_std": g30["attack_rate_std"],
            "none": n30["attack_rate"], "none_std": n30["attack_rate_std"],
            "clean": clean["attack_rate"],
        })
        if args.no_gate:
            gate["gate_waived"] = ("--no-gate: report-only run (small-n, "
                                   "@dir stress, or attack-robust task)")
            gate_ok = True
        else:
            gate_ok = sep
    summary["gate"] = gate
    with open(os.path.join(args.out, f"{stem}.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(gate))
    return 0 if gate_ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
