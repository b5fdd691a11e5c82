#!/usr/bin/env python
"""Attack-matrix eval — adaptive-adversary campaigns × poisoning defenses
on LIVE clusters: the repo's headline security claim (ISSUE 14).

Every cell boots a real in-process cluster (TCP loopback transport, full
crypto, admission plane armed) under one (campaign, defense, secure-agg)
combination and one seed, runs it to --rounds, and reads the outcome off
the settled ledgers and telemetry snapshots:

  * final_error       anchor model error after the run
  * chains_equal      surviving-prefix oracle across ALL peers
                      (attackers included — a campaign that forks the
                      honest survivors is a consensus break, the
                      strongest possible finding)
  * defense verdict   which poisoned sources ever entered an accepted
                      block record, rejection counts, where poisoned
                      stake landed (tools/verdicts.chain_defense_verdict
                      — the ONE parser, shared with eval_poison and the
                      membership suite)
  * sheds / breaker opens / campaign action tallies

`survived` means: chains equal, at least one real block, and (for
poison-bearing campaigns) NO poisoned source ever accepted — the
defense held while the system stayed live. `failed` is the same bit as
a 0/1 numeric so `tools/bench_diff.py` flags a future PR that flips a
survived cell (failed 0 → 1 reads as a lower-is-better regression).

Campaigns (runtime/adversary.py, docs/ADVERSARY.md):
  none       clean baseline (no poison, no campaign)
  static     the reference's static label-flip poisoners (poison only)
  roleflood  poisoners that also aim a frame storm at the per-round
             elected miner/noisers (admission plane under fire)
  sybil      poisoners that kill + rejoin as fresh incarnations on a
             seeded schedule (membership + admission planes under fire)
  hug        threshold-hugging poisoners that modulate magnitude/
             direction against observed verdicts (defense under fire)

Operating point: committee DP noising OFF — the defense-geometry
configuration (the reference's own ML-layer poison evals; at ε=1.0 the
noise masks every geometry defense, measured in poison.json — see
ops/robust_agg.py OPERATING POINT). Documented in the artifact.

Every cell is replayable from ONE seed via the recorded chaos command:

    python -m biscotti_tpu.tools.chaos --nodes 8 --rounds 8 --seed 11 \
        --dataset digits --secure-agg 1 --defense KRUM --poison 0.375 \
        --campaign hug --campaign-attackers 0.375 --admission 1

Artifacts: eval/results/attack_matrix.json (+ .csv). Exit 0 iff every
cell completed; survival is DATA (the matrix exists to document which
campaigns the stack survives and which it provably does not), guarded
against regression by bench_diff, not by this exit code.

Usage: python eval/eval_attack_matrix.py [--dataset digits] [--nodes 8]
           [--rounds 8] [--seed 11] [--poison 0.375]
           [--defenses NONE,KRUM,MULTIKRUM,FOOLSGOLD,ENSEMBLE] [--quick]
           [--out eval/results]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CAMPAIGN_CELLS = ("none", "static", "roleflood", "sybil", "hug")


def _cell_plan(campaign: str, ns):
    """The CampaignPlan for one matrix cell: attackers mirror the
    poisoned fraction, so the colluding set IS the poisoned set."""
    from biscotti_tpu.runtime.adversary import CampaignPlan

    if campaign in ("none", "static"):
        return CampaignPlan()
    kw = dict(attackers=ns.poison)
    if campaign == "roleflood":
        kw["flood"] = ns.flood
    elif campaign == "sybil":
        kw["recycle_period"] = max(2, ns.rounds // 2)
        kw["recycle_down"] = 1
    return CampaignPlan(campaign=campaign, **kw)


def _cell_cfg(i: int, campaign: str, defense, secure_agg: bool, port: int,
              ns):
    from biscotti_tpu.config import BiscottiConfig, Defense, Timeouts
    from biscotti_tpu.runtime.admission import AdmissionPlan

    fast = Timeouts(update_s=6.0, block_s=18.0, krum_s=4.0, share_s=6.0,
                    rpc_s=5.0)
    poison = 0.0 if campaign == "none" else ns.poison
    return BiscottiConfig(
        node_id=i, num_nodes=ns.nodes, dataset=ns.dataset,
        base_port=port, num_verifiers=ns.verifiers, num_miners=1,
        num_noisers=1,
        secure_agg=secure_agg, noising=False,
        verification=defense != Defense.NONE, defense=defense,
        poison_fraction=poison,
        max_iterations=ns.rounds, convergence_error=0.0,
        sample_percent=1.0, batch_size=8, timeouts=fast, seed=ns.seed,
        # admission armed in every cell (harness-scaled rates, the chaos
        # defaults) so shed columns are comparable across campaigns
        admission_plan=AdmissionPlan(enabled=True, update_rate=8.0,
                                     bulk_rate=6.0, control_rate=16.0),
        campaign_plan=_cell_plan(campaign, ns),
    )


def _replay_cmd(campaign: str, defense, secure_agg: bool, port: int,
                ns) -> str:
    parts = [
        "python -m biscotti_tpu.tools.chaos",
        f"--nodes {ns.nodes} --rounds {ns.rounds} --seed {ns.seed}",
        f"--dataset {ns.dataset} --base-port {port}",
        f"--verifiers {ns.verifiers}",
        f"--secure-agg {int(secure_agg)} --defense {defense.value}",
        "--admission 1",
    ]
    if campaign != "none":
        parts.append(f"--poison {ns.poison}")
    if campaign not in ("none", "static"):
        parts.append(f"--campaign {campaign} "
                     f"--campaign-attackers {ns.poison}")
    if campaign == "roleflood":
        parts.append(f"--campaign-flood {ns.flood}")
    return " ".join(parts)


def run_cell(campaign: str, defense, secure_agg: bool, port: int,
             ns) -> dict:
    from biscotti_tpu.runtime.membership import (ChurnRunner,
                                                 surviving_prefix_oracle)
    from biscotti_tpu.runtime.peer import PeerAgent
    from biscotti_tpu.tools import verdicts

    def make(i):
        return PeerAgent(_cell_cfg(i, campaign, defense, secure_agg,
                                   port, ns))

    plan = _cell_plan(campaign, ns)
    recycle = plan.recycle_schedule(ns.nodes, ns.rounds,
                                    protocol_seed=ns.seed)
    made = {}

    def make_tracked(i):
        a = make(i)
        made[i] = a  # latest incarnation; node 0 is never recycled
        return a

    async def go():
        if recycle:
            # sybil cells ride the membership runner: kills self-fire in
            # the attackers' round loops, the runner relaunches fresh
            # incarnations (docs/ADVERSARY.md)
            runner = ChurnRunner(make_tracked, ns.nodes, recycle)
            return await runner.run(), runner.events_applied
        agents = [make_tracked(i) for i in range(ns.nodes)]
        return await asyncio.gather(*(a.run() for a in agents)), None

    results, applied = asyncio.run(go())
    anchor_blocks = made[0].chain.blocks

    from biscotti_tpu.tools import obs

    # per-verifier verdict streams (accept/reject walk + observed
    # magnitudes + ENSEMBLE scorer votes): the replayable evidence that
    # the hugger's scale walk happened — and, in the ENSEMBLE row, that
    # it was suppressed — not just a final error number
    trust = obs.merge_trust([r["telemetry"] for r in results
                             if "telemetry" in r], streams=True)

    equal, settled, real = surviving_prefix_oracle(results)
    poison = 0.0 if campaign == "none" else ns.poison
    verdict = verdicts.cluster_defense_verdict(
        results, ns.nodes, poison, anchor_blocks=anchor_blocks)
    survived = bool(equal and real >= 1
                    and (campaign == "none"
                         or verdict["n_accepted_poisoned"] == 0))
    final_error = results[0].get("final_error")
    row = {
        "campaign": campaign, "defense": defense.value,
        "secure_agg": secure_agg, "seed": ns.seed,
        "final_error": round(float(final_error), 4),
        "chains_equal": equal, "settled": settled, "real_blocks": real,
        "survived": survived, "failed": 0 if survived else 1,
        "accepted_poisoned_n": verdict.get("n_accepted_poisoned", 0),
        "verdict": verdict,
        "trust": trust if trust.get("verifiers") else None,
        "recycles_applied": applied,
        "replay": _replay_cmd(campaign, defense, secure_agg, port, ns),
    }
    return row


def format_matrix(rows) -> str:
    """The attack × defense table, one line per (campaign, sa) row."""
    defenses = sorted({r["defense"] for r in rows})
    lines = [f"{'campaign':<11} {'sa':<3} "
             + " ".join(f"{d:>22}" for d in defenses)]
    combos = sorted({(r["campaign"], r["secure_agg"]) for r in rows},
                    key=lambda c: (CAMPAIGN_CELLS.index(c[0]),
                                   not c[1]))
    for camp, sa in combos:
        cells = []
        for d in defenses:
            r = next((x for x in rows if x["campaign"] == camp
                      and x["defense"] == d
                      and x["secure_agg"] == sa), None)
            if r is None:
                cells.append(f"{'-':>22}")
                continue
            if "error" in r:
                cells.append(f"{'ERR':>22}")
                continue
            tag = "ok" if r["survived"] else "FAIL"
            cells.append(f"{tag} err={r['final_error']:.3f} "
                         f"p={r['accepted_poisoned_n']}".rjust(22))
        lines.append(f"{camp:<11} {'on' if sa else 'off':<3} "
                     + " ".join(cells))
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="mnist@dir0.3",
                    help="Dirichlet-skewed mnist by default: the "
                         "defense-geometry regime where honest non-IID "
                         "updates spread and the tight poison cluster "
                         "is separable (the FoolsGold operating point, "
                         "poison_mnist_dir0.3_100_nonoise.json); "
                         "homogeneous/real sets hide the poisoners "
                         "inside the honest cluster at this scale")
    ap.add_argument("--nodes", type=int, default=10)
    ap.add_argument("--verifiers", type=int, default=3,
                    help="verifier committee size: majority approval "
                         "(2 of 3) keeps one colluding verifier from "
                         "rubber-stamping its fellow poisoners "
                         "(ref krum.go:47-58 collusion semantics)")
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--poison", type=float, default=0.3,
                    help="poison/attacker fraction: 0.3 at 10 nodes = "
                         "ids {8,9} (the reference's top-ids formula)")
    ap.add_argument("--flood", type=int, default=30,
                    help="roleflood targeted replay factor")
    ap.add_argument("--defenses",
                    default="NONE,KRUM,MULTIKRUM,FOOLSGOLD,ENSEMBLE")
    ap.add_argument("--campaigns", default=",".join(CAMPAIGN_CELLS))
    ap.add_argument("--base-port", type=int, default=14400)
    ap.add_argument("--quick", action="store_true",
                    help="2 campaigns x 2 defenses, secure-agg on only "
                         "(the bench gate's smoke configuration)")
    ap.add_argument("--out", default="eval/results")
    ap.add_argument("--tag", default="attack_matrix")
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_enable_x64", True)
    from biscotti_tpu.utils import jaxenv

    jaxenv.configure_compile_cache()

    from biscotti_tpu.config import Defense
    from biscotti_tpu.tools.verdicts import separates

    defenses = [Defense(d.strip()) for d in args.defenses.split(",") if d]
    campaigns = [c.strip() for c in args.campaigns.split(",") if c]
    for c in campaigns:
        if c not in CAMPAIGN_CELLS:
            ap.error(f"unknown campaign cell {c!r}")
    if args.quick:
        campaigns = [c for c in ("static", "hug") if c in campaigns] \
            or campaigns[:2]
        defenses = defenses[:2]

    # the cell list: every campaign × defense with secure-agg ON, plus
    # secure-agg OFF replicates for the geometry-relevant comparison
    # (static vs hug under the accept-mask defenses — the plain-update
    # path the reference's ML evals ran)
    cells = [(c, d, True) for c in campaigns for d in defenses]
    if not args.quick:
        for c in ("static", "hug"):
            for d in defenses:
                if c in campaigns and d != Defense.NONE:
                    cells.append((c, d, False))

    rows = []
    port = args.base_port
    for camp, d, sa in cells:
        try:
            row = run_cell(camp, d, sa, port, args)
        except Exception as e:
            # a wedged/failed cell becomes a recorded error row — the
            # artifact still lands with every other cell, and the exit
            # code says the matrix is incomplete
            row = {"campaign": camp, "defense": d.value,
                   "secure_agg": sa, "seed": args.seed,
                   "error": f"{type(e).__name__}: {e}"}
        rows.append(row)
        print(json.dumps({k: row.get(k) for k in
                          ("campaign", "defense", "secure_agg",
                           "final_error", "chains_equal", "survived",
                           "accepted_poisoned_n", "error")
                          if k in row}))
        port += args.nodes + 2  # fresh port block per cell

    # adaptive-vs-static: does the threshold-hugger measurably degrade
    # any defense cell relative to the static poisoner? (an honest
    # negative — defenses hold, modulation traced — is a valid result)
    hug_vs_static = []
    for d in defenses:
        for sa in (True, False):
            h = next((r for r in rows if r["campaign"] == "hug"
                      and r["defense"] == d.value
                      and r["secure_agg"] == sa
                      and "error" not in r), None)
            s = next((r for r in rows if r["campaign"] == "static"
                      and r["defense"] == d.value
                      and r["secure_agg"] == sa
                      and "error" not in r), None)
            if h is None or s is None:
                continue
            worse_err, _ = separates(s["final_error"], 0.0,
                                     h["final_error"], 0.0)
            hug_vs_static.append({
                "defense": d.value, "secure_agg": sa,
                "static_error": s["final_error"],
                "hug_error": h["final_error"],
                "hug_degrades_error": worse_err,
                "static_accepted_poisoned": s["accepted_poisoned_n"],
                "hug_accepted_poisoned": h["accepted_poisoned_n"],
                "hug_smuggles_more": (h["accepted_poisoned_n"]
                                      > s["accepted_poisoned_n"]),
            })

    os.makedirs(args.out, exist_ok=True)
    summary = {
        "experiment": "attack_matrix",
        "dataset": args.dataset, "nodes": args.nodes,
        "rounds": args.rounds, "seed": args.seed,
        "poison": args.poison, "flood": args.flood,
        "noising": False,
        "operating_point_note": (
            "committee DP noising OFF — the defense-geometry operating "
            "point (at eps=1.0 the noise norm masks every geometry "
            "defense toward accept-everyone; ops/robust_agg.py "
            "OPERATING POINT, measured in poison.json). survived = "
            "chains equal AND >=1 real block AND no poisoned source "
            "ever accepted."),
        "defenses": [d.value for d in defenses],
        "campaigns": campaigns,
        "rows": rows,
        "hug_vs_static": hug_vs_static,
        "table": format_matrix(rows),
    }
    with open(os.path.join(args.out, f"{args.tag}.json"), "w") as f:
        json.dump(summary, f, indent=1)
    cols = ["campaign", "defense", "secure_agg", "final_error",
            "chains_equal", "settled", "real_blocks", "survived",
            "accepted_poisoned_n"]
    with open(os.path.join(args.out, f"{args.tag}.csv"), "w") as f:
        f.write(",".join(cols) + "\n")
        for r in rows:
            f.write(",".join(str(r.get(c, "")) for c in cols) + "\n")
    print(format_matrix(rows))
    complete = not any("error" in r for r in rows)
    return 0 if complete else 1


if __name__ == "__main__":
    raise SystemExit(main())
