"""Adaptive defense plane tests (ops/trust.py, docs/DEFENSES.md).

Unit level: plan validation + CLI knobs, TrustLedger determinism (two
ledgers fed the same block/decision sequence are bit-identical), the
chain walk's decline-path semantics (eligible absence IS the reject
signal), the slow-trust ramp (graduation, absence reset, duty-cycle
gate), the proven gate on the one-shot vetoes, the temporal-drift
scorer on verdict-coupled vs honest walks, ensemble hysteresis
(hold-down, no flap), and the FoolsGold small-N cluster-size fix.

Integration level (`-m defense` isolates): a clean ENSEMBLE cluster
accrues ZERO false rejections (the headline acceptance criterion), the
defaults-off guard (any other defense arms no ledger, emits no trust
metrics), and verdict-stream + ledger identity across the TCP and
hive-loopback transport layouts.
"""

import asyncio
import math

import numpy as np
import pytest

from biscotti_tpu.config import BiscottiConfig, Defense, Timeouts
from biscotti_tpu.ops import trust as trustlib
from biscotti_tpu.ops.trust import TrustLedger, TrustPlan
from biscotti_tpu.runtime.peer import PeerAgent
from biscotti_tpu.tools.chaos import chain_oracle

FAST = Timeouts(update_s=5.0, block_s=15.0, krum_s=3.0, share_s=5.0,
                rpc_s=4.0)


def _cfg(i, n, port, **kw):
    base = dict(
        node_id=i, num_nodes=n, dataset="creditcard", base_port=port,
        num_verifiers=1, num_miners=1, num_noisers=1,
        secure_agg=False, noising=False, verification=True,
        max_iterations=3, convergence_error=0.0, sample_percent=1.0,
        batch_size=8, timeouts=FAST, seed=3,
    )
    base.update(kw)
    return BiscottiConfig(**base)


def _run_cluster(cfgs):
    async def go():
        agents = [PeerAgent(c) for c in cfgs]
        results = await asyncio.gather(*(a.run() for a in agents))
        return results, agents

    return asyncio.run(go())


def _flat_cos(n, c=0.05, overrides=None):
    """n x n cosine matrix with constant off-diagonal c; overrides is
    {(i, j): value} applied symmetrically."""
    m = [[c] * n for _ in range(n)]
    for i in range(n):
        m[i][i] = 1.0
    for (i, j), v in (overrides or {}).items():
        m[i][j] = m[j][i] = v
    return m


def _neutral_decide(led, it, ids, **kw):
    """A decide() call shaped so no veto fires unless a kwarg says so."""
    n = len(ids)
    args = dict(norms=[1.0] * n, residuals=[0.5] * n, scores=[1.0] * n,
                keep=[True] * n, cos=_flat_cos(n))
    args.update(kw)
    return led.decide(it, ids, **args)


# ---------------------------------------------------------------- units


def test_plan_validation_and_cli_knobs():
    TrustPlan().validate()  # defaults must be self-consistent
    for bad in (dict(geo_ratio=1.0), dict(sim_margin=0.0),
                dict(sim_min_pairs=0), dict(mag_band=1.0),
                dict(proven_accepts=-1), dict(proven_window=0),
                dict(drift_hi=0.2, drift_lo=0.3), dict(drift_min_obs=1),
                dict(hold_rounds=-1), dict(ramp_floor=0.0),
                dict(absence_reset=0), dict(stream_cap=0)):
        with pytest.raises(ValueError):
            TrustPlan(**bad).validate()

    import argparse

    ap = argparse.ArgumentParser()
    BiscottiConfig.add_args(ap)
    ns = ap.parse_args([
        "--node-id", "0", "--num-nodes", "4", "--defense", "ENSEMBLE",
        "--trust-geo-ratio", "3.5", "--trust-mag-band", "4.0",
        "--trust-hold", "5", "--trust-ramp-rounds", "6",
        "--trust-ramp-floor", "0.25", "--trust-absence-reset", "2",
        "--fg-min-cluster", "2",
    ])
    cfg = BiscottiConfig.from_args(ns)
    assert cfg.defense == Defense.ENSEMBLE
    assert cfg.trust_plan.geo_ratio == 3.5
    assert cfg.trust_plan.mag_band == 4.0
    assert cfg.trust_plan.hold_rounds == 5
    assert cfg.trust_plan.ramp_rounds == 6
    assert cfg.trust_plan.ramp_floor == 0.25
    assert cfg.trust_plan.absence_reset == 2
    assert cfg.fg_min_cluster == 2
    # knobs not flagged keep the plan defaults
    assert cfg.trust_plan.sim_margin == TrustPlan.sim_margin

    with pytest.raises(ValueError):
        _cfg(0, 4, 15000, defense=Defense.ENSEMBLE, fedsys=True)
    with pytest.raises(ValueError):
        _cfg(0, 4, 15000, fg_min_cluster=0)


def test_ledger_determinism_and_replay_guard():
    """Two ledgers fed the identical block/decision sequence are
    bit-identical — the property the TCP-vs-hive criterion rests on —
    and replayed / out-of-order blocks are ignored."""

    def feed(led):
        led.sync_block(0, {i: True for i in range(6)}, committee={6, 7})
        _neutral_decide(led, 1, list(range(6)))
        led.sync_block(1, {0: True, 1: False, 3: True}, committee={2, 5})
        _neutral_decide(led, 2, [0, 1, 3, 4],
                        norms=[1.0, 9.0, 1.1, 0.9],
                        scores=[1.0, 30.0, 1.2, 0.8],
                        keep=[True, False, True, True])
        led.sync_block(2, {}, committee=None)    # empty: no signal

    a = TrustLedger(TrustPlan(), 8)
    b = TrustLedger(TrustPlan(), 8)
    feed(a)
    feed(b)
    assert a.snapshot() == b.snapshot()
    assert a.trust_scores() == b.trust_scores()

    snap = a.snapshot()
    a.sync_block(1, {0: False, 1: True}, committee=None)  # replay
    a.sync_block(0, {5: False}, committee=None)           # out-of-order
    assert a.snapshot() == snap
    assert a._peers[0].walk[1] is True


def test_chain_walk_decline_path_semantics():
    """A DEFENSE rejection leaves NO chain record (the worker declines),
    so the walk must read eligible absence as the reject signal, while
    committee membership and unknown electorates carry none."""
    led = TrustLedger(TrustPlan(), 6)
    led.sync_block(0, {0: True, 1: False}, committee={2, 3})
    assert led._peers[0].walk[0] is True
    assert led._peers[1].walk[0] is False          # miner-stage reject
    assert led._peers[4].walk[0] is False          # eligible + absent
    assert led._peers.get(2) is None               # committee: no signal
    led.sync_block(1, {0: True}, committee=None)   # unknown electorate
    assert 1 not in led._peers[4].walk


def test_slow_trust_ramp_graduation_and_absence_reset():
    plan = TrustPlan(ramp_rounds=4, ramp_floor=0.4, absence_reset=3)
    led = TrustLedger(plan, 4)
    assert led.weight(3) == 1.0          # unseen: grandfathered
    led.seed_fresh([3])
    assert led.weight(3) == pytest.approx(0.4)
    for it in range(4):                  # accepted blocks ramp it up
        led.sync_block(it, {3: True}, committee=set())
    assert led.weight(3) == 1.0 and led._peers[3].ramp is None
    # graduated identity disappearing for absence_reset eligible rounds
    # restarts the ramp — the sybil-recycle trigger
    for it in range(4, 7):
        led.sync_block(it, {0: True}, committee=set())
    assert led._peers[3].ramp == 0 and led._peers[3].resets == 1
    assert led.weight(3) == pytest.approx(0.4)
    # seed_fresh never demotes an identity with accepted history
    led2 = TrustLedger(plan, 4)
    led2.sync_block(0, {1: True}, committee=set())
    led2.seed_fresh([1])
    assert led2.weight(1) == 1.0


def test_slow_trust_duty_cycle_gates_without_arming_hold():
    """A ramping identity is throttled to its weight's duty cycle; the
    pure slow_trust vote must NOT arm the hysteresis hold, or a fresh
    identity could never accrue the accepts it needs to graduate.

    Credit is CHAIN-derived: each round's decision is followed by the
    block it produced — an accepted record consumes the pass, a
    throttled round banks its weight as an eligible absence."""
    led = TrustLedger(TrustPlan(ramp_rounds=4, ramp_floor=0.4), 2)
    led.seed_fresh([0])
    walk = []
    for it in range(5):
        accepts, votes, _ = _neutral_decide(led, it, [0, 1])
        walk.append((accepts[0], tuple(votes[0])))
        assert accepts[1] and not votes[1]       # veteran untouched
        records = {1: True}
        if accepts[0]:
            records[0] = True            # the pass lands on the chain
        led.sync_block(it, records, committee=set())
    # credit 0.4 / 0.8 / 1.2->accept(->0.75) / 1.3->accept
    assert walk == [(False, ("slow_trust",)), (False, ("slow_trust",)),
                    (True, ()), (False, ("slow_trust",)), (True, ())]
    assert led._peers[0].hold == 0


def test_slow_trust_verdict_unanimous_across_churned_committees():
    """Chain-derived credit (ROADMAP item 2b residual): verifiers that
    folded the same committed blocks issue the IDENTICAL slow_trust
    verdict regardless of which rounds each of them happened to decide.
    Before this change the credit accumulator mutated inside decide(),
    so a freshly seated verifier on a churned committee disagreed with
    a veteran one about a ramping identity — a per-round verdict split
    the protocol's majority-approval then had to paper over."""
    plan = TrustPlan(ramp_rounds=4, ramp_floor=0.4)
    veteran = TrustLedger(plan, 3)   # decides EVERY round
    joiner = TrustLedger(plan, 3)    # seated late: only folds the chain
    for led in (veteran, joiner):
        led.seed_fresh([0])
    for it in range(6):
        accepts, _, _ = _neutral_decide(veteran, it, [0, 1])
        records = {1: True}
        if accepts[0]:
            records[0] = True
        for led in (veteran, joiner):
            led.sync_block(it, records, committee=set())
    assert veteran._peers[0].credit == joiner._peers[0].credit
    va = _neutral_decide(veteran, 6, [0, 1])
    ja = _neutral_decide(joiner, 6, [0, 1])
    assert va == ja
    # and deciding is side-effect-free on the credit state: replaying
    # the same decision yields the same verdict (idempotent verdicts
    # are what make committee rotation safe)
    assert _neutral_decide(joiner, 6, [0, 1]) == ja


def test_proven_gate_exempts_veterans_from_one_shot_vetoes():
    """Same outlier geometry/magnitude, opposite verdicts: an identity
    with a majority-accepted recent walk is exempt from the one-shot
    vetoes, one with no earned history is not — and an attacker cannot
    fake the walk because rejection leaves no record to graduate on."""
    led = TrustLedger(TrustPlan(proven_accepts=2), 8)
    for it in range(2):
        # peer 6 is eligible yet absent -> negative walk evidence, so
        # neither proven nor committee-clean
        led.sync_block(it, {i: True for i in range(5)},
                       committee={5, 7})
    assert led.proven(0)
    assert not led.proven(6) and not led.committee_clean(6)
    ids = [0, 1, 2, 3, 6]
    outlier = dict(
        norms=[50.0, 1.0, 1.1, 0.9, 50.0],
        scores=[100.0, 1.0, 1.2, 0.8, 100.0],
        keep=[False, True, True, True, False],
    )
    accepts, votes, _ = _neutral_decide(led, 3, ids, **outlier)
    assert accepts[0] and not votes[0]           # proven: gated
    assert not accepts[4]                        # fresh: full scrutiny
    assert set(votes[4]) == {"geometry", "magnitude"}
    # one-sided magnitude: a scaled-DOWN probe carries proportionally
    # little poison and must not fire the veto on its own
    _, votes2, _ = _neutral_decide(
        led, 4, ids, norms=[1.0, 1.0, 1.1, 0.9, 0.01])
    assert "magnitude" not in votes2[4]


def test_committee_clean_exemption():
    """An empty walk after real blocks settled means every absence was
    committee duty — no negative evidence, so the one-shot vetoes stay
    gated. An eligible absence (the decline signal) ends the exemption,
    and at genesis (no blocks) nobody is exempt."""
    led = TrustLedger(TrustPlan(), 6)
    assert not led.committee_clean(0)            # genesis: scrutinise
    led.sync_block(0, {0: True, 1: True}, committee={4, 5})
    led.sync_block(1, {0: True, 1: True}, committee={4, 5})
    assert led.committee_clean(4)
    assert not led.committee_clean(2)            # eligible-absent
    ids = [0, 1, 4, 2]
    accepts, votes, _ = _neutral_decide(
        led, 2, ids,
        scores=[1.0, 1.1, 80.0, 80.0],
        keep=[True, True, False, False])
    assert accepts[2] and not votes[2]           # committee-clean: gated
    assert not accepts[3] and votes[3] == ["geometry"]


def test_similarity_veto_and_min_pairs_guard():
    plan = TrustPlan(sim_margin=0.15, sim_mad_mult=6.0, sim_min_pairs=3)
    led = TrustLedger(plan, 8)
    n = 6
    # a colluding pair at cos 0.9 against an honest baseline of 0.05;
    # keep covers 4 honest peers -> 6 calibration pairs
    cos = _flat_cos(n, 0.05, {(4, 5): 0.9})
    accepts, votes, detail = _neutral_decide(
        led, 0, list(range(n)), cos=cos,
        keep=[True, True, True, True, False, False])
    assert accepts[:4] == [True] * 4
    assert not accepts[4] and not accepts[5]
    assert votes[4] == ["similarity"] and votes[5] == ["similarity"]
    assert detail["sim_bar"] < 0.9
    # a pool too small for a usable calibration sample disables the
    # veto instead of trusting a single-cosine bar
    led2 = TrustLedger(plan, 8)
    _, _, d2 = _neutral_decide(led2, 0, [0, 1, 2],
                               cos=_flat_cos(3, 0.8),
                               keep=[True, True, False])
    assert d2["sim_bar"] == 2.0


def test_drift_flags_verdict_coupled_walk_not_honest_noise():
    """The cross-round consistency scorer: a hugger's residual moves
    WITH its chain verdicts (up on accept, down on reject); honest
    minibatch noise is uncorrelated and spans too little range."""
    plan = TrustPlan()
    led = TrustLedger(plan, 4)
    r_hug = 1.0
    accepted = True
    for it in range(12):
        # observe this round's residual, THEN the verdict lands on chain
        # and the controller reacts for the next round — the real
        # ordering in _ensemble_mask (decide before block it commits)
        r_hon = 1.0 + 0.01 * (1 if it % 2 else -1)
        _neutral_decide(led, it, [0, 1], residuals=[r_hug, r_hon])
        led.sync_block(it, {0: accepted, 1: True}, committee=set())
        r_hug *= 1.6 if accepted else 0.5         # the hug controller
        accepted = not accepted
    assert led._peers[0].drift_score >= plan.drift_hi
    assert led._peers[0].flagged
    assert led._peers[1].drift_score == 0.0 and not led._peers[1].flagged
    assert led.trust_scores()[0] == 0.0
    # constant-verdict monotone regime: an always-rejected hugger
    # backing its scale off is equally coupled
    led2 = TrustLedger(plan, 2)
    r = 8.0
    for it in range(10):
        _neutral_decide(led2, it, [0, 1], residuals=[r, 1.0])
        led2.sync_block(it, {1: True}, committee=set())  # 0 absent
        r *= 0.6
    assert led2._peers[0].drift_score == 1.0


def test_hysteresis_hold_no_flap():
    """One veto round arms hold_rounds of continued rejection; the peer
    re-enters only after serving the full hold with no further votes."""
    led = TrustLedger(TrustPlan(hold_rounds=3), 4)
    ids = [0, 1, 2, 3]
    _, votes, _ = _neutral_decide(led, 0, ids,
                                  scores=[40.0, 1.0, 1.1, 0.9],
                                  keep=[False, True, True, True])
    assert votes[0] == ["geometry"]
    verdicts = []
    for it in range(1, 5):
        accepts, votes, _ = _neutral_decide(led, it, ids)
        verdicts.append((accepts[0], tuple(votes[0])))
    assert verdicts == [(False, ("hold",)), (False, ("hold",)),
                        (False, ("hold",)), (True, ())]


def test_foolsgold_min_cluster_gate():
    """The small-N fix: an accidental honest pair is freed by the
    cluster-size gate (a sybil CLUSTER is what FoolsGold models), a
    genuine triple is still caught, and min_cluster=1 restores the
    original kernel."""
    from biscotti_tpu.ops.robust_agg import foolsgold_accept_mask

    rng = np.random.default_rng(7)
    base = rng.normal(size=(9, 400)).astype(np.float32)
    base[7] = base[8] + 0.01 * rng.normal(size=400).astype(np.float32)
    m3 = np.asarray(foolsgold_accept_mask(base, min_cluster=3))
    m1 = np.asarray(foolsgold_accept_mask(base, min_cluster=1))
    assert m3[7] and m3[8]                 # pair freed at min_cluster=3
    assert not m1[7] and not m1[8]         # PR-1 behaviour preserved
    triple = base.copy()
    triple[6] = triple[8] + 0.01 * rng.normal(size=400).astype(np.float32)
    mt = np.asarray(foolsgold_accept_mask(triple, min_cluster=3))
    assert not mt[6] and not mt[7] and not mt[8]
    assert mt[:6].all()


def test_trust_scores_and_stream_constants():
    led = TrustLedger(TrustPlan(), 3)
    assert led.trust_scores() == {0: 1.0, 1: 1.0, 2: 1.0}
    snap = led.snapshot()
    assert snap["synced_it"] == -1 and snap["decisions"] == 0
    assert trustlib.TRUST_METRIC == "biscotti_trust_score"
    assert trustlib.VOTES_METRIC == "biscotti_defense_votes_total"
    assert set(trustlib.SCORERS) >= {"geometry", "similarity",
                                     "magnitude", "drift", "slow_trust",
                                     "hold"}


def test_pearson_constant_sides():
    assert trustlib.pearson([1.0, 1.0, 1.0], [1.0, 2.0, 3.0]) == 0.0
    assert trustlib.pearson([1.0, 2.0], [3.0]) == 0.0
    assert trustlib.pearson([1.0, 2.0, 3.0],
                            [2.0, 4.0, 6.0]) == pytest.approx(1.0)


# ----------------------------------------------- live: clean-run safety


@pytest.mark.defense
def test_ensemble_clean_run_zero_false_rejections():
    """THE acceptance criterion: honest peers under a clean ENSEMBLE run
    accrue zero false rejections and zero stake debits — every verdict
    stream row is all-accept with no votes, no identity is flagged or
    reset, and the chains stay equal."""
    n, port = 6, 15520
    results, agents = _run_cluster(
        [_cfg(i, n, port, defense=Defense.ENSEMBLE) for i in range(n)])
    eq, _, real = chain_oracle(results)
    assert eq and real >= 1
    saw_stream = False
    for a, r in zip(agents, results):
        assert a.trust is not None
        tr = r["telemetry"].get("trust")
        assert tr is not None and tr["defense"] == "ENSEMBLE"
        led = tr.get("ledger")
        if led is not None:
            assert led["flagged"] == [] and led["resets"] == {}
            assert not any(v in led["votes"] for v in
                           ("geometry", "similarity", "magnitude",
                            "drift", "hold"))
        for row in tr.get("stream", []):
            saw_stream = True
            assert all(row["accept"]), row
            assert not any(row["votes"]), row
    assert saw_stream


@pytest.mark.defense
def test_defaults_off_guard_no_ledger_no_trust_metrics():
    """`--defense KRUM` (or anything but ENSEMBLE) arms NO TrustLedger
    and emits NO trust metrics — the structural half of the off-path
    bit-identity contract. The verdict stream itself records for every
    defense (it is the attack-matrix evidence channel)."""
    n, port = 4, 15560
    results, agents = _run_cluster(
        [_cfg(i, n, port, defense=Defense.KRUM) for i in range(n)])
    eq, _, real = chain_oracle(results)
    assert eq and real >= 1
    for a, r in zip(agents, results):
        assert a.trust is None
        snap = r["telemetry"]
        assert trustlib.TRUST_METRIC not in snap["metrics"]
        assert not any(k.startswith(trustlib.VOTES_METRIC)
                       for k in snap["counters"])
        tr = snap.get("trust")
        if tr is not None:
            assert "ledger" not in tr
            assert tr["defense"] == "KRUM"


# ------------------------------------------ live: transport determinism


# budgets that bound only the unhappy path (tests/test_crypto_kernels.py's
# live guard uses the same): the first cluster of a process pays its JIT
# compiles inside round 0, and under FAST's 3 s krum timer a cold run
# minted an empty first block that the warm second run did not
WIDE = Timeouts(update_s=25.0, block_s=90.0, krum_s=20.0, share_s=25.0,
                rpc_s=25.0)


def _raced_rounds(agents):
    """Rounds in which more workers asked a verifier than its pool takes:
    the first `thresh` arrivals win, a timing-dependent subset. Read from
    the agents' event rings, which must still hold the run's first event."""
    assert [a.tele.recorder.wrapped for a in agents] == [0] * len(agents)
    asked = [ev for a in agents for ev in a.tele.recorder.tail(100000)
             if ev["event"] == "verify_request"]
    assert len({ev["iter"] for ev in asked}) == 3  # every round's are there
    return sorted({ev["iter"] for ev in asked if ev["pool"] > ev["thresh"]})


@pytest.mark.defense
def test_trust_state_identical_across_tcp_and_hive_loopback():
    """Same seed => bit-identical verdict streams and ledger snapshots
    on both transport layouts (TCP one-agent-per-peer vs hive loopback
    co-hosting; exact per-agent trainers so chains match by
    construction) — the ISSUE's determinism criterion.

    Seed 1: at n=6 its committees are disjoint in all three rounds, so
    every verifier is asked by exactly the four workers its pool takes.
    Where a peer sits on both committees (seed 3's third round: five
    workers for a pool of four) the seed protocol itself accepts
    whichever four arrive first, and two runs of ONE layout differ. That
    precondition is read from each run's own events before anything is
    compared across runs."""
    from biscotti_tpu.runtime.hive import Hive

    n = 6
    kw = dict(defense=Defense.ENSEMBLE, seed=1, timeouts=WIDE)
    tcp_results, tcp_agents = _run_cluster(
        [_cfg(i, n, 15600, **kw) for i in range(n)])
    hive = Hive(_cfg(0, n, 15660, **kw), hive_id="trust",
                batch_device=False)
    hive_results = asyncio.run(hive.run())

    for results, agents in ((tcp_results, tcp_agents),
                            (hive_results, hive.agents)):
        dump = results[0]["chain_dump"]
        assert all(r["chain_dump"] == dump for r in results)
        blocks = dump.splitlines()[1:]
        assert len(blocks) == 3 and not any("ndeltas=0" in b for b in blocks)
        assert _raced_rounds(agents) == [], "choose another geometry"

    assert tcp_results[0]["chain_dump"] == hive_results[0]["chain_dump"]
    streams = 0
    for i in range(n):
        t = tcp_results[i]["telemetry"].get("trust")
        h = hive_results[i]["telemetry"].get("trust")
        assert (t is None) == (h is None)
        if t is not None:
            assert t["stream"] == h["stream"]
            assert t.get("ledger") == h.get("ledger")
            streams += len(t["stream"])
    assert streams == 3  # one verdict row a round, compared
