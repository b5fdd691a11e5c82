"""Hierarchical aggregation overlay (runtime/overlay.py, docs/OVERLAY.md):
tree derivation, defaults-off bit-identity, secure-agg subtree
aggregation with chain equality against the flat fan-out, plain-mode
relay fan-out, and the corrupted-subtree fallback (RLC refusal ->
per-member forwarding -> exact rejection evidence)."""

import asyncio

import numpy as np
import pytest

from biscotti_tpu.config import BiscottiConfig, Timeouts
from biscotti_tpu.runtime import overlay as ov
from biscotti_tpu.runtime.peer import PeerAgent

# warm budgets: the first cluster in a process pays JIT compilation, and
# a cold krum timer firing early would shrink one run's verifier pool —
# exactly the timing flake the equality oracle must not see. Deadlines
# only bound the unhappy path; the happy path proceeds on events.
FAST = Timeouts(update_s=20.0, block_s=60.0, krum_s=20.0, share_s=20.0,
                rpc_s=10.0)


def _cfg(i, n, port, **kw):
    base = dict(
        node_id=i, num_nodes=n, dataset="creditcard", base_port=port,
        num_verifiers=1, num_miners=2, num_noisers=1,
        secure_agg=True, noising=False, verification=True,
        max_iterations=2, convergence_error=0.0, sample_percent=1.0,
        batch_size=8, timeouts=FAST, seed=3,
    )
    base.update(kw)
    return BiscottiConfig(**base)


def _run_cluster(cfgs, agent_cls=PeerAgent, byzantine=()):
    async def go():
        agents = [(agent_cls if i in byzantine else PeerAgent)(c)
                  for i, c in enumerate(cfgs)]
        return await asyncio.gather(*(a.run() for a in agents))

    return asyncio.run(go())


def _overlay_counters(results):
    out = {}
    for r in results:
        for k, v in r["counters"].items():
            if k.startswith("overlay"):
                out[k] = out.get(k, 0) + v
    return out


# ------------------------------------------------------- tree derivation


@pytest.mark.overlay
def test_router_groups_partition_and_relay_rotates():
    r = ov.Router(True, 4, 10, seed=7)
    assert r.enabled and r.depth == 3
    # groups partition the id space into contiguous blocks
    seen = []
    for gid in range(3):
        seen += r.members(gid)
    assert seen == list(range(10))
    assert r.members(2) == [8, 9]  # ragged tail group
    # the relay is a member of its own group, identical for every
    # deriving peer, and rotates with the round
    relays = {it: r.relay(0, it) for it in range(40)}
    assert all(rel in r.members(0) for rel in relays.values())
    assert len(set(relays.values())) > 1
    r2 = ov.Router(True, 4, 10, seed=7)
    assert all(r2.relay(0, it) == rel for it, rel in relays.items())
    # a different protocol seed derives a different rotation
    r3 = ov.Router(True, 4, 10, seed=8)
    assert any(r3.relay(0, it) != relays[it] for it in range(40))


@pytest.mark.overlay
def test_router_plan_routes_remote_subtrees_only():
    r = ov.Router(True, 3, 9, seed=0)
    # self in group 0: own-group targets and singleton remote targets go
    # direct; a >= 2-target remote subtree goes through its relay
    direct, relayed = r.plan([1, 2, 3, 6, 7, 8], iteration=1, self_id=0)
    assert set(direct) >= {1, 2, 3}
    assert sum(len(ts) for ts in relayed.values()) == 3
    for relay, ts in relayed.items():
        assert r.gid_of(relay) == r.gid_of(ts[0]) == 2
    # disabled router: everything direct (the seed schedule)
    off = ov.Router(False, 3, 9, seed=0)
    assert off.plan([1, 6, 7], 1, 0) == ([1, 6, 7], {})


def test_overlay_defaults_off_and_requires_group():
    assert BiscottiConfig().overlay is False
    agent_cfg = _cfg(0, 4, 0)  # port unused: no run
    assert not ov.Router.from_config(agent_cfg).enabled
    with pytest.raises(ValueError):
        BiscottiConfig(overlay=True)  # no subtree: refuse, don't no-op


# --------------------------------------------------- live cluster parity


def _accepted_by_round(results):
    """{round: accepted worker ids}, from the verifiers' verdict streams
    (every defense records one; docs/DEFENSES.md)."""
    out = {}
    for r in results:
        for row in (r["telemetry"].get("trust") or {}).get("stream", []):
            out[row["it"]] = [s for s, ok in zip(row["src"], row["accept"])
                              if ok]
    return out


@pytest.mark.overlay
def test_secure_agg_overlay_chains_equal_flat_run():
    """THE equivalence oracle: same seed, overlay on vs off -> identical
    chains (same contributors, same commitments, same quorums, same
    aggregate), with the overlay run actually aggregating subtrees.

    n=7: this geometry's committees are disjoint both rounds, so the
    worker set equals num_samples and the Krum pool cannot race — the
    precondition for CROSS-RUN bit-equality (with committee overlap the
    seed protocol itself accepts a timing-dependent subset).

    Subtrees of 4: Krum keeps workers 1 and 3 of {1, 3, 4, 6} in both
    rounds, and a relay combines only what the verifier released, so
    they must share a subtree for there to be anything to aggregate
    (with subtrees of 3 they sat alone in {0,1,2} and {3,4,5}: every
    offer was a lone one, forwarded per member, and the run aggregated
    nothing, every time). Read from the flat run's own verdicts below,
    so that a change of data or defence that moves the accept set fails
    HERE, by name, not at the counter."""
    n, group = 7, 4
    off = _run_cluster([_cfg(i, n, 15860) for i in range(n)])
    accepted = _accepted_by_round(off)
    router = ov.Router(True, group, n, seed=3)
    assert sorted(accepted) == [0, 1], accepted
    for it, ids in accepted.items():
        gids = [router.gid_of(i) for i in ids]
        assert len(gids) > len(set(gids)), (
            f"round {it}: accepted workers {ids} share no subtree of "
            f"{group}: nothing to aggregate, choose another geometry")
    on = _run_cluster([_cfg(i, n, 15880, overlay=True, overlay_group=group)
                       for i in range(n)])
    assert _accepted_by_round(on) == accepted
    assert all(r["chain_dump"] == off[0]["chain_dump"] for r in off)
    assert all(r["chain_dump"] == on[0]["chain_dump"] for r in on)
    assert on[0]["chain_dump"] == off[0]["chain_dump"]
    lines = on[0]["chain_dump"].splitlines()
    assert len(lines) >= 3 and "ndeltas=0" not in lines[1]
    c_on = _overlay_counters(on)
    assert c_on.get("overlay_aggregate_registered", 0) > 0
    assert c_on.get("overlay_offer_sent", 0) > 0
    # the flat run must not have touched a single overlay path
    assert _overlay_counters(off) == {}
    # telemetry snapshot carries the overlay readout (docs/OVERLAY.md)
    snap = on[0]["telemetry"]["overlay"]
    assert snap["enabled"] and snap["depth"] == 3 \
        and snap["group_size"] == group


@pytest.mark.overlay
def test_plain_mode_overlay_relays_and_chains_equal():
    """Plain mode: update fan-out and block broadcast ride the relay —
    content untouched, so chains equal the flat run byte-for-byte."""
    n = 7
    kw = dict(secure_agg=False, verification=False, num_miners=2)
    off = _run_cluster([_cfg(i, n, 14110, **kw) for i in range(n)])
    on = _run_cluster([_cfg(i, n, 14140, overlay=True, overlay_group=3,
                            **kw) for i in range(n)])
    assert all(r["chain_dump"] == on[0]["chain_dump"] for r in on)
    assert on[0]["chain_dump"] == off[0]["chain_dump"]
    c = _overlay_counters(on)
    assert c.get("overlay_relayed_sent", 0) > 0
    assert c.get("overlay_relay_forwarded", 0) > 0


@pytest.mark.overlay
def test_corrupted_subtree_falls_back_to_exact_evidence():
    """A Byzantine leaf poisons its subtree's aggregate (corrupted share
    rows pass the relay's digest check but not the miner's RLC check):
    the miner refuses the aggregate, the relay degrades to per-member
    forwarding, and the per-update machinery rejects EXACTLY the
    offender — honest subtree members still contribute."""
    n = 7
    bad = 4  # a round-0 worker, grouped with worker 3 (group size 3)

    class Corrupt(PeerAgent):
        async def _overlay_submit_secret(self, it, commitment, u, shares,
                                         blind_rows, comms):
            shares = np.array(shares, np.int64)
            shares[:, 0] += 1  # breaks share-vs-commitment consistency
            return await super()._overlay_submit_secret(
                it, commitment, u, shares, blind_rows, comms)

    cfgs = [_cfg(i, n, 14170, overlay=True, overlay_group=3,
                 max_iterations=1) for i in range(n)]
    results = _run_cluster(cfgs, agent_cls=Corrupt, byzantine={bad})
    c = _overlay_counters(results)
    rejected = sum(r["counters"].get("submission_rejected", 0)
                   for r in results)
    # if the corrupted leaf was drawn as a worker this round, its
    # subtree aggregate must have been refused and re-tried per member,
    # with the offender rejected and honest members preserved
    if any(r["counters"].get("overlay_offer_sent", 0)
           or r["counters"].get("overlay_offer_local", 0)
           for i, r in enumerate(results) if i == bad):
        assert c.get("overlay_aggregate_refused", 0) > 0
        assert c.get("overlay_fallback_forwarded", 0) > 0
        assert rejected > 0
    dumps = [r["chain_dump"] for r in results]
    assert all(d == dumps[0] for d in dumps)
    assert "ndeltas=0" not in dumps[0].splitlines()[1]


@pytest.mark.overlay
def test_seeded_poison_verdicts_identical_with_overlay():
    """Seeded poison scenario: defense traffic is point-to-point and
    unaggregated by design, so the Krum verdicts — and with them the
    accepted/rejected records sealed into the chain — must be identical
    with the overlay on vs off. Chain equality covers verdict parity:
    blocks carry the accepted set, the rejected records, and the stake
    debits they feed."""
    n = 7
    kw = dict(poison_fraction=0.3, max_iterations=1)
    off = _run_cluster([_cfg(i, n, 14190, **kw) for i in range(n)])
    on = _run_cluster([_cfg(i, n, 14195, overlay=True, overlay_group=3,
                            **kw) for i in range(n)])
    assert all(r["chain_dump"] == on[0]["chain_dump"] for r in on)
    assert on[0]["chain_dump"] == off[0]["chain_dump"]
    # same defense outcomes, counted: rejected + declined workers agree
    for key in ("update_rejected", "submission_rejected"):
        assert sum(r["counters"].get(key, 0) for r in on) \
            == sum(r["counters"].get(key, 0) for r in off)
