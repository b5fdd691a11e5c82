"""Hive runtime tests (runtime/hive.py, docs/HIVE.md).

Unit level: the loopback fast path must be a TRANSPORT optimization,
not a semantics change — admission budgets, the seeded fault draw, and
wire byte accounting all still apply to in-process frames, and the
batched device plane must serve each co-hosted peer the SAME SGD delta
its standalone Trainer would compute (Trainer-parity randomness).

Integration level: a small hive is tier-1 (the co-hosting path cannot
rot behind the `slow` marker), a 2-hive split holds the cross-hive
chain-equality oracle over real TCP between hives, and the chaos-marked
2-hive x 100-peer cluster holds the surviving-prefix oracle under a
seeded drop + churn plan.
"""

import asyncio
from types import SimpleNamespace

import numpy as np
import pytest

from biscotti_tpu.config import BiscottiConfig, Defense, Timeouts
from biscotti_tpu.runtime import codecs as wcodecs
from biscotti_tpu.runtime.admission import AdmissionController, AdmissionPlan
from biscotti_tpu.runtime.faults import FaultAction, FaultPlan
from biscotti_tpu.runtime.hive import (LOOPBACK, LOOPBACK_RPCS_METRIC, Hive,
                                       HiveStepper, LoopbackHub,
                                       _frame_estimate)
from biscotti_tpu.runtime.rpc import BusyError, RPCError
from biscotti_tpu.telemetry.registry import MetricsRegistry

FAST = Timeouts(update_s=4.0, block_s=20.0, krum_s=4.0, share_s=4.0, rpc_s=6.0)


# ------------------------------------------------------- loopback endpoint


class _FakeAgent:
    """The slice of PeerAgent a LoopbackEndpoint touches: an id, the
    cluster address book, a server lifecycle flag + callee metrics, an
    AdmissionController, and the `_handle` dispatch."""

    def __init__(self, pid, port, metrics=None, plan=None, handler=None):
        self.id = pid
        self.peers = {pid: ("127.0.0.1", port)}
        self.server = SimpleNamespace(serving=True, metrics=metrics,
                                      service_delay_s=0.0)
        self.admission = AdmissionController(plan or AdmissionPlan())
        self._handler = handler
        self.handled = []

    async def _handle(self, msg_type, meta, arrays):
        self.handled.append((msg_type, meta, arrays))
        if self._handler is not None:
            return await self._handler(msg_type, meta, arrays)
        return {"ok": True}, {"echo": np.asarray(arrays["a"]) * 2.0}


def _lb_value(reg, name):
    """Sum of a counter family's series in `reg` (labels vary per test)."""
    fam = reg.snapshot().get(name)
    return sum(row["value"] for row in fam["series"]) if fam else 0.0


def test_loopback_call_roundtrip_readonly_views_and_accounting():
    async def scenario():
        hub = LoopbackHub()
        callee_reg, caller_reg = MetricsRegistry(), MetricsRegistry()
        agent = _FakeAgent(1, 13801, metrics=callee_reg)
        ep = hub.register(agent)
        assert hub.lookup("127.0.0.1", 13801) is ep
        assert hub.lookup("127.0.0.1", 13999) is None  # remote: TCP
        assert hub.local_ids == frozenset({1})

        sent = np.ones(4)
        meta, arrays = await ep.call("Echo", {"x": 5}, {"a": sent},
                                     timeout=5, src=0, metrics=caller_reg)
        assert meta == {"ok": True}
        assert np.array_equal(arrays["echo"], np.full(4, 2.0))
        # both directions are read-only views: the handler cannot mutate
        # what the caller handed it, nor the caller what the callee returned
        assert not arrays["echo"].flags.writeable
        _, hmeta, harrays = agent.handled[0]
        assert hmeta == {"x": 5}
        assert not harrays["a"].flags.writeable
        assert harrays["a"].base is sent  # aliased, never copied
        with pytest.raises(ValueError):
            harrays["a"][0] = 99.0

        # byte accounting: the would-be frame size lands on the CALLER's
        # registry under the `loopback` direction; the reply on the CALLEE's
        want = _frame_estimate({"x": 5}, {"a": sent})
        got = caller_reg.counter(wcodecs.WIRE_BYTES_METRIC).value(
            msg_type="Echo", direction=LOOPBACK, codec=wcodecs.RAW)
        assert got == want > sent.nbytes
        reply = callee_reg.counter(wcodecs.WIRE_BYTES_METRIC).value(
            msg_type="Echo.reply", direction=LOOPBACK, codec=wcodecs.RAW)
        assert reply > 0
        assert caller_reg.counter(LOOPBACK_RPCS_METRIC).value(
            msg_type="Echo", kind="call") == 1
        # admission released after the handler: inflight drained to zero
        assert agent.admission.inflight_total == 0

    asyncio.run(scenario())


def test_loopback_admission_still_sheds_on_fast_path():
    async def scenario():
        hub = LoopbackHub()
        # a zero-rate update bucket sheds the very first delivery
        plan = AdmissionPlan(enabled=True, update_rate=0.001,
                             burst_factor=0.001)
        agent = _FakeAgent(2, 13802, plan=plan)
        ep = hub.register(agent)
        with pytest.raises(BusyError):
            await ep.call("RegisterUpdate", {}, {"a": np.ones(2)},
                          timeout=2, src=0)
        assert not agent.handled, "shed frame must never reach the handler"
        assert agent.admission.shed_counts.get("rate", 0) >= 1
        assert agent.admission.inflight_total == 0

    asyncio.run(scenario())


def test_loopback_fault_injection_still_applies():
    async def scenario():
        hub = LoopbackHub()
        agent = _FakeAgent(3, 13803)
        ep = hub.register(agent)
        ones = np.ones(2)

        # reset: transport failure before delivery
        with pytest.raises(ConnectionError):
            await ep.call("Echo", {}, {"a": ones}, timeout=2, src=0,
                          fault=FaultAction(reset=True))
        assert not agent.handled

        # drop: the handler never runs, the caller waits out its budget
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        with pytest.raises(asyncio.TimeoutError):
            await ep.call("Echo", {}, {"a": ones}, timeout=0.08, src=0,
                          fault=FaultAction(drop=True))
        assert loop.time() - t0 >= 0.08
        assert not agent.handled

        # delay: delivered late, value intact
        t0 = loop.time()
        meta, _ = await ep.call("Echo", {}, {"a": ones}, timeout=2, src=0,
                                fault=FaultAction(delay_s=0.05))
        assert meta == {"ok": True} and loop.time() - t0 >= 0.05

        # duplicate: one awaited reply + one background delivery
        agent.handled.clear()
        await ep.call("Echo", {}, {"a": ones}, timeout=2, src=0,
                      fault=FaultAction(duplicate=True))
        for _ in range(50):
            if len(agent.handled) >= 2:
                break
            await asyncio.sleep(0.01)
        assert len(agent.handled) == 2

        # drop on a post: silently lost (fire-and-forget semantics)
        agent.handled.clear()
        await ep.post("Echo", {}, {"a": ones}, timeout=1, src=0,
                      fault=FaultAction(drop=True))
        await asyncio.sleep(0.05)
        assert not agent.handled

    asyncio.run(scenario())


def test_loopback_lifecycle_and_error_mapping():
    async def scenario():
        hub = LoopbackHub()

        async def boom(msg_type, meta, arrays):
            raise KeyError("handler bug")

        agent = _FakeAgent(4, 13804, handler=boom)
        ep = hub.register(agent)
        # a handler bug surfaces as RPCError, exactly like the TCP server
        with pytest.raises(RPCError, match="internal"):
            await ep.call("Echo", {}, {"a": np.ones(1)}, timeout=2, src=0)
        # a closed peer's endpoint stops resolving (callers fall to TCP
        # and get connection-refused) and refuses direct delivery
        agent.server.serving = False
        assert hub.lookup("127.0.0.1", 13804) is None
        with pytest.raises(ConnectionError):
            await ep._dispatch("Echo", {}, {}, src=0)

    asyncio.run(scenario())


# ---------------------------------------------------- batched device plane


def _cfg(i, n, port, **kw):
    base = dict(
        node_id=i, num_nodes=n, dataset="creditcard", base_port=port,
        num_verifiers=1, num_miners=1, num_noisers=1,
        secure_agg=False, noising=False, verification=False,
        max_iterations=2, convergence_error=0.0, sample_percent=1.0,
        batch_size=8, timeouts=FAST, seed=3,
    )
    base.update(kw)
    return BiscottiConfig(**base)


# what the stepper is built for in these tests: the classifier it always
# covered, a wider one, and a model with a frozen tree (refused until the
# stepper took models/peer_step.py's program: the tree is an argument)
STEPPED = {
    "creditcard": dict(),
    "mnist": dict(dataset="mnist", model_name="softmax", batch_size=10),
    "lm_tokens_tiny": dict(dataset="lm_tokens_tiny", batch_size=2,
                           learning_rate=0.1, grad_clip=1.0),
}


def _weights(stepper, kind):
    """Weights to step from: zeros, as a genesis round does; for the model
    with adapters, drawn (at zero they do not count in the forward)."""
    if kind != "lm_tokens_tiny":
        return np.zeros(stepper.num_params)
    import jax

    from biscotti_tpu.models.zoo import model_for_dataset

    return np.asarray(model_for_dataset("lm_tokens_tiny").flat_init(
        jax.random.PRNGKey(1)), np.float64)


@pytest.mark.parametrize("kind", sorted(STEPPED))
def test_hive_stepper_matches_standalone_trainers(kind):
    """Trainer-parity randomness: a hive-hosted peer's SGD delta is the
    same delta its standalone agent would compute (same fold_in key
    streams, same minibatch draw), to float tolerance — and the whole
    hive's round is ONE batched dispatch, served to every co-hosted
    caller from the same memoized batch."""
    from biscotti_tpu.data import datasets as ds
    from biscotti_tpu.models.trainer import Trainer

    n = 3
    cfg = _cfg(0, n, 13810, **STEPPED[kind])
    stepper = HiveStepper(cfg, range(n))
    w = _weights(stepper, kind)

    async def go():
        outs = await asyncio.gather(*(stepper.step(pid, w, 0)
                                      for pid in range(n)))
        noises = await asyncio.gather(*(stepper.noise(pid, 0)
                                        for pid in range(n)))
        errs = await asyncio.gather(*(stepper.test_error(w, 0)
                                      for _ in range(n)))
        return outs, noises, errs

    outs, noises, errs = asyncio.run(go())
    assert stepper.batches == 1, "co-hosted peers must share one dispatch"
    assert stepper.evals == 1
    for pid in range(n):
        t = Trainer(cfg.dataset, ds.shard_name(cfg.dataset, pid, False),
                    cfg=cfg, seed=pid)
        assert np.any(outs[pid])
        np.testing.assert_allclose(outs[pid], t.private_fun(w, 0),
                                   rtol=1e-5, atol=1e-6)
        assert errs[pid] == pytest.approx(t.test_error(w))
    # epsilon=0 run: noise is exactly zero without a per-peer bank
    assert all(not np.any(nz) for nz in noises)
    # distinct peers draw distinct minibatches (the peer axis is real)
    assert not np.allclose(outs[0], outs[1])


def _mesh():
    import jax

    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs the multi-device CPU mesh")
    return jax.sharding.Mesh(np.array(devices), ("peers",))


def _all_deltas(stepper, w, it):
    async def go():
        return np.stack([await stepper.step(pid, w, it)
                         for pid in stepper.local_ids])

    return asyncio.run(go())


@pytest.mark.parametrize("kind", sorted(STEPPED))
def test_hive_stepper_on_the_mesh_matches_one_device(kind):
    """One program, two placements: the stack and the keys sharded over
    eight devices, and on one (chip_smoke.py checks the same on four
    chips)."""
    mesh = _mesh()
    n = 2 * mesh.devices.size
    cfg = _cfg(0, n, 13814, **STEPPED[kind])
    on_one = HiveStepper(cfg, range(n))  # first: the mesh moves the tree
    on_mesh = HiveStepper(cfg, range(n), mesh)
    assert on_mesh.n_dev == mesh.devices.size and on_one.n_dev == 1
    assert len({s.device for s in on_mesh._x.addressable_shards}) \
        == mesh.devices.size
    assert on_mesh._x.addressable_shards[0].data.shape[0] == 2
    w = _weights(on_one, kind)
    got, want = _all_deltas(on_mesh, w, 1), _all_deltas(on_one, w, 1)
    assert on_mesh.batches == on_one.batches == 1
    assert got.shape == (n, on_one.num_params) and np.any(want)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    async def errs():
        return (await on_mesh.test_error(w, 1),
                await on_one.test_error(w, 1))

    e_mesh, e_one = asyncio.run(errs())
    assert e_mesh == pytest.approx(e_one)
    # a span the mesh does not divide stays on one device
    assert HiveStepper(cfg, range(n - 1), mesh).n_dev == 1


def test_hive_stepper_walks_blocks_on_the_mesh_and_shares_the_frozen_tree(
        monkeypatch):
    """On a mesh a block of peers is sized from what ONE device has free
    (not the devices' memory together), the walked program (here: a peer a
    block, sixteen blocks over the sharded axis) gives what one device
    gives in one block, and the frozen tree is the process's one copy,
    replicated: a co-hosted Trainer built afterwards holds the same
    arrays."""
    import jax

    from biscotti_tpu.data import datasets as ds
    from biscotti_tpu.models import peer_step
    from biscotti_tpu.models.trainer import Trainer

    free = []

    class Sized(peer_step.PeerSteps):
        def __init__(self, model, cfg, rows, samples, free_bytes):
            free.append((samples, free_bytes))
            super().__init__(model, cfg, rows, samples, free_bytes)

    monkeypatch.setattr(peer_step, "PeerSteps", Sized)
    mesh = _mesh()
    n = 2 * mesh.devices.size
    cfg = _cfg(0, n, 13818, **STEPPED["lm_tokens_tiny"])
    on_one = HiveStepper(cfg, range(n))
    on_mesh = HiveStepper(cfg, range(n), mesh)
    assert on_one.steps.block == on_mesh.steps.block == n  # the tiny model
    (s_one, free_one), (s_mesh, free_mesh) = free
    assert s_one == s_mesh == n
    # less of the stack stands on a device of the mesh, and nothing more
    assert free_one < free_mesh < peer_step.device_bytes()
    on_mesh.steps.block = 1  # before the program is traced
    w = _weights(on_one, "lm_tokens_tiny")
    got, want = _all_deltas(on_mesh, w, 1), _all_deltas(on_one, w, 1)
    assert np.any(want)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    leaves = jax.tree.leaves(on_mesh._frozen)
    assert leaves and all(
        leaf.sharding.is_fully_replicated
        and len(leaf.addressable_shards) == mesh.devices.size
        for leaf in leaves)
    light = Trainer(cfg.dataset, ds.shard_name(cfg.dataset, 0, False),
                    cfg=cfg, seed=0, light=True)
    assert all(a is b for a, b in zip(jax.tree.leaves(light.frozen), leaves))
    assert light.test_error(w) == pytest.approx(
        asyncio.run(on_mesh.test_error(w, 1)))


def test_hive_stack_reports_the_layout_it_was_put_in(monkeypatch):
    """The stepper's stack goes up through `put_stack`: on a device whose
    default is not the layout `stack_layout` asks for (here: asked for by
    the test, peer axis between rows and features; the CPU holds any), the
    stack sits in that layout, says so, and the deltas are the same."""
    from jax.experimental.layout import Layout

    from biscotti_tpu.models import peer_step

    cfg = _cfg(0, 4, 13816, **STEPPED["mnist"])
    plain = HiveStepper(cfg, range(4))
    info = plain.stack_info()
    assert info["layout"] == "2,1,0" and info["row_major"] is True
    assert info["compact_bytes"] == info["device_bytes"] \
        == 4 * plain._x.shape[1] * 784 * 4
    assert not plain._x.committed

    def other(shape, itemsize=4):
        return Layout(major_to_minor=(1, 0, 2)) if len(shape) == 3 else None

    monkeypatch.setattr(peer_step, "stack_layout", other)
    moved = HiveStepper(cfg, range(4))
    info = moved.stack_info()
    assert info["layout"] == "2,0,1" and info["row_major"] is False
    assert moved._x.committed  # a layout of its own commits the stack
    assert tuple(moved._x.format.layout.major_to_minor) == (1, 0, 2)
    assert moved._y.format.layout == plain._y.format.layout
    w = np.zeros(plain.num_params)
    np.testing.assert_array_equal(_all_deltas(moved, w, 2),
                                  _all_deltas(plain, w, 2))


def test_hive_stepper_refuses_unequal_shards_and_hive_falls_back(
        monkeypatch):
    """Truncating co-hosted shards to a common row count would change
    which rows `sample_batch` can draw vs each peer's standalone
    Trainer — so unequal shards must refuse to batch, and the Hive must
    fall back to exact per-agent trainers instead of silently breaking
    parity."""
    from biscotti_tpu.data import datasets as ds
    from biscotti_tpu.runtime.hive import UnequalShardsError

    real = ds.load_shard

    def uneven(dataset, shard):
        out = dict(real(dataset, shard))
        if shard.endswith("1"):  # one peer's shard is short
            out = {k: (v[:-5] if k in ("x_train", "y_train") else v)
                   for k, v in out.items()}
        return out

    monkeypatch.setattr(ds, "load_shard", uneven)
    cfg = _cfg(0, 3, 13812)
    with pytest.raises(UnequalShardsError, match="unequal"):
        HiveStepper(cfg, range(3))
    h = Hive(cfg, range(3), hive_id="fb")
    assert h.stepper is None
    assert "unequal" in h.stepper_fallback
    # agents got FULL trainers: standalone sampling streams, exact
    assert all(not a.trainer.light for a in h.agents)


def test_light_trainer_holds_no_private_state_and_shares_eval():
    from biscotti_tpu.data import datasets as ds
    from biscotti_tpu.models.trainer import Trainer

    cfg = _cfg(1, 3, 13811)
    full = Trainer(cfg.dataset, ds.shard_name(cfg.dataset, 1, False), cfg=cfg,
                   seed=1)
    light = Trainer(cfg.dataset, ds.shard_name(cfg.dataset, 1, False), cfg=cfg,
                    seed=1, light=True)
    assert light.x_train is None and light.noise_samples is None
    # eval splits are process-shared device buffers, not per-peer copies
    assert light.x_test is full.x_test
    w = np.zeros(light.num_params)
    assert light.test_error(w) == pytest.approx(full.test_error(w))
    for fn in (lambda: light.private_fun(w, 0),
               lambda: light.get_noise(0),
               lambda: light.train_error(w),
               lambda: light.roni(w, w)):
        with pytest.raises(RuntimeError, match="light"):
            fn()


# ------------------------------------------------------- hive integration


def _loopback_rpcs(agents):
    return sum(_lb_value(a.pool.metrics, LOOPBACK_RPCS_METRIC)
               for a in agents if a.pool.metrics is not None)


def test_hive_small_cluster_tier1_chains_equal():
    """The tier-1 co-hosting smoke (small H, fast): one hive's peers run
    a full protocol round over the loopback transport + batched device
    plane and land identical chains, with real loopback traffic counted
    and the per-hive readout surfaced through telemetry."""
    n = 5
    hive = Hive(_cfg(0, n, 13820), hive_id="t1")
    results = asyncio.run(hive.run())
    assert len(results) == n
    dumps = {r["chain_dump"] for r in results}
    assert len(dumps) == 1, "co-hosted chains diverged"
    assert len(results[0]["chain_dump"].splitlines()) >= 2, \
        "no real block landed"
    # the device plane actually batched (one dispatch per round, not n)
    assert 1 <= hive.stepper.batches <= 2 * n
    # the loopback fast path actually carried traffic
    assert _loopback_rpcs(hive.agents) > 0
    # per-hive readout: shared dict, surfaced under telemetry["hive"]
    snap = hive.agents[0].telemetry_snapshot()
    assert snap["hive"]["id"] == "t1"
    assert snap["hive"]["peers"] == n


# warm budgets (tests/test_overlay.py's): the first round compiles the
# delta program for the eight-device mesh, the noise draw and the share
# pipeline, beside five other workers' compiles under the driver's command,
# and a 4 s update timer firing on that mints an empty first block.
# Deadlines only bound the unhappy path; the happy path proceeds on events
WARM = Timeouts(update_s=20.0, block_s=60.0, krum_s=20.0, share_s=20.0,
                rpc_s=10.0)


def _device_cfg(n, port, **kw):
    return _cfg(0, n, port, verification=True, defense=Defense.NONE,
                timeouts=WARM, **kw)


@pytest.mark.parametrize("secure_agg", [False, True],
                         ids=["plain", "secure_agg"])
def test_device_peers_mint_real_blocks(secure_agg):
    """Peers-as-devices: the 8-device CPU mesh hosts all peers' SGD steps
    as ONE sharded program per round, while the full asyncio protocol —
    verifier committees, (with `secure_agg`) VSS shares and DP noise,
    block gossip — runs over real TCP between the agents and the
    chain-equality oracle closes the loop."""
    mesh = _mesh()
    n = mesh.devices.size
    hive = Hive(_device_cfg(n, 15520 if secure_agg else 15510,
                            secure_agg=secure_agg, noising=secure_agg),
                mesh=mesh, loopback=False)
    assert hive.hub is None and hive.stepper.n_dev == n
    results = asyncio.run(hive.run())
    dumps = [r["chain_dump"] for r in results]
    assert all(d == dumps[0] for d in dumps), "chain-equality oracle violated"
    lines = dumps[0].splitlines()
    assert len(lines) >= 2 and "ndeltas=0" not in lines[1], dumps[0]
    # the data plane really ran on the mesh: one sharded batch per round,
    # not one XLA call per peer
    assert hive.stepper.batches >= 1
    if not secure_agg:
        assert len(lines) == 3 and hive.stepper.batches <= 3
    assert _loopback_rpcs(hive.agents) == 0  # TCP between the agents


def test_stepper_shared_metric_memoizes():
    """The per-round convergence metric is computed once per distinct
    (iteration, weights) and served to every co-located peer — the shared
    eval the scale harness leans on (identical model × identical global
    test split, peer.py's uniform-convergence requirement)."""
    mesh = _mesh()
    n = mesh.devices.size
    stepper = HiveStepper(_device_cfg(n, 15530), range(n), mesh)
    w = np.zeros(stepper.num_params, np.float64)
    w2 = np.ones(stepper.num_params, np.float64)

    async def drive():
        # n peers ask for the same (it, w); then one divergent chain
        a = await asyncio.gather(*(stepper.test_error(w, 0)
                                   for _ in range(n)))
        b = await stepper.test_error(w2, 0)
        c = await stepper.test_error(w, 1)
        return a, b, c

    a, b, c = asyncio.run(drive())
    assert len(set(a)) == 1
    assert stepper.evals == 3  # (0,w) shared by all peers; (0,w2); (1,w)
    assert a[0] == c  # same weights at a later height: same value


def test_two_hives_cross_tcp_chains_equal():
    """Cross-hive interop (tier-1): the cluster split across TWO hives —
    loopback inside each, real TCP between them — holds the cross-hive
    chain-equality oracle that per-process output alone cannot see."""
    n = 6
    cfg = _cfg(0, n, 13830)
    h1 = Hive(cfg, range(0, 3), hive_id="h1")
    h2 = Hive(cfg, range(3, 6), hive_id="h2")
    assert h1.hub.local_ids == frozenset({0, 1, 2})
    assert h2.hub.local_ids == frozenset({3, 4, 5})

    async def go():
        return await asyncio.gather(h1.run(), h2.run())

    r1, r2 = asyncio.run(go())
    dumps = {r["chain_dump"] for r in r1 + r2}
    assert len(dumps) == 1, "chains forked across hives"
    assert _loopback_rpcs(h1.agents + h2.agents) > 0


@pytest.mark.slow
@pytest.mark.chaos
def test_chaos_two_hives_hundred_peers_drop_and_churn():
    """The density chaos gate: 2 hives x 50 co-hosted peers (N=100 on
    one box) under a seeded drop + churn plan. Loopback and TCP frames
    both pay the fault draw; churned peers self-kill mid-run and their
    loopback endpoints stop resolving. The surviving prefix must stay
    equal across ALL peers of BOTH hives."""
    from biscotti_tpu.runtime.membership import surviving_prefix_oracle

    n, rounds = 100, 3
    plan = FaultPlan(seed=23, drop=0.02, delay=0.10, delay_s=0.02,
                     churn=0.05, churn_period=2, churn_down=1)
    assert plan.churn_schedule(n, rounds), "seed must actually churn"
    cfg = _cfg(0, n, 13700, max_iterations=rounds, fault_plan=plan,
               timeouts=Timeouts(update_s=8.0, block_s=40.0, krum_s=8.0,
                                 share_s=8.0, rpc_s=10.0))
    h1 = Hive(cfg, range(0, 50), hive_id="c1")
    h2 = Hive(cfg, range(50, 100), hive_id="c2")

    async def go():
        return await asyncio.gather(h1.run(), h2.run())

    r1, r2 = asyncio.run(go())
    results = r1 + r2
    assert len(results) == n
    equal, settled, _ = surviving_prefix_oracle(results)
    assert equal, "chains diverged under drop+churn across hives"
    assert settled >= 1, f"no progress under chaos: settled={settled}"
    # injected faults actually fired on this run
    injected = sum(sum(r.get("faults", {}).values()) for r in results)
    assert injected > 0, "fault plan never fired"


# -------------------------------------------------------------- obs merge


def test_obs_merges_per_hive_table():
    """The obs CLI's per-host columns (tools/obs.py merge_hives): peers
    of one hive collapse into one row keyed by hive id, keeping the max
    RSS / loop-lag samples seen, and the rendered cluster table carries
    the co-hosted count, RSS/peer, and the event-loop lag gauge."""
    from biscotti_tpu.tools import obs

    def snap(hid, peers, rss, lag, drift=0):
        return {"hive": {"id": hid, "peers": peers, "rss_bytes": rss,
                         "rss_peak_bytes": rss, "loop_lag_s": lag,
                         "rss_drift_bytes": drift,
                         "loop_lag_drift_s": lag / 10}}

    snaps = [snap("h0", 2, 100 << 20, 0.01, drift=1 << 20),
             snap("h0", 2, 120 << 20, 0.5),
             snap("h1", 3, 90 << 20, 0.02), {"other": True}]
    # avoided-traffic accounting: loopback-direction wire bytes must
    # surface in the merged wire table (a fully co-hosted cluster would
    # otherwise read "out 0B" and the layout comparison goes dark)
    snaps[0]["metrics"] = {"biscotti_wire_bytes_total": {
        "type": "counter", "series": [
            {"labels": {"msg_type": "RegisterBlock",
                        "direction": "loopback", "codec": "raw64"},
             "value": 4096}]}}
    merged = obs.merge_snapshots(snaps)
    assert merged["wire"]["loopback_bytes"] == 4096
    hives = merged["hives"]
    assert set(hives) == {"h0", "h1"}
    assert hives["h0"]["scraped"] == 2
    assert hives["h0"]["rss_peak_bytes"] == 120 << 20  # freshest sample
    assert hives["h0"]["loop_lag_s"] == 0.5            # starvation visible
    assert hives["h0"]["rss_per_peer_bytes"] == (120 << 20) // 2
    assert hives["h1"]["peers_cohosted"] == 3
    # drift keeps the worst window even when a later scrape reads lower
    assert hives["h0"]["rss_drift_bytes"] == 1 << 20
    assert hives["h0"]["loop_lag_drift_s"] == 0.05
    table = obs.format_table(merged)
    assert "rss/peer" in table and "looplag" in table
    assert "rssdrift" in table and "1.0MB" in table
    assert "h0" in table and "0.5000" in table
    assert "loopback 4.0KB avoided" in table


def test_drift_is_quarter_median_delta():
    """runtime/hive.drift: windowed RSS/loop-lag drift must survive
    allocator sawtooth (quarter medians, not last-minus-first) and stay
    zero until the window holds one sample per quarter."""
    from biscotti_tpu.runtime.hive import drift

    assert drift([]) == 0.0
    assert drift([5.0, 6.0, 7.0]) == 0.0          # <4 samples: no signal
    # monotone leak: newest-quarter median minus oldest-quarter median
    assert drift([0.0, 1.0, 2.0, 3.0]) == 3.0
    assert drift(list(range(8))) == pytest.approx((6 + 7) / 2 - (0 + 1) / 2)
    # sawtooth with no trend: one outlier spike must NOT read as drift
    saw = [100.0, 104.0] * 12                     # quarter = 6, even
    assert drift(saw) == 0.0
    assert abs(drift(saw + [400.0])) <= 4.0       # spike stays invisible
    # flat-then-step leak is visible
    assert drift([100.0] * 10 + [164.0] * 10) == 64.0
