"""Peers-as-devices deployment mode — the data plane on the mesh, the
control plane in the runtime (SURVEY §7.1's "same round logic, two
launchers", §5.8's integration of the two planes).

The plain in-process cluster runs N peer agents whose SGD steps each
dispatch their own XLA call. Here ONE sharded XLA program computes EVERY
local peer's delta per round — `shard_map` over a `Mesh` peer axis, each
device holding its peers' shards — while the agents keep speaking the
full protocol (verifier committees, VSS shares, block gossip, stake).
Device peers therefore mint REAL blocks through the runtime; the
reference's closest analogue is 5 OS processes per VM with no sharing at
all (ref: azure/azure-run/runBiscotti.sh nodesInEachVM).

    stepper = BatchStepper(cfg, mesh)           # one per host process
    agents  = [PeerAgent(cfg_i, stepper=stepper) for i in local_ids]

The stepper computes all N deltas at a round's FIRST request (one sharded
dispatch; one all-gather back to host) and serves every other agent from
that batch — peers advance in protocol lockstep, so the batch hit rate is
the worker count.

Launcher CLI (the "second launcher"):
    python -m biscotti_tpu.runtime.device_cluster -t 8 -d mnist \
        --iterations 3   # mesh over all visible devices
"""

from __future__ import annotations

import asyncio
import math
from typing import Dict, Optional

import numpy as np


async def single_flight_memo(cache: Dict, pending: Dict, key, compute):
    """Single-flight async memo shared by the batched device planes
    (BatchStepper here, hive.HiveStepper): the first caller computes
    off-loop, every concurrent waiter receives the VALUE from the future
    itself (never a post-await cache re-read — another peer far enough
    ahead may evict the key between set_result and a waiter resuming),
    and a failed compute raises in every caller. Returns
    (value, computed_here)."""
    if key in cache:
        return cache[key], False
    if key in pending:
        return await pending[key], False
    fut = asyncio.get_running_loop().create_future()
    pending[key] = fut
    try:
        val = await asyncio.to_thread(compute)
    except BaseException as e:
        fut.set_exception(e)
        fut.exception()  # mark retrieved if nobody is waiting
        del pending[key]
        raise
    cache[key] = val
    fut.set_result(val)
    del pending[key]
    return val, True


class BatchStepper:
    """Round-batched sharded SGD: all peers' deltas in one XLA call.

    Thread-compatible with the asyncio agents: `step()` is async and the
    underlying sharded dispatch runs in a worker thread. Per-iteration
    batches are cached (keyed by iteration) and evicted once consumed, so
    memory stays at O(batches_in_flight · N · d)."""

    def __init__(self, cfg, mesh, axis: str = "peers"):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from biscotti_tpu.data import datasets as ds
        from biscotti_tpu.models.trainer import (local_step_fn,
                                                 sample_batch, step_rule)
        from biscotti_tpu.models.zoo import model_for_dataset
        from biscotti_tpu.parallel.sim import _poisoned_ids

        self.cfg = cfg
        self.axis = axis
        self.mesh = mesh
        n = cfg.num_nodes
        n_dev = math.prod(mesh.devices.shape)
        if n % n_dev != 0:
            raise ValueError(f"num_nodes {n} must divide over {n_dev} devices")

        model = model_for_dataset(cfg.dataset,
                                  getattr(cfg, "model_name", ""))
        self.num_params = model.num_params
        if model.init_frozen is not None:
            # ROADMAP B0's remainder: the frozen base on the live path
            raise NotImplementedError(
                f"model {model.name!r} holds a frozen tree; the batched "
                "live plane steps classifiers only (the simulator and the "
                "per-peer Trainer take it)")
        mode, rate = step_rule(model, cfg)
        step = local_step_fn(model, mode, clip=cfg.grad_clip, alpha=rate)

        poisoned = _poisoned_ids(n, cfg.poison_fraction)
        xs, ys = [], []
        for i in range(n):
            shard = ds.load_shard(cfg.dataset,
                                  ds.shard_name(cfg.dataset, i, i in poisoned))
            xs.append(shard["x_train"])
            ys.append(shard["y_train"])
        rows = min(len(x) for x in xs)
        x_all = jnp.asarray(np.stack([x[:rows] for x in xs]))
        y_all = jnp.asarray(np.stack([y[:rows] for y in ys]))
        root = jax.random.PRNGKey(cfg.seed)
        batch = min(cfg.batch_size, rows)

        def local_deltas(w, x_loc, y_loc, it):
            pid = jax.lax.axis_index(axis)
            n_loc = x_loc.shape[0]
            gids = pid * n_loc + jnp.arange(n_loc)
            bkey = jax.random.fold_in(root, it)

            def one(gid, xi, yi):
                k = jax.random.fold_in(bkey, gid)
                idx = sample_batch(k, rows, batch)
                return step(w, xi[idx], yi[idx])

            return jax.vmap(one)(gids, x_loc, y_loc)

        mapped = jax.shard_map(
            local_deltas, mesh=mesh,
            in_specs=(P(), P(axis), P(axis), P()),
            out_specs=P(axis), check_vma=False,
        )
        self._step = jax.jit(mapped)
        sharding = NamedSharding(mesh, P(axis))
        self._x = jax.device_put(x_all, sharding)
        self._y = jax.device_put(y_all, sharding)

        self._cache: Dict[int, np.ndarray] = {}
        self._pending: Dict[int, asyncio.Future] = {}
        self._served: Dict[int, int] = {}
        self.batches = 0  # sharded dispatch count (observability/tests)

        # shared convergence metric: every peer scores the SAME model on the
        # SAME global test split each round (peer.py's uniform-convergence
        # requirement), so one evaluation serves the whole cluster. Keyed on
        # (iteration, weight digest) — transiently divergent chains compute
        # their own value, identical chains share one.
        test = ds.load_shard(cfg.dataset, f"{cfg.dataset}_test")
        self._x_test = jnp.asarray(test["x_test"])
        self._y_test = jnp.asarray(test["y_test"])
        self._err_fn = jax.jit(model.error_flat)
        self._eval_cache: Dict[tuple, float] = {}
        self._eval_pending: Dict[tuple, asyncio.Future] = {}
        self.evals = 0  # distinct metric computations (observability/tests)

    async def _memo(self, cache: Dict, pending: Dict, key, compute):
        return await single_flight_memo(cache, pending, key, compute)

    async def step(self, peer_id: int, w: np.ndarray, it: int) -> np.ndarray:
        """This peer's delta for iteration `it`; the first caller computes
        the whole batch on the mesh."""
        import jax.numpy as jnp

        def compute():
            return np.asarray(
                self._step(jnp.asarray(w, jnp.float32), self._x, self._y,
                           it), dtype=np.float64)

        deltas, computed = await self._memo(self._cache, self._pending, it,
                                            compute)
        if computed:
            self.batches += 1
        delta = deltas[peer_id]
        self._served[it] = self._served.get(it, 0) + 1
        if self._served[it] >= self.cfg.num_nodes:
            self._cache.pop(it, None)  # everyone served: evict
        # keep at most a few rounds resident regardless of stragglers
        for old in [k for k in self._cache if k < it - 3]:
            self._cache.pop(old, None)
        return delta

    async def test_error(self, w: np.ndarray, it: int) -> float:
        """Global-test-split error of `w` — computed once per distinct
        (iteration, weights) across the cluster; all other peers are served
        from the memo (they evaluate identical inputs, see __init__)."""
        import hashlib

        import jax.numpy as jnp

        wb = np.ascontiguousarray(w)
        key = (it, hashlib.sha1(wb.tobytes()).hexdigest())

        def compute():
            return float(self._err_fn(jnp.asarray(wb, jnp.float32),
                                      self._x_test, self._y_test))

        err, computed = await self._memo(self._eval_cache,
                                         self._eval_pending, key, compute)
        if computed:
            self.evals += 1
        for old in [k for k in self._eval_cache if k[0] < it - 3]:
            self._eval_cache.pop(old, None)
        return err


async def run_cluster(cfg_base, mesh, iterations: int, log_dir: str = ""):
    """Boot N agents sharing one BatchStepper; returns
    (stepper, agents, results)."""
    import os

    from biscotti_tpu.runtime.peer import PeerAgent

    stepper = BatchStepper(cfg_base, mesh)
    agents = []
    for i in range(cfg_base.num_nodes):
        cfg = cfg_base.replace(node_id=i, max_iterations=iterations)
        agents.append(PeerAgent(
            cfg, stepper=stepper,
            log_path=os.path.join(log_dir, f"events_{i}.jsonl")
            if log_dir else ""))
    results = await asyncio.gather(*(a.run() for a in agents))
    return stepper, agents, results


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(
        description="peers-as-devices cluster launcher (SURVEY §7.1)")
    from biscotti_tpu.config import BiscottiConfig

    BiscottiConfig.add_args(ap)
    ap.add_argument("--iterations", type=int, default=3)
    ns = ap.parse_args(argv)
    import jax

    from biscotti_tpu.utils import jaxenv

    jax.config.update("jax_enable_x64", True)
    jaxenv.configure_compile_cache()
    cfg = BiscottiConfig.from_args(ns)

    devices = np.array(jax.devices())
    mesh = jax.sharding.Mesh(devices, ("peers",))
    stepper, agents, results = asyncio.run(
        run_cluster(cfg, mesh, ns.iterations))
    dumps = [r["chain_dump"] for r in results]
    summary = {
        "mode": "peers-as-devices",
        **jaxenv.device_info(),
        "devices": len(devices),
        "nodes": cfg.num_nodes,
        "sharded_batches": stepper.batches,
        "chains_equal": all(d == dumps[0] for d in dumps),
        "blocks": len(dumps[0].splitlines()) - 1,
    }
    print(json.dumps(summary))
    return 0 if summary["chains_equal"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
