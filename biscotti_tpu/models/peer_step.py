"""How a stack of co-resident peers' shards becomes their [S, d] deltas.

ONE definition beneath the simulator (parallel/sim.py) and the live
runtime's batched plane (runtime/hive.py): which shards load, how their
stack reaches its devices (`put_stack`, in the layout `stack_layout`
reads rows from), how the minibatch rows leave it (`PeerSteps.minibatches`:
one composed gather), and how the peer axis is stepped
(`PeerSteps.deltas`: `trainer.block_step_fn` over blocks of `peer_block`
peers). What differs between the callers stays theirs: the key stream
(they hand in one key a peer), which peers step and where their shards sit
in the stack (`at`), the frozen tree (an argument), and the noise.
`Trainer._private` (models/trainer.py) is the same step for one peer on
one shard: the oracle the parity tests compare against.
"""

from __future__ import annotations

import contextlib
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout

from biscotti_tpu.data import datasets as ds
from biscotti_tpu.models.base import Model
from biscotti_tpu.models.trainer import block_step_fn, sample_batch, step_rule

# The peer stack is held on the device in the layout the round READS: the
# round takes S x B single rows out of [N, rows, d], so a row has to be
# contiguous, i.e. the feature axis minor-most and the peer axis major-most
# (row-major). Left to itself the TPU runtime picks whatever tiling pads
# least: for [3383, 480, 784] that is the PEER axis in the lanes (784 is
# 6.125 lanes of 128), and taking rows from it means a relayout of the whole
# stack every round (PERF.md section 6, PR 25). Row-major pays for its
# padding in device memory, so it is asked for only where the padded stack
# stays within this factor of the compact one: 784 features cost 1.14x,
# 3,072 and 8,742 at most 1.01x; creditcard's 24 would cost 5.3x, and such
# stacks (megabytes) keep the runtime's default.
STACK_PAD_LIMIT = 1.25

# What the runtime of one chip of the fleet this is written for (TPU v5e)
# states as its memory, `memory_stats()["bytes_limit"]` read on the chip,
# for where the backend does not say (`memory_stats()` is None on the CPU):
# 15.75 GiB, NOT the 16 GiB of the data sheet. With 16 the CPU tests worked
# out a DeepSeek-V2 block of 3 for a cell that ran 1 on the chip through
# two PRs' records (PERF.md section 6, PR 35).
DEVICE_BYTES = 16909336064

# The share of what the standing arrays leave free (the caller's `standing`:
# the base, the stacks, the round's deltas and noise) that a block of
# peers' `step_bytes` may take. By `b x step_bytes / free` the published
# cells' candidates read: DeepSeek-V2 3: 0.506 (7: 1.18); Granite 3: 0.695
# (1: 0.23); Laguna 3: 0.325 (7: 0.758, its scores still counted:
# `laguna.step_bytes` says why). 0.6 takes DeepSeek-V2's 3, which half
# missed by 33 MB: that round compiles for the v5e at 15.00 GB with its
# arguments and code of the 16.91 the chip states, and on the chip is 10%
# faster than a peer at a time (264 streams of a 629 MB expert stack where
# 768; PERF.md section 6, PR 35). It leaves Granite at 1: its 3 compiles
# (7.43 GB of temporaries) but nothing says it is faster and one scan call
# of three windows takes 1.94 x three calls of one. Any share in 0.51-0.69
# gives the same three blocks. The other 40% of the free bytes (2.1 GB in
# the tightest cell) are for what a compiled round needs beyond `standing`
# and its peers' count (DeepSeek-V2: 0.5 GB of the walked attention's
# stacked results), its code (0.13 GB) and fragmentation.
BLOCK_SHARE = 0.6


def peer_block(samples: int, step_bytes: Optional[int], free: int) -> int:
    """How many of a round's `samples` peers step together: all of them
    where the model states no activation size (every classifier), else the
    largest divisor of `samples` whose block of `step_bytes` a peer fits
    BLOCK_SHARE of the `free` bytes (at least one peer). A divisor, so that
    every block is the same program."""
    if not step_bytes:
        return samples
    fit = max(1, int(BLOCK_SHARE * free) // step_bytes)
    return max(b for b in range(1, samples + 1)
               if samples % b == 0 and b <= fit)


def device_bytes() -> int:
    """The first device's memory, as the runtime states it."""
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", DEVICE_BYTES))


def stack_layout(shape, itemsize: int = 4) -> Optional[Layout]:
    """The device layout the round reads a peer stack of `shape` in, decided
    from the shape alone: row-major (the last axis minor, the peer axis
    major) where the TPU's tiling of the two minor-most axes (8 x 128 of a
    32-bit type, 128 lanes minor) pads it by at most STACK_PAD_LIMIT, else
    None, the runtime's default. On a backend whose default is row-major
    already (the CPU) asking for it changes nothing."""
    if len(shape) < 2 or 0 in shape:
        return None
    sublanes = 8 * max(1, 4 // itemsize)
    padded = (math.prod(shape[:-2])
              * -(-shape[-2] // sublanes) * sublanes
              * -(-shape[-1] // 128) * 128)
    if padded > STACK_PAD_LIMIT * math.prod(shape):
        return None
    return Layout(major_to_minor=tuple(range(len(shape))))


@contextlib.contextmanager
def outside_compile_cache():
    """What compiles inside compiles with the persistent compile cache
    switched off, and is neither fetched from it nor written to it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()  # the switch is read once a process
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def put_stack(a, sharding=None) -> jax.Array:
    """`a` (a host or a device array) onto `sharding` (None: where
    `jnp.asarray` puts it), in `stack_layout`'s layout: THE way a peer
    stack reaches its device, on one chip and on a mesh. Placement first,
    then the layout: this JAX moves data between host and devices in the
    runtime's default layout only, and `device_put` to a `Format` is a
    relayout program on the devices that already hold the array (the
    default copy is freed when it ends; both exist while it runs). Where
    the default is the layout asked for, nothing more happens.

    The relayout program compiles outside the persistent compile cache
    (a fraction of a second): fetched back from it, an executable with a
    layout of its own on its OUTPUT hands out buffers that report the
    default layout while holding the other (v5e, JAX 0.9.0; PERF.md section
    6, PR 25), and every program that then takes the stack is compiled for
    the wrong one and refused when it runs."""
    a = jnp.asarray(a) if sharding is None else jax.device_put(a, sharding)
    layout = stack_layout(a.shape, a.dtype.itemsize)
    if layout is None or (tuple(a.format.layout.major_to_minor)
                          == layout.major_to_minor):
        return a
    with outside_compile_cache():
        return jax.device_put(a, Format(layout, a.sharding))


def stack_info(x: jax.Array) -> dict:
    """Where the peer stack `x` sits: its device layout as the runtime
    prints it, its bytes on one device (tiling padding included) and its
    compact bytes. Row-major (`2,1,0`) is what `stack_layout` asks for
    unless the padding would pass STACK_PAD_LIMIT."""
    layout = x.format.layout
    tiling = "".join("T(%s)" % ",".join(map(str, t))
                     for t in layout.tiling or ())
    minor_to_major = ",".join(
        str(a) for a in reversed(layout.major_to_minor))
    shard = x.addressable_shards[0].data
    return {
        "layout": minor_to_major + (":" + tiling if tiling else ""),
        "row_major": tuple(layout.major_to_minor) == tuple(range(x.ndim)),
        "device_bytes": int(shard.on_device_size_in_bytes()),
        "compact_bytes": int(x.nbytes),
    }


def _poisoned_ids(num_nodes: int, poison_fraction: float) -> set:
    """Top poison_fraction of node ids load bad shards
    (ref: DistSys/main.go:836-845, honest.go:102-118). THE formula lives
    in tools/verdicts.poisoned_ids — one definition shared with the
    campaign plane's attacker draw and every verdict reader; this name is
    the alias the simulator and the live runtime load their shards by."""
    from biscotti_tpu.tools.verdicts import poisoned_ids

    return poisoned_ids(num_nodes, poison_fraction)


def load_shards(cfg, ids):
    """The train shards of the peers `ids` of `cfg`'s cluster, the
    poisoned ones' bad shards included: (xs, ys), one host array a peer.
    What to do about unequal row counts is the caller's policy."""
    poisoned = _poisoned_ids(cfg.num_nodes, cfg.poison_fraction)
    xs, ys = [], []
    for i in ids:
        shard = ds.load_shard(
            cfg.dataset, ds.shard_name(cfg.dataset, i, i in poisoned))
        xs.append(shard["x_train"])
        ys.append(shard["y_train"])
    return xs, ys


class PeerSteps:
    """The local steps of the peers that share a stack, as two pure
    functions to trace into the caller's program: `samples` peers a call,
    each with `rows` rows, stepped by `model`'s declared rule under `cfg`
    in blocks that fit `free_bytes` of device memory (a call that brings
    another count, a device's share under `shard_map`, is walked in a
    common divisor of the two)."""

    def __init__(self, model: Model, cfg, rows: int, samples: int,
                 free_bytes: int):
        mode, rate = step_rule(model, cfg)
        self.batch_size = cfg.batch_size
        self._block_step = block_step_fn(model, mode, clip=cfg.grad_clip,
                                         alpha=rate)
        # peers whose steps are computed together (`samples`: all at once)
        self.block = peer_block(
            samples,
            model.step_bytes
            and model.step_bytes(min(cfg.batch_size, rows)),
            free_bytes)

    def minibatches(self, bkeys: jax.Array, at: jax.Array, x: jax.Array,
                    y: jax.Array):
        """The minibatches [S, B, ...] of S peers whose shards are the rows
        `at` of the stack (x, y): every peer's row numbers from its own key
        (`bkeys` [S], the caller's stream), composed with `at` into S x B
        row numbers of the stack seen as [N * rows, ...], and taken in ONE
        gather. The program reads nothing else of the stack: there is no
        `x[at]` of S whole shards in between, which is also what let the
        compiler hoist the matmul's bfloat16 cast over every row of every
        peer (PERF.md, PR 25). The merged view is free in `stack_layout`'s
        layout (a bitcast: `rows` is a multiple of the 8-row tile in every
        dataset there is); where it were not, parallel/sim.py's
        `whole_stack_instructions` names the copy."""
        n, rows = x.shape[:2]
        with jax.named_scope("round_sample"):
            idx = jax.vmap(lambda k: sample_batch(
                k, rows, self.batch_size))(bkeys)  # [S, B]
            flat = at[:, None] * rows + idx
        with jax.named_scope("round_gather"):
            return (x.reshape(n * rows, *x.shape[2:])[flat],
                    y.reshape(n * rows, *y.shape[2:])[flat])

    def deltas(self, w: jax.Array, xb: jax.Array, yb: jax.Array, frozen):
        """The [S, d] deltas of the minibatches [S, B, ...] and what the
        model's dispatch counted: the peer axis in blocks of `self.block`
        (one block where that is all of them: every classifier), each
        block the same program, one after the other."""
        s, block = xb.shape[0], min(self.block, xb.shape[0])
        if s % block:  # not the `samples` this was built for
            block = math.gcd(s, block)
        if block == s:
            return self._block_step(w, xb, yb, frozen)
        blocks = [a.reshape(s // block, block, *a.shape[1:])
                  for a in (xb, yb)]
        deltas, counts = jax.lax.map(
            lambda b: self._block_step(w, b[0], b[1], frozen), blocks)
        return (deltas.reshape(s, -1),
                jax.tree.map(lambda c: jnp.sum(c, axis=0), counts))
