"""In-process N-peer round simulator — peers mapped to the device.

This is the TPU-idiomatic replacement for the reference's process-per-peer
deployment when you want *round math* rather than *protocol transport*: the
reference can only simulate N peers by booting N OS processes exchanging RPC
(ref: DistSys/localTest.sh) or by a Python for-loop (ref:
ML/Pytorch/ml_main_mnist.py:24-60). Here one jitted XLA program executes the
whole round for all peers at once:

    deltas   = vmap(local_step)     — S contributors' SGD steps, batched matmuls
               (models/peer_step.py, which the live runtime's stepper runs
               too: one composed gather of the minibatch rows, the peer
               axis in blocks where one step's activations are large; a
               model's frozen base is an ARGUMENT of the program, held
               once for all peers)
    noise    = vmap(threefry draw)  — DP noising committee equivalent
    mask     = Krum | RONI kernel   — verifier committee equivalent
    w'       = w + Σ maskᵢ·deltaᵢ   — miner aggregation (sum, ref honest.go:360-375)
    stake'   = ±STAKE_UNIT scatter  — ledger bookkeeping (ref honest.go:414-419)

Peers-as-devices: `make_sharded_round_step` shards the peer axis over a
`jax.sharding.Mesh` with `shard_map`; the only cross-peer communication is an
`all_gather` of the [S,d] noised deltas for Krum and a `psum` of the masked
aggregate — both ride ICI, replacing the reference's TCP fan-out.

Committee *identity* (who is verifier/miner this round) does not change the
round's math, only who executes it; the distributed runtime (runtime/peer.py)
models identities. The simulator reproduces the math at full fidelity,
including contributor sampling and stake evolution.
"""

from __future__ import annotations

import math
import re
import time
from dataclasses import dataclass, field
from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from biscotti_tpu.config import BiscottiConfig, Defense
from biscotti_tpu.data import datasets as ds
from biscotti_tpu.models.base import Model
from biscotti_tpu.models.peer_step import (PeerSteps, _poisoned_ids,
                                           device_bytes, load_shards,
                                           outside_compile_cache, put_stack,
                                           stack_info)
from biscotti_tpu.models.trainer import step_rule
from biscotti_tpu.models.zoo import model_for_dataset
from biscotti_tpu.ops import dp_noise
from biscotti_tpu.ops.krum import default_num_adversaries, krum_accept_mask
from biscotti_tpu.ops.roni import roni_accept_mask
from biscotti_tpu.utils.profiling import PhaseClock

# The round's stages, as `jax.named_scope`s in the round program: THE
# vocabulary a device trace is read by (docs/OBSERVABILITY.md, "Device
# trace"). A scope is compile-time metadata: it lands in the `op_name` of
# every instruction traced inside it and changes no instruction. The
# one-chip step and `sharded_round_step_fn` open them in the helpers they
# share, so both programs carry the same names. The three `krum_*` scopes
# are opened in ops/krum.py and ops/krum_pallas.py. Each name is matched as
# a whole token, and none is a JAX primitive's or function's name.
STAGES = (
    "round_sample",     # contributor choice, key folding, minibatch indices
    "round_gather",     # ONE gather of the [S, B] minibatch rows from the stack
    "round_grad",       # loss gradient and clip, vmapped over the peers
    "round_noise",      # noise keys, the normal draw, deltas + noise
    "krum_prepare",     # cast, pad, squared norms
    "krum_scores",      # Pallas kernel, or Gram matmul + distances + top_k
    "krum_select",      # top_k of the scores, the accept-mask scatter
    "round_aggregate",  # masked sum of the accepted deltas, w + agg
    "round_ledger",     # stake scatter, the fault plane's drop mask
    "round_eval",       # test error of the next weights
)


def _array_dims(result_type: str):
    """The dimensions of every array in an HLO result type (a tuple type
    holds several): `bf16[3383,480,512]{2,1,0:T(8,128)(2,1)}` -> (3383,
    480, 512)."""
    return [tuple(int(v) for v in dims.split(",") if v)
            for dims in re.findall(r"\b[a-z]+\d*\[([\d,]*)\]", result_type)]


def whole_stack_instructions(hlo: str, peers: int, rows: int) -> List[str]:
    """`name = type opcode` of every instruction in the optimized HLO text
    `hlo` whose result spans a whole stack of `peers` x `rows`: an array
    with both extents among its dimensions (or merged into one) and more
    than one value a row, however many (the compiler may work in column
    blocks; the labels, one value a row and a thousandth of the stack, it
    may stage in fast memory for the gather). Parameters do
    not count: they are the stack. Nor does a `bitcast` (another shape for
    the same buffer: nothing moves), nor what sits INSIDE a fusion (it is
    never materialized; the fusion's own result is what reaches memory).
    For the sharded step `peers` is one device's share."""
    fused = set(re.findall(r"\bfusion\(.*\bcalls=%?([\w.\-]+)", hlo))
    found, inside = [], None
    for line in hlo.splitlines():
        header = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$", line)
        if header:
            inside = header.group(1)
            continue
        m = re.match(r"\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s"
                     r"([a-z][a-z\-]*)\(", line)
        if m is None or inside in fused \
                or m.group(3) in ("parameter", "bitcast"):
            continue
        for dims in _array_dims(m.group(2)):
            if (peers * rows in dims or (peers in dims and rows in dims)) \
                    and math.prod(dims) > peers * rows:
                found.append(f"{m.group(1)} = {m.group(2)} {m.group(3)}")
                break
    return found


@dataclass
class RoundLog:
    """One reference-log row: `iteration,error,timestamp`
    (ref: eval parser usenix-eval/generateResults.py:23-52)."""

    iteration: int
    error: float
    timestamp: float
    accepted: int = 0

    def csv(self) -> str:
        return f"{self.iteration},{self.error:.6f},{self.timestamp:.6f}"


def defense_mask(defense: Defense, model: Model, w: jax.Array,
                 noised: jax.Array, x_val: jax.Array, y_val: jax.Array,
                 roni_threshold: float, num_adversaries: int,
                 frozen=None) -> jax.Array:
    """Verifier-committee accept mask over the round's noised updates —
    shared by the single-chip (vmap) and sharded (shard_map) round steps so
    the two paths cannot drift. TRIMMED_MEAN has no per-update reject (it
    is an aggregation rule, not a mask — see masked_aggregate), so it
    accepts all like NONE."""
    n = noised.shape[0]
    if defense == Defense.KRUM:
        return krum_accept_mask(noised, num_adversaries)
    if defense == Defense.MULTIKRUM:
        from biscotti_tpu.ops.robust_agg import multikrum_accept_mask

        return multikrum_accept_mask(noised, num_adversaries)
    if defense == Defense.FOOLSGOLD:
        from biscotti_tpu.ops.robust_agg import foolsgold_accept_mask

        return foolsgold_accept_mask(noised)
    if defense == Defense.RONI:
        return roni_accept_mask(model, w, noised, x_val, y_val,
                                roni_threshold, frozen)
    return jnp.ones((n,), jnp.bool_)


def masked_aggregate(mask: jax.Array, deltas: jax.Array, noised: jax.Array,
                     dp_in_model: bool, defense: Defense = Defense.KRUM,
                     trim_fraction: float = 0.35) -> jax.Array:
    """Miner aggregation: sum of accepted RAW deltas (the noised copies exist
    only for verification, ref: SURVEY §2.3 row 21) — except in dp_in_model
    mode where the noise IS part of the update (ref: honest.go:172-179).
    Under TRIMMED_MEAN the sum is replaced by the coordinate-wise trimmed
    aggregate (ops/robust_agg.py); the mask is all-ones there."""
    agg_src = noised if dp_in_model else deltas
    if defense == Defense.TRIMMED_MEAN:
        from biscotti_tpu.ops.robust_agg import trimmed_mean_aggregate

        return trimmed_mean_aggregate(agg_src, trim_fraction)
    return jnp.sum(jnp.where(mask[:, None], agg_src, 0.0), axis=0)


class Simulator:
    """N peers on one chip (vmapped) or across a mesh (shard_map)."""

    def __init__(self, cfg: BiscottiConfig, model: Optional[Model] = None,
                 metrics=None):
        self.cfg = cfg
        # optional telemetry registry (telemetry.MetricsRegistry): run()
        # then feeds a per-round duration histogram and height/error
        # gauges — the simulator's rounds land on the same scrapeable
        # plane as the live runtime's (the CLI's --metrics-out wires this)
        self.metrics = metrics
        self.model = model or model_for_dataset(
            cfg.dataset, getattr(cfg, "model_name", ""))
        self.mode, rate = step_rule(self.model, cfg)
        self.num_params = self.model.num_params
        n = cfg.num_nodes

        # set-up and per-round host phases, also `biscotti:<name>` spans in
        # a profiler trace (sim.shards / sim.stack / sim.to_device /
        # sim.build; sim.round.args / sim.round.dispatch / sim.round.stage)
        self.phases = PhaseClock()

        with self.phases.phase("sim.shards"):
            xs, ys = load_shards(cfg, range(n))
            test = ds.load_shard(cfg.dataset, f"{cfg.dataset}_test")
            attack = ds.load_shard(cfg.dataset, f"{cfg.dataset}_digit1")
        rows = min(len(x) for x in xs)
        with self.phases.phase("sim.stack"):
            x_host = np.stack([x[:rows] for x in xs])  # [N, rows, d]
            y_host = np.stack([y[:rows] for y in ys])  # [N, rows]
        self.rows = rows

        with self.phases.phase("sim.build"):
            self.root_key = jax.random.PRNGKey(cfg.seed)
            self._use_noise = cfg.noising or cfg.dp_in_model
            self._noise_eps = cfg.epsilon if self._use_noise else 0.0
            self._noise_scale = dp_noise.sigma_for(self._noise_eps, cfg.delta)
            self._dp_mechanism = cfg.dp_mechanism
            self._noise_alpha = rate  # the step's rate scales its noise
            # the frozen base, drawn on the device leaf by leaf from the
            # seed and held ONCE for all peers; `{}` for every classifier
            with self.phases.phase("sim.frozen"):
                self.frozen = self.model.frozen(self.root_key)
            standing = (self.frozen_bytes() + x_host.nbytes + y_host.nbytes
                        + 4 * (3 * cfg.num_samples + 2) * self.num_params)
            # the sampled peers' minibatches and deltas: models/peer_step.py
            self.steps = PeerSteps(self.model, cfg, rows, cfg.num_samples,
                                   device_bytes() - standing)
            self.last_counts = {}  # what the last round's dispatch counted
            self._round_hlo_text = None
            self._round_step_raw, noised_raw = self._build_round_step()
            self._round_step_jit = jax.jit(self._round_step_raw,
                                           donate_argnums=(0, 1))
            self._noised_jit = jax.jit(noised_raw)

        # the hand-over only: the copy itself runs on the runtime's threads
        # after jnp.asarray returns, beside whatever the host does next
        # (tracing and fetching the first round). Waiting for it here was
        # tried (PERF.md, PR 24): 22 s at 3,383 peers, and 5-6 s more of
        # set-up than not waiting. The stack goes up LAST and in the layout
        # the round reads (stack_layout): where that takes a relayout
        # program, the device runs it when the copy has landed, and every
        # program queued after it waits as long. The keys above are such
        # programs, and the first round's lowering fetches them
        with self.phases.phase("sim.to_device"):
            self.x_val = jnp.asarray(test["x_test"])
            self.y_val = jnp.asarray(test["y_test"])
            self.x_attack = jnp.asarray(attack["x_test"])
            self.y_attack = jnp.asarray(attack["y_test"])
            self.x = put_stack(x_host)
            self.y = put_stack(y_host)
            # the seed stands on the device from here on: one array for
            # every round and for run_scan (a Simulator is built for one
            # seed; the seed stays an ARGUMENT of the program)
            self.seed = self._place(np.int32(cfg.seed))

        # what the round's host side knows from the last call, so that it
        # builds, copies and checks nothing between a sync's return and the
        # next dispatch: the arrays it returned (they are where the stack
        # is: `_at_home`) and the round counter it staged behind them
        self._returned = (None, None)
        self._staged_it, self._staged = None, None
        self._host = {"placed": 0, "rounds": 0, "staged": 0}

        def round_step(w, stake, it):
            with self.phases.phase("sim.round.args"):
                it = int(it)
                if w is not self._returned[0] \
                        or stake is not self._returned[1]:
                    w, stake = self._at_home(w, stake)  # from outside
                staged = it == self._staged_it
                # ONE form of `it` for every call, a typed scalar where the
                # seed is: a Python int on one call and an array on the
                # next are two programs. Built here only for a first call,
                # a replayed round or a jump
                at = self._staged if staged else self._place(np.int32(it))
            with self.phases.phase("sim.round.dispatch"):
                *out, self.last_counts = self._round_step_jit(
                    w, stake, at, self.seed, self.x, self.y, self.x_val,
                    self.y_val, self.frozen)
            with self.phases.phase("sim.round.stage"):
                # the next round's counter, handed over behind the running
                # round: a host copy (no program), while the device works
                self._returned = (out[0], out[1])
                self._staged_it = it + 1
                self._staged = self._place(np.int32(it + 1))
                self._host["rounds"] += 1
                self._host["staged"] += staged
            return tuple(out)

        self.round_step = round_step

    # ------------------------------------------------------------------ build

    def _contributors(self, key: jax.Array) -> jax.Array:
        """Per-round contributor subset of static size NUM_SAMPLES. The
        reference's verifier acts on the first KRUM_UPDATETHRESH arrivals
        (ref: krum.go:296); arrival order is scheduling noise, which a random
        subset models."""
        n, s = self.cfg.num_nodes, self.cfg.num_samples
        if s >= n:
            return jnp.arange(n)
        return jax.random.choice(key, n, (s,), replace=False)

    def _peer_noise(self, key: jax.Array) -> jax.Array:
        """Fresh per-round draw, distribution-identical to the reference's
        presampled bank row (Σ_batch σ·N(0,1) scaled by −α/batch; ref:
        client_obj.py:59-67,97-98). Presampling a [N,iters,d] bank would cost
        GBs of HBM at CNN sizes for zero statistical difference."""
        b = self.cfg.batch_size
        if self._dp_mechanism == "mcmc13":
            # Song&Sarwate'13 mechanism: fresh exact draw from the
            # MCMC path's stationary density (dp_noise.knorm_draw; the
            # per-peer trainer runs the chain itself for emcee parity)
            draw = dp_noise.knorm_draw(key, self._noise_eps, 1,
                                       self.num_params)[0]
        else:
            draw = self._noise_scale * math.sqrt(b) * jax.random.normal(
                key, (self.num_params,), jnp.float32
            )
        return (-self._noise_alpha / b) * draw

    def _peer_updates(self, w: jax.Array, bkey: jax.Array, nkey: jax.Array,
                      ids: jax.Array, at: jax.Array, x: jax.Array,
                      y: jax.Array, frozen=None):
        """Raw and noised [S, d] deltas of the peers `ids`, and the model's
        counts — shared by the
        one-chip step and the sharded one, so the two draw the same
        streams under the same scopes. `at` says where in the stack (x, y)
        each of those peers' shards sits: the sampled ids themselves on one
        chip, `arange(n_loc)` on a device that holds only its own peers."""
        with jax.named_scope("round_sample"):  # this caller's key stream
            bkeys = jax.vmap(lambda i: jax.random.fold_in(bkey, i))(ids)
        xb, yb = self.steps.minibatches(bkeys, at, x, y)
        with jax.named_scope("round_grad"):
            deltas, counts = self.steps.deltas(w, xb, yb, frozen)  # [S, d]
        with jax.named_scope("round_noise"):
            if self._use_noise:
                nkeys = jax.vmap(lambda i: jax.random.fold_in(nkey, i))(ids)
                noise = jax.vmap(self._peer_noise)(nkeys)
            else:
                noise = jnp.zeros_like(deltas)
            return deltas, deltas + noise, counts

    def _build_round_step(self):
        cfg = self.cfg
        model = self.model
        defense = cfg.defense if cfg.verification else Defense.NONE
        # cheap mirror of the live fault plane (cfg.fault_plan, runtime/
        # faults.py): with drop probability p, each contributor's round
        # frame is lost with p — deterministically in (fault seed, it, i),
        # so same seed ⇒ same degraded rounds here AND in the live runtime
        # sense (fewer contributors, no stake movement for the lost ones).
        # Semantics match the live system's dominant drop outcome: the
        # worker computed and verifiers scored the update (defense_mask
        # still sees it), but the miner-bound frame died, so it joins no
        # aggregate and earns no stake. Per-link structure is not modeled
        # — this is the ROUND-level agreement knob, not a transport sim.
        drop_p = cfg.fault_plan.drop if cfg.fault_plan.enabled else 0.0
        if drop_p > 0.0 and defense == Defense.TRIMMED_MEAN:
            raise ValueError(
                "fault_plan.drop is not supported with defense=TRIMMED_MEAN "
                "in the simulator: the trimmed aggregate has no per-update "
                "mask to carry the drops (run the live runtime for that)")
        fault_base = jax.random.PRNGKey(cfg.fault_plan.seed)

        # data tensors are ARGUMENTS, not closure captures: a captured jnp
        # array is baked into the HLO as a constant, which at CNN sizes
        # makes the program itself hundreds of MB (the [N, rows, d] peer
        # stack) and slow to compile. As arguments they stay
        # device-resident buffers. The SEED
        # is an argument for the same reason: a baked-in PRNGKey constant
        # would force a fresh trace+compile per seed, making multi-seed
        # sweeps (eval_poison --seeds) pay the compile N times.
        seed_base = jax.random.PRNGKey(0)  # same constant for every sim

        def updates(w, it, seed, x, y, frozen):
            """Round `it`'s contributor ids with their raw and noised
            deltas — everything the round does before the defence — and
            what the model's dispatch counted."""
            with jax.named_scope("round_sample"):
                rkey = jax.random.fold_in(
                    jax.random.fold_in(seed_base, seed), it)
                ckey, bkey, nkey = jax.random.split(rkey, 3)
                cidx = self._contributors(ckey)
            deltas, noised, counts = self._peer_updates(
                w, bkey, nkey, cidx, cidx, x, y, frozen)
            return cidx, deltas, noised, counts

        def noised_updates(w, it, seed, x, y, frozen=None):
            return updates(w, it, seed, x, y, frozen)[:3]

        def round_step(w, stake, it, seed, x, y, x_val, y_val, frozen=None):
            cidx, deltas, noised, counts = updates(w, it, seed, x, y, frozen)
            s = cidx.shape[0]
            mask = defense_mask(defense, model, w, noised, x_val,
                                y_val, cfg.roni_threshold,
                                default_num_adversaries(s), frozen)
            with jax.named_scope("round_ledger"):
                delta_stake = jnp.where(mask, cfg.stake_unit,
                                        -cfg.stake_unit)
                if drop_p > 0.0:
                    dkey = jax.random.fold_in(fault_base, it)
                    keep = jax.random.uniform(dkey, (s,)) >= drop_p
                    mask = mask & keep  # lost frames join no aggregate …
                    delta_stake = jnp.where(keep, delta_stake, 0)  # … or ledger
            with jax.named_scope("round_aggregate"):
                w_next = w + masked_aggregate(mask, deltas, noised,
                                              cfg.dp_in_model, defense,
                                              cfg.trim_fraction)
            with jax.named_scope("round_ledger"):
                stake_next = stake.at[cidx].add(delta_stake)
            with jax.named_scope("round_eval"):
                err = model.error_flat(w_next, x_val, y_val, frozen)
            return w_next, stake_next, mask, err, counts

        return round_step, noised_updates

    def round_hlo(self) -> str:
        """The optimized HLO text of the round program at this simulator's
        own shapes, every instruction carrying its `op_name` with the
        STAGES scopes: what a device trace's instruction names (`fusion.3`)
        are joined against (docs/OBSERVABILITY.md, "Device trace").

        Lowered from shapes (no buffer is touched, nothing is donated), the
        data arguments with their own formats (`jit` compiles for the layout
        an argument has, so a lowering from bare shapes would be another
        program, with other instruction names), with
        `it` as the int32 scalar the `round_step` closure hands over, and
        compiled OUTSIDE the persistent compile cache: its key ignores scope
        metadata (`jax_compilation_cache_include_metadata_in_key` is off),
        so a cache filled by an older tree would hand back that tree's
        executable, and its text that tree's names. For the same reason it
        is traced anew, through a wrapper of its own: JAX keeps the
        executable it fetched on the memoized lowering of
        `_round_step_jit`, and would hand that back uncompiled. A compile
        costs seconds: call it after a timed window, never in one. The
        text is kept: a second reader of the same run does not compile
        again."""
        if self._round_hlo_text is not None:
            return self._round_hlo_text

        def round_step(*args):  # the name the program and its scopes carry
            return self._round_step_raw(*args)

        lowered = jax.jit(round_step, donate_argnums=(0, 1)).lower(
            *self.round_arg_shapes())
        with outside_compile_cache():
            self._round_hlo_text = lowered.compile().as_text()
        return self._round_hlo_text

    def round_arg_shapes(self):
        """The arguments of the round program as `round_step` hands them
        over, as shapes: what `round_hlo()` lowers. `it` and the seed are
        int32 scalars, never weakly typed; the data arguments carry their
        own formats."""
        w = jax.ShapeDtypeStruct((self.num_params,), jnp.float32)
        stake = jax.ShapeDtypeStruct((self.cfg.num_nodes,), jnp.int32)
        it = seed = jax.ShapeDtypeStruct((), jnp.int32)
        data = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.format),
            (self.x, self.y, self.x_val, self.y_val, self.frozen))
        return (w, stake, it, seed, *data)

    # ------------------------------------------------- the gather's witness

    def frozen_bytes(self) -> int:
        """Bytes of the model's frozen tree (0: it has none)."""
        return sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves(self.frozen))

    @property
    def peer_block(self) -> int:
        """Sampled peers whose local steps the round computes together."""
        return self.steps.block

    def stack_info(self) -> dict:
        """Where the peer stack sits (`peer_step.stack_info`)."""
        return stack_info(self.x)

    def whole_stack_instructions(self, hlo: Optional[str] = None
                                 ) -> List[str]:
        """Which instructions of the round's program produce an array that
        spans the whole peer stack (`whole_stack_instructions` below, on
        `round_hlo()`). Expected: none. The round reads S x B rows; one
        name here is a pass over all N x rows of them, every round: a
        regression (PERF.md section 6, PR 25: 34.9 ms of 39.7)."""
        return whole_stack_instructions(
            self.round_hlo() if hlo is None else hlo,
            self.x.shape[0], self.rows)

    # ------------------------------------------------------------------ run

    def _at_home(self, *arrays):
        """`arrays` committed to the stack's device where the stack is. A
        layout of its own commits the stack, a program's results are
        committed when one of its arguments is, and `jit` compiles anew for
        every mix of committed and free arguments: fresh weights into a
        round whose stake came out of the last one would compile the round
        a second time, and the first round on its own results a third. An
        array that is already there is handed back as it is. For what
        enters from outside (fresh weights, `init_state`, a caller's own
        arrays): `round_step` does not ask again about the arrays it
        returned itself."""
        if not self.x.committed:
            return arrays  # nothing is committed: nothing to match
        home = tuple(a if getattr(a, "committed", False) else self._place(a)
                     for a in arrays)
        self._host["placed"] += sum(a is not b for a, b in zip(arrays, home))
        return home

    def _place(self, value):
        """The host `value` on the device, committed where the stack is: a
        copy, never a program (a program would queue behind the stack's
        relayout: `init_state`)."""
        if self.x.committed:
            return jax.device_put(value, self.x.sharding)
        return jax.device_put(value)

    def round_host_stats(self) -> dict:
        """What the round's host side did since construction:
        `args_placed_total`, arrays `_at_home` had to move (2 after
        `init_state` where the stack is committed, then flat through a
        closed loop); `round_counter_staged_share`, rounds that took the
        `it` staged behind the round before over rounds run (towards 1 in
        a closed loop, 0 for a caller that replays one round)."""
        host = self._host
        return {"args_placed_total": host["placed"],
                "round_counter_staged_share":
                    host["staged"] / max(host["rounds"], 1)}

    def init_state(self):
        # host values, handed over as copies: a program (`jnp.zeros`) would
        # queue behind the stack's relayout, and whoever reads the fresh
        # stake back before the first round would wait for the whole stack
        w = jnp.asarray(np.zeros((self.num_params,), np.float32))
        stake = jnp.asarray(np.full((self.cfg.num_nodes,),
                                    self.cfg.default_stake, np.int32))
        return self._at_home(w, stake)

    def run(self, num_rounds: Optional[int] = None, log_every: int = 1,
            stop_at_convergence: bool = True):
        """Python round loop over the jitted step; returns (w, stake, logs).
        Log rows mirror the reference's parsed node-0 output so eval tooling
        is directly comparable (BASELINE.md)."""
        # imported here and in `dispatch_stats`, not at the top: what
        # stands above `run` is held byte for byte, a Pallas call's cache
        # key holds the line numbers of what traces it (ROADMAP C10)
        from biscotti_tpu.ops import moe

        if num_rounds is None:
            num_rounds = self.cfg.max_iterations
        w, stake = self.init_state()
        logs: List[RoundLog] = []
        m = self.metrics
        if m is not None:
            info = self.stack_info()
            m.gauge("biscotti_sim_stack_bytes",
                    "peer stack on the device, bytes under its device "
                    "layout (minor-to-major; 2,1,0 is row-major)").set(
                info["device_bytes"], layout=info["layout"])
            m.gauge("biscotti_sim_frozen_bytes",
                    "the model's frozen tree on the device, held once for "
                    "all peers (0: the model has none)").set(
                self.frozen_bytes())
            m.gauge("biscotti_sim_peer_block",
                    "sampled peers whose local steps the round computes "
                    "together (num_samples: all of them at once)").set(
                self.peer_block)
            # what the model declares (models/lm.py); a value is a number,
            # or a function of the run's start that is called once, here
            for name, text, value, labels in self.model.info.get("gauges",
                                                                 ()):
                if callable(value):
                    value = float(value(self.model.unravel(w), self.x_val,
                                        self.frozen))
                m.gauge(name, text).set(value, **labels)
        for it in range(num_rounds):
            t0 = time.perf_counter()
            w, stake, mask, err = self.round_step(w, stake, it)
            if m is not None:
                jax.block_until_ready(w)  # charge the round its device time
                m.histogram("biscotti_sim_round_seconds",
                            "simulator device-round wall clock").observe(
                    time.perf_counter() - t0)
                m.gauge("biscotti_sim_round_height",
                        "simulator rounds completed").set(it + 1)
                host = self.round_host_stats()
                m.gauge("biscotti_sim_args_placed_total",
                        "arrays the round's host side had to move to the "
                        "stack's device since construction (2 after "
                        "init_state, then flat through a closed loop)").set(
                    host["args_placed_total"])
                m.gauge("biscotti_sim_round_counter_staged_share",
                        "rounds that took the round counter staged on the "
                        "device behind the round before, over rounds run "
                        "(towards 1 in a closed loop)").set(
                    host["round_counter_staged_share"])
                for key, value in self.dispatch_stats().items():
                    m.gauge(*moe.GAUGES[key]).set(value)
            if it % log_every == 0 or it == num_rounds - 1:
                e = float(err)
                logs.append(RoundLog(it, e, time.time(), int(mask.sum())))
                if m is not None:
                    m.gauge("biscotti_sim_error",
                            "simulator latest test error").set(e)
                if stop_at_convergence and e < self.cfg.convergence_error:
                    break
        return w, stake, logs

    def run_scan(self, num_rounds: Optional[int] = None,
                 seed: Optional[int] = None):
        """Whole training as ONE compiled XLA program (`lax.scan` over
        rounds) — no host in the loop at all. Upper bound of the TPU design;
        nothing in the reference's architecture can express this. `seed`
        overrides cfg.seed without rebuilding the Simulator (it is a traced
        argument, so multi-seed sweeps reuse one compiled executable)."""
        if num_rounds is None:
            num_rounds = self.cfg.max_iterations
        w, stake = self.init_state()
        step = self._round_step_raw

        # cache the jitted scan per run length: a fresh @jax.jit wrapper
        # each call would empty the in-memory jit cache and re-trace the
        # whole N-round program per seed, defeating the seed-as-argument
        # design
        full = getattr(self, "_scan_cache", {}).get(num_rounds)
        if full is None:

            @jax.jit
            def full(w, stake, seed, x, y, x_val, y_val, frozen):
                def body(carry, it):
                    w, stake = carry
                    w, stake, mask, err, _ = step(w, stake, it, seed, x, y,
                                                  x_val, y_val, frozen)
                    return (w, stake), (err, jnp.sum(mask))

                return jax.lax.scan(body, (w, stake),
                                    jnp.arange(num_rounds))

            self._scan_cache = getattr(self, "_scan_cache", {})
            self._scan_cache[num_rounds] = full

        seed = self.seed if seed is None else self._place(np.int32(seed))
        (w, stake), (errs, accepted) = full(
            w, stake, seed, self.x, self.y, self.x_val, self.y_val,
            self.frozen)
        return w, stake, np.asarray(errs), np.asarray(accepted)

    # ------------------------------------------------------------------ metrics

    def noised_updates(self, w, it: int) -> jax.Array:
        """The [S, d] noised deltas round `it` hands the verifier
        committee at weights `w` — the defence's actual input, for
        checking a scoring kernel against an oracle on it."""
        return self._noised_jit(*self._at_home(w), it, self.seed,
                                self.x, self.y, self.frozen)[2]

    def dispatch_stats(self, counts=None) -> dict:
        """`ops/moe.dispatch_stats` of what a round's expert dispatch
        counted (`counts`: the last round's, which the round returns summed
        over its peer blocks), `{}` for a model that has none; reads them
        back: call it outside a timed round."""
        from biscotti_tpu.ops import moe

        return moe.dispatch_stats(
            self.last_counts if counts is None else counts,
            self.cfg.num_samples / self.peer_block)

    def test_error(self, w) -> float:
        return float(self.model.error_flat(jnp.asarray(w), self.x_val,
                                           self.y_val, self.frozen))

    def attack_rate(self, w) -> float:
        return float(self.model.error_flat(jnp.asarray(w), self.x_attack,
                                           self.y_attack, self.frozen))

    def attack_success_rate(self, w) -> float:
        """Stricter source→target metric: fraction of attack-source samples
        predicted as exactly the attack target class (the 1→7 rate;
        trainer.attack_success_rate analogue — not inflated by benign
        confusion the way attack_rate's 1−accuracy is)."""
        target = ds.spec(self.cfg.dataset).attack_target
        logits = self.model.apply_flat(jnp.asarray(w), self.x_attack,
                                       self.frozen)
        pred = jnp.argmax(logits, axis=-1)
        return float(jnp.mean((pred == target).astype(jnp.float32)))


# ---------------------------------------------------------------- sharded path


def make_sharded_round_step(sim: Simulator, mesh: jax.sharding.Mesh,
                            axis: str = "peers"):
    """Peers-across-devices round step via shard_map.

    Every peer contributes (S = N — contributor sampling is a single-chip
    refinement); the peer axis of (x, y) is sharded over `axis`, the model is
    replicated. Cross-device traffic is exactly one all_gather of the [N,d]
    noised deltas (Krum needs the full set) and one psum of the masked local
    aggregate — the ICI-collective replacement for the reference's
    TCP update fan-out (ref: SURVEY §5.8).

    Randomness derives from the same seed-as-argument scheme as the
    single-chip round_step — fold_in(fold_in(PRNGKey(0), seed), it) — so
    `run_step(w, it, seed=...)` overrides behave identically on both paths
    (previously this path read sim.root_key and seed overrides silently
    no-opped on sharded runs; ADVICE round 5). The fault plane's drop-mask
    knob (cfg.fault_plan.drop) is mirrored here too — see _build_round_step.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    step = sharded_round_step_fn(sim, mesh, axis)
    sharding = NamedSharding(mesh, P(axis))
    x_sh = put_stack(sim.x, sharding)  # stack_layout, on every device
    y_sh = put_stack(sim.y, sharding)

    def run_step(w, it, seed: Optional[int] = None):
        s = sim.cfg.seed if seed is None else seed
        return step(w, x_sh, y_sh, jnp.asarray(it),
                    jnp.asarray(s, jnp.int32), sim.frozen)

    run_step.x = x_sh  # the sharded peer stack: lets a caller check placement
    return run_step


def sharded_round_step_fn(sim: Simulator, mesh: jax.sharding.Mesh,
                          axis: str = "peers"):
    """The jitted program behind make_sharded_round_step:
    `(w, x, y, it, seed, frozen) -> (w', mask, err)` with (x, y) sharded
    over `axis` and the model's frozen tree on every device whole (experts
    over a second mesh axis: ROADMAP B2's remainder). No data is placed, so it can also be lowered ahead of time for
    a mesh of devices this host does not have
    (tests/test_tpu_lowering.py)."""
    from jax.sharding import PartitionSpec as P

    cfg = sim.cfg
    model = sim.model
    n = cfg.num_nodes
    defense = cfg.defense if cfg.verification else Defense.NONE
    f = default_num_adversaries(n)
    seed_base = jax.random.PRNGKey(0)  # same constant as _build_round_step
    drop_p = cfg.fault_plan.drop if cfg.fault_plan.enabled else 0.0
    fault_base = jax.random.PRNGKey(cfg.fault_plan.seed)

    def local_deltas(w, x_loc, y_loc, it, seed, frozen):
        with jax.named_scope("round_sample"):
            pid = jax.lax.axis_index(axis)
            local = jnp.arange(x_loc.shape[0])
            gids = pid * x_loc.shape[0] + local
            rkey = jax.random.fold_in(jax.random.fold_in(seed_base, seed),
                                      it)
            bkey, nkey = jax.random.split(rkey)
        return sim._peer_updates(w, bkey, nkey, gids, local, x_loc, y_loc,
                                 frozen)[:2]

    def sharded_step(w, x_loc, y_loc, it, seed, frozen):
        deltas, noised = local_deltas(w, x_loc, y_loc, it, seed, frozen)
        all_noised = jax.lax.all_gather(noised, axis, tiled=True)  # [N, d]
        mask = defense_mask(defense, model, w, all_noised, sim.x_val,
                            sim.y_val, cfg.roni_threshold, f, frozen)
        if drop_p > 0.0:
            # mirror of the live fault plane's frame drops: the accepted
            # update whose miner-bound frame is lost contributes nothing
            # (see _build_round_step for the exact shared semantics)
            with jax.named_scope("round_ledger"):
                dkey = jax.random.fold_in(fault_base, it)
                mask = mask & (jax.random.uniform(dkey, (n,)) >= drop_p)
        with jax.named_scope("round_aggregate"):
            pid = jax.lax.axis_index(axis)
            n_loc = deltas.shape[0]
            if defense == Defense.TRIMMED_MEAN:
                # order statistics need the FULL peer set: one more
                # all_gather (of the raw deltas) and the trimmed aggregate
                # is computed replicated — same collective budget class as
                # Krum's gather
                src = all_noised if cfg.dp_in_model else jax.lax.all_gather(
                    deltas, axis, tiled=True)
                agg = masked_aggregate(mask, src, src, cfg.dp_in_model,
                                       defense, cfg.trim_fraction)
            else:
                local_mask = jax.lax.dynamic_slice_in_dim(mask, pid * n_loc,
                                                          n_loc)
                local_agg = masked_aggregate(local_mask, deltas, noised,
                                             cfg.dp_in_model)
                agg = jax.lax.psum(local_agg, axis)
            w_next = w + agg
        with jax.named_scope("round_eval"):
            err = model.error_flat(w_next, sim.x_val, sim.y_val, frozen)
        return w_next, mask, err

    mapped = jax.shard_map(
        sharded_step, mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(), P(), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )

    def sharded_step(w, x, y, it, seed, frozen=None):  # noqa: F811
        return mapped(w, x, y, it, seed, {} if frozen is None else frozen)

    return jax.jit(sharded_step)


# ------------------------------------------------------------------- CLI


def main(argv=None) -> int:
    """Standalone federated simulation CLI — the reference's ml_main_* file
    family (ref: ML/Pytorch/ml_main_mnist.py:24-60, ml_main_diffpriv.py,
    _credit/_cifar/_lfw variants) as one parameterized entry point, with
    the whole round jitted instead of a Python peer loop."""
    import argparse
    import json as _json

    from biscotti_tpu.config import BiscottiConfig

    ap = argparse.ArgumentParser(description="in-process N-peer simulator")
    BiscottiConfig.add_args(ap)
    ap.add_argument("--rounds", type=int, default=0,
                    help="override max-iterations for the run")
    ap.add_argument("--scan", action="store_true",
                    help="compile the WHOLE training run as one XLA program")
    ap.add_argument("--csv", default="",
                    help="write iteration,error,timestamp rows here")
    ap.add_argument("--metrics-out", default="",
                    help="write a Prometheus text page of the run's "
                         "telemetry (round histogram, height/error gauges) "
                         "here; non-scan runs only")
    ns = ap.parse_args(argv)
    if ns.metrics_out and ns.scan:
        ap.error("--metrics-out requires a non-scan run (run_scan compiles "
                 "the whole training into one XLA program; there are no "
                 "per-round host observations to export)")
    from biscotti_tpu.utils import jaxenv

    jaxenv.configure_compile_cache()
    cfg = BiscottiConfig.from_args(ns)
    registry = None
    if ns.metrics_out:
        from biscotti_tpu.telemetry import MetricsRegistry

        registry = MetricsRegistry()
    sim = Simulator(cfg, metrics=registry)
    rounds = ns.rounds or cfg.max_iterations
    if ns.scan:
        w, stake, errs, accepted = sim.run_scan(rounds)
        logs = [RoundLog(i, float(e), time.time(), int(a))
                for i, (e, a) in enumerate(zip(errs, accepted))]
    else:
        w, stake, logs = sim.run(rounds)
    if ns.csv:
        with open(ns.csv, "w") as f:
            f.write("\n".join(l.csv() for l in logs) + "\n")
    if registry is not None:
        with open(ns.metrics_out, "w") as f:
            f.write(registry.render())
    summary = {
        "dataset": cfg.dataset, "nodes": cfg.num_nodes,
        "rounds_run": len(logs),
        "final_error": logs[-1].error if logs else float("nan"),
        "test_error": sim.test_error(w),
        "attack_rate": sim.attack_rate(w),
        **jaxenv.device_info(),
    }
    print(_json.dumps(summary))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
