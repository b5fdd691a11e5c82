"""Driver `device_round_gdn`: whole rounds on the device alone, of
Qwen3-Next-80B-A3B-Instruct's share (gated delta-net and gated attention
layers 3 : 1, a sparse MLP of 512 experts on every layer, a frozen base
held once beside the adapters it trains).

The program under test is `biscotti_tpu.parallel.sim.Simulator`, as in
`device_round_lm`, whose set-up, window and numbers this driver keeps: one
`round_step` a round, each ended by `block_until_ready`, closed loop; the
checked round from seeded NON-zero adapters; `w_next_leaf_gap`,
`logit_gap`, `router_flips_beyond_ties` (a plain top-k: ONE cut, so
`device_round_lm.router_flips` as it is), `accept_beyond_ties`,
`stake_mismatch`, `rounds_failed`, `compiles_in_window`, `err_gap`, every
round failed where a token was dropped. What differs is the model's
family: the published keys the reference reads (`reference_spec`), the
sizes the program must have built (`check_sizes`), the reference itself
(`benchmark/reference/qwen3_next.py`: the delta rule a token at a time),
the program's own routing (`models/qwen3_next.routing`) and the controls.
As in `device_round_ssm`, a control that departs in the forward is judged
at its logits first and steps no gradient where they alone fail.
`device_round_lm`'s helpers that name no family are imported as they are.
"""

import contextlib
import time

import numpy as np

from benchmark.drivers.device_round import load_shards
from benchmark.drivers.device_round_lm import (LIMITS, _precision,  # noqa: F401
                                               limits_of, router_flips)

CONTROLS = {  # name -> the reference's variant put in the program's place
    "bfloat16": {"store": "bfloat16"},    # adapters, deltas and sums
    "no_delta": {"delta": False},         # d = beta v: gated linear attention
    "beta_one": {"beta": 1.0},
    "decay_bfloat16": {"decay": "bfloat16"},  # g and its running sums
    "no_carry": {"carry": False},         # the state not carried over chunks
    "no_l2norm": {"l2norm": False},
    "gate_before_norm": {"gate_first": True},
    "norm_not_zero_centred": {"zero_centred": False},
    "no_output_gate": {"output_gate": False},
    "no_shared_gate": {"shared_gate": False},
    "rotary_full": {"rotary": "full"},    # all 256 turned
    "no_renormalise": {"renormalise": False},
}

PUBLISHED = ("hidden_size", "num_hidden_layers", "full_attention_interval",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "partial_rotary_factor", "rope_theta", "linear_num_key_heads",
             "linear_num_value_heads", "linear_key_head_dim",
             "linear_value_head_dim", "linear_conv_kernel_dim",
             "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps")


def reference_spec(config):
    """What `reference/qwen3_next.py` needs of the configuration's file:
    the published keys at the layers held, and the adapters."""
    spec = {key: config[key] for key in PUBLISHED}
    spec["first_expert"] = config["model"]["held_first_expert"]
    spec["lora_rank"] = config["adapters"]["rank"]
    spec["lora_alpha"] = config["adapters"]["alpha"]
    return spec


def variant_of(control, config):
    """The reference's variant of a control; those that depart at a
    chunk's boundary learn the chunk the configuration assumes."""
    variant = dict(CONTROLS[control])
    if "carry" in variant or "decay" in variant:
        variant["chunk"] = config["model"]["rule_chunk"]
    return variant


def check_sizes(sim, config):
    """The program's model is the configuration's: d, the frozen count and
    every width the file states."""
    import jax

    from benchmark.reference import qwen3_next as ref

    want = config["model"]
    frozen = sum(a.size for a in jax.tree.leaves(sim.frozen))
    d_ref = ref.num_params(reference_spec(config))
    if not (sim.num_params == d_ref == want["num_params"]):
        raise RuntimeError(f"d = {sim.num_params} (the reference's layout "
                           f"{d_ref}), the configuration states "
                           f"{want['num_params']}")
    if frozen != want["frozen_params"]:
        raise RuntimeError(f"{frozen} frozen parameters, the configuration "
                           f"states {want['frozen_params']}")
    layers = sim.frozen["layers"]
    interval = config["full_attention_interval"]
    delta, attention = layers[0], layers[interval - 1]
    hidden, dh = config["hidden_size"], config["head_dim"]
    keys = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    values = (config["linear_num_value_heads"]
              * config["linear_value_head_dim"])
    found = {"layers": ["gdn" if "w_qkvz" in layer else "attention"
                        for layer in layers],
             "untied": "head" in sim.frozen,
             "vocabulary rows": sim.frozen["embed"].shape[0],
             "held experts": tuple(delta["experts"]["w_gate"].shape),
             "shared expert": tuple(delta["shared"]["w_gate"].shape),
             "router outputs": delta["router"].shape[1],
             "in_proj_qkvz": tuple(delta["w_qkvz"].shape),
             "in_proj_ba": tuple(delta["w_ba"].shape),
             "conv": tuple(delta["conv_w"].shape),
             "out_proj": tuple(delta["w_out"].shape),
             "q": tuple(attention["wq"].shape),
             "k": tuple(attention["wk"].shape),
             "head norms": tuple(attention["q_norm"].shape),
             "chunk": sim.model.info["config"].chunk,
             "window": int(sim.x.shape[-1])}
    stated = {"layers": [("attention" if (at + 1) % interval == 0 else "gdn")
                         for at in range(config["num_hidden_layers"])],
              "untied": not config["tie_word_embeddings"],
              "vocabulary rows": config["vocab_size"],
              "held experts": (config["num_experts"], hidden,
                               config["moe_intermediate_size"]),
              "shared expert": (hidden,
                                config["shared_expert_intermediate_size"]),
              "router outputs": config["published"]["num_experts"],
              "in_proj_qkvz": (hidden, 2 * keys + 2 * values),
              "in_proj_ba": (hidden,
                             2 * config["linear_num_value_heads"]),
              "conv": (config["linear_conv_kernel_dim"], 2 * keys + values),
              "out_proj": (values, hidden),
              "q": (hidden, 2 * config["num_attention_heads"] * dh),
              "k": (hidden, config["num_key_value_heads"] * dh),
              "head norms": (dh,),
              "chunk": want["rule_chunk"],
              "window": want["window_tokens"]}
    if found != stated:
        raise RuntimeError(f"the program holds {found}, the configuration "
                           f"states {stated}")


def run(cell, fields, seconds, trace_dir, meter, t0):
    import jax

    from biscotti_tpu.config import BiscottiConfig, Defense
    from biscotti_tpu.parallel.sim import Simulator

    from benchmark import trace as trace_reduction

    mix = cell["mix"]
    seed = fields["seed"]
    cfg = BiscottiConfig(**dict(fields, defense=Defense[fields["defense"]]))
    load_shards(cfg)
    sim = Simulator(cfg)
    check_sizes(sim, cell["config"])

    _, stake = sim.init_state()
    seen = []  # what the checked rounds were given and returned
    it = 0
    for it in range(int(mix["warm_rounds"])):
        if it < max(1, int(mix["checked_rounds"])):
            w = sim.model.flat_init(jax.random.PRNGKey(seed + it))
        w_in, stake_in = np.asarray(w), np.asarray(stake)  # donated below
        w, stake, mask, err = sim.round_step(w, stake, it)
        jax.block_until_ready(w)
        if it < int(mix["checked_rounds"]):
            seen.append({"it": it, "w_in": w_in, "stake_in": stake_in,
                         "w_next": np.asarray(w),
                         "stake_next": np.asarray(stake),
                         "mask": np.asarray(mask), "err": float(err)})
    it += 1

    tracing = trace_dir is not None
    if tracing:
        seconds = min(seconds, float(mix["trace_seconds"]))
        trace_reduction.start(trace_dir)
        span = jax.profiler.TraceAnnotation
    else:
        span = lambda name: contextlib.nullcontext()  # noqa: E731

    setup_s = time.time() - t0
    durs, masks, counted = [], [], []
    epoch0 = time.time()
    start = time.perf_counter()
    end = start + seconds
    t1 = start
    while True:
        with span("bench:round_step dispatch"):
            w, stake, mask, err = sim.round_step(w, stake, it)
        with span("bench:block_until_ready"):
            jax.block_until_ready(w)
        t2 = time.perf_counter()
        durs.append(t2 - t1)
        masks.append(mask)
        counted.append(getattr(sim, "last_counts", {}))
        it += 1
        if t2 >= end:
            break
        t1 = t2
    elapsed = t2 - start
    epoch1 = time.time()
    if tracing:
        jax.profiler.stop_trace()

    s = cfg.num_samples
    want = s - s // 2 if cfg.verification else s
    accepted = np.asarray([int(np.asarray(m).sum()) for m in masks])
    finite = bool(np.all(np.isfinite(np.asarray(w))))
    failed = int(np.sum(accepted != want)) if finite else len(durs)
    # the program's own routing counts, a round (read back after the window)
    stats = [sim.dispatch_stats(c) for c in counted]
    moe = {name: [row[name] for row in stats]
           for name in (stats[0] if stats else {})}
    dropped = sum(moe.get("tokens_dropped", []))
    return {
        "cell": cell, "cfg": cfg, "sim": sim, "seed": seed, "seen": seen,
        "round_s": durs, "attempted": len(durs),
        "failed": failed if not dropped else len(durs),
        "compiles_in_window": meter.between(epoch0, epoch1),
        "moe": moe, "peer_block": int(sim.peer_block),
        "end_to_end": {
            "device_round_ms": 1e3 * elapsed / len(durs),
            "device_round_ms.p95": 1e3 * float(np.quantile(durs, 0.95)),
            "setup_s": setup_s,
        },
    }


def _round_inputs(record):
    from biscotti_tpu.data import datasets as ds

    cfg, config = record["cfg"], record["cell"]["config"]
    dataset = cfg.dataset
    rnd = {"n": cfg.num_nodes, "s": cfg.num_samples,
           "rows": len(ds.load_shard(dataset, f"{dataset}0")["x_train"]),
           "batch": cfg.batch_size, "clip": cfg.grad_clip,
           "eta": cfg.learning_rate, "epsilon": cfg.epsilon,
           "delta": cfg.delta, "noising": cfg.noising,
           "verification": cfg.verification, "stake_unit": cfg.stake_unit}

    def shard_rows(peer, idx):  # inputs only: the peers' own data
        shard = ds.load_shard(dataset, f"{dataset}{peer}")
        return shard["x_train"][idx], shard["y_train"][idx]

    test = ds.load_shard(dataset, f"{dataset}_test")
    return (reference_spec(config), rnd, shard_rows, test["x_test"],
            test["y_test"])


def program_view(sim, w, x_val):
    """What the PROGRAM makes of the held-out windows under adapters `w`:
    its logits [b, T, V] and its router's choices [L, b*T, k], through the
    model the round itself runs."""
    import jax
    import jax.numpy as jnp

    from biscotti_tpu.models import qwen3_next

    model, cfg = sim.model, sim.model.info["config"]

    @jax.jit
    def view(w, x, frozen):
        experts, _ = qwen3_next.routing(cfg, model.unravel(w), x, frozen)
        return model.apply_flat(w, x, frozen), experts

    logits, experts = view(jnp.asarray(w, jnp.float32), jnp.asarray(x_val),
                           sim.frozen)
    return np.asarray(logits, np.float64), np.asarray(experts)


def reference_view(spec, frozen, w, x_val, dtype, variant=None):
    """The reference's: logits, and per layer the chosen experts [N, k]
    and all the probabilities [N, E_all]."""
    import jax.numpy as jnp

    from benchmark.reference import qwen3_next as ref

    variant = {k: v for k, v in (variant or {}).items() if k != "store"}
    _, run = ref.compiled(spec, dtype, variant)
    logits, picks = run(frozen, jnp.asarray(w, dtype), jnp.asarray(x_val))
    return (np.asarray(logits, np.float64),
            [np.asarray(e) for e, _ in picks],
            [np.asarray(p, np.float64) for _, p in picks])


def leaf_gaps(spec, got, ref):
    """|got - ref| (L2) of every adapter leaf over the larger of that
    leaf's reference norm and the median leaf's."""
    from benchmark.reference import qwen3_next as rq

    ref_leaves = rq.leaves(spec, np.asarray(ref, np.float64))
    got_leaves = rq.leaves(spec, np.asarray(got, np.float64))
    norms = [float(np.linalg.norm(r)) for _, r in ref_leaves]
    floor = float(np.median(norms))
    return {name: float(np.linalg.norm(g - r)) / max(nr, floor, 1e-300)
            for (name, g), (_, r), nr in zip(got_leaves, ref_leaves, norms)}


def logit_gap(logits, truth):
    """|logits - truth| / |truth| (L2, every position); infinite where
    the logits are not finite (a control whose state overflows)."""
    gap = float(np.linalg.norm(logits - truth) / np.linalg.norm(truth))
    return gap if np.isfinite(gap) else float("inf")


def compare(spec, mix, got, ref, ref_view):
    """One round's returns against the reference's of the same round."""
    from benchmark.reference import krum as rkrum

    beyond = rkrum.beyond_ties(ref["scores"], ref["accept"], got["mask"],
                               float(mix["tie_rel"])) \
        if ref["scores"].any() else []
    update = (np.asarray(got["w_next"], np.float64)
              - np.asarray(got["w_in"], np.float64))
    gaps = leaf_gaps(spec, update, ref["agg"])
    logits, experts, probs = ref_view
    flips, worst_flip, tokens_differ = router_flips(
        got["experts"], experts, probs, float(mix["router_band"]))
    worst = sorted(gaps, key=gaps.get)[-3:]
    differ = np.nonzero(np.asarray(got["mask"], bool) != ref["accept"])[0]
    order = np.sort(ref["scores"])
    keep = int(ref["accept"].sum())
    cut = 0.5 * (order[keep - 1] + order[min(keep, len(order) - 1)])
    return {
        "_detail": {
            "worst_leaves": {name: gaps[name] for name in worst},
            "err": got["err"], "err_ref": ref["err"],
            "accept_differs": int(differ.size),
            # how far from the cut the program's disagreements sit
            "worst_tie_rel": max((abs(ref["scores"][i] - cut) / abs(cut)
                                  for i in differ), default=0.0)
            if cut else 0.0,
            "worst_flip_rel": worst_flip, "tokens_differ": tokens_differ,
            "update_norm": float(np.linalg.norm(ref["agg"])),
            "delta_norms": [float(np.linalg.norm(row))
                            for row in ref["deltas"][:4]]},
        "accept_beyond_ties": len(beyond),
        "stake_mismatch": int(np.sum(ref["stake_next"]
                                     != got["stake_next"])),
        "router_flips_beyond_ties": flips,
        "w_next_leaf_gap": max(gaps.values()),
        "err_gap": abs(ref["err"] - got["err"]),
        "logit_gap": logit_gap(got["logits"], logits),
    }


def check(record, control=None, limits=None):
    """Hold what the checked rounds of the timed object returned to the
    reference: [(name, value, limit, ok)]. `control` names one of
    CONTROLS: the reference with that departure then stands in the
    program's place (tests and limit-setting)."""
    import jax

    from benchmark.reference import qwen3_next as ref

    t0 = time.perf_counter()
    limits = dict(limits_of(record["cell"]), **(limits or {}))
    config, mix = record["cell"]["config"], record["cell"]["mix"]
    sim = record["sim"]
    spec, rnd, shard_rows, x_val, y_val = _round_inputs(record)
    dtype, precision = _precision(jax.devices()[0].platform)
    worst = {"rounds_failed": record["failed"],
             "compiles_in_window": record["compiles_in_window"]}
    for got in record["seen"]:  # the program's side, at its own precision
        if "logits" not in got:
            got["logits"], got["experts"] = program_view(sim, got["w_in"],
                                                         x_val)
    with precision:
        for got in record["seen"]:
            if "_ref" not in got:  # once a record, whatever stands in
                got["_ref"] = ref.reference_round(
                    spec, rnd, record["seed"], got["it"], got["w_in"],
                    got["stake_in"], sim.frozen, shard_rows, x_val, y_val,
                    dtype, accept_from=got["mask"])
                got["_ref_view"] = reference_view(
                    spec, sim.frozen, got["w_in"], x_val, dtype)
            truth, truth_view = got["_ref"], got["_ref_view"]
            if control:
                variant = variant_of(control, config)
                view = None
                if "store" not in variant:
                    # a departure of the forward: its logits first (two
                    # windows, seconds). Where they alone are over the
                    # limit the control is not correct already, and the
                    # 21 gradients of its round (minutes of token-by-token
                    # recurrence, a compile a variant) are not computed
                    view = reference_view(spec, sim.frozen, got["w_in"],
                                          x_val, dtype, variant)
                    gap = logit_gap(view[0], truth_view[0])
                    if limits["logit_gap"] is not None \
                            and gap > limits["logit_gap"]:
                        flips = router_flips(view[1], truth_view[1],
                                             truth_view[2],
                                             float(mix["router_band"]))[0]
                        for name, value in (
                                ("logit_gap", gap),
                                ("router_flips_beyond_ties", flips)):
                            worst[name] = max(worst.get(name, 0), value)
                        record.setdefault("detail", []).append(
                            {"control": control, "stopped_at": "logit_gap"})
                        continue
                low = ref.reference_round(
                    spec, rnd, record["seed"], got["it"], got["w_in"],
                    got["stake_in"], sim.frozen, shard_rows, x_val, y_val,
                    dtype, variant=variant)
                logits, experts, _ = view or reference_view(
                    spec, sim.frozen, ref.bf16(got["w_in"]), x_val, dtype,
                    variant)
                got = dict(got, w_next=low["w_next"], mask=low["accept"],
                           stake_next=low["stake_next"], err=low["err"],
                           logits=logits, experts=experts)
                # the oracle aggregates and pays with the set it is handed
                stake = np.array(got["stake_in"], np.int64)
                np.add.at(stake, truth["sampled"], np.where(
                    low["accept"], rnd["stake_unit"], -rnd["stake_unit"]))
                truth = dict(truth, stake_next=stake, agg=truth["deltas"][
                    low["accept"]].sum(axis=0))
            found = compare(spec, mix, got, truth, truth_view)
            record.setdefault("detail", []).append(found.pop("_detail"))
            for name, value in found.items():
                worst[name] = max(worst.get(name, 0), value)
    record["check_s"] = time.perf_counter() - t0
    return [(name, value, limits[name],
             limits[name] is not None and value <= limits[name])
            for name, value in worst.items()]
