"""Driver `device_round_gdn_dense`: whole rounds on the device alone, of
Olmo-Hybrid-7B's first pipeline stage (gated delta-net layers at heads of
96 | 192 with beta in (0, 2) and plain full-attention layers 3 : 1, a
dense SwiGLU on every layer, the norm on each sub-block's output, a frozen
base held once beside the adapters it trains).

The program under test is `biscotti_tpu.parallel.sim.Simulator`, as in
`device_round_ssm`, whose set-up, window and numbers this driver keeps: one
`round_step` a round, each ended by `block_until_ready`, closed loop; the
checked round from seeded NON-zero adapters; `w_next_leaf_gap`,
`logit_gap`, `accept_beyond_ties`, `stake_mismatch`, `rounds_failed`,
`compiles_in_window`, `err_gap`. What differs is the model's family: the
published keys the reference reads (`reference_spec`), the sizes the
program must have built (`check_sizes`), the reference itself
(`benchmark/reference/olmo_hybrid.py`: the delta rule a token at a time,
the scores whole) and the controls. There is no router: no tie rule, no
`router_flips_beyond_ties`, and `logit_gap` is over every position. A
control that departs in the forward is judged at its logits first and
steps no gradient where they alone fail. `device_round_ssm`'s and
`device_round_gdn`'s helpers that name no family are imported as they are;
the checked round's `detail` carries `peer_block`, as `device_round_swa`'s
does.
"""

import contextlib
import time

import numpy as np

from benchmark.drivers.device_round import load_shards
from benchmark.drivers.device_round_gdn import logit_gap
from benchmark.drivers.device_round_lm import _precision
from benchmark.drivers.device_round_ssm import (LIMITS, limits_of,  # noqa: F401
                                                program_logits)

CONTROLS = {  # name -> the reference's variant put in the program's place
    "bfloat16": {"store": "bfloat16"},    # adapters, deltas and sums
    "decay_bfloat16": {"decay": "bfloat16"},  # g and its running sums
    "beta_not_doubled": {"beta_scale": 1.0},  # beta = sigmoid(b), in (0, 1)
    "beta_one": {"beta": 1.0},
    "no_delta": {"delta": False},         # d = beta v: gated linear attention
    "no_carry": {"carry": False},         # the state not carried over chunks
    "no_l2norm": {"l2norm": False},
    "gate_before_norm": {"gate_first": True},
    "norm_before_mixer": {"norm_first": True},  # the pre-norm order
    "no_qk_norm": {"qk_norm": False},
    "rotary_on": {"rotary": 500000.0},    # the OLMo 2 family's theta
}

PUBLISHED = ("hidden_size", "num_hidden_layers", "layer_types",
             "num_attention_heads", "num_key_value_heads",
             "linear_num_key_heads", "linear_num_value_heads",
             "linear_key_head_dim", "linear_value_head_dim",
             "linear_conv_kernel_dim", "rms_norm_eps")


def reference_spec(config):
    """What `reference/olmo_hybrid.py` needs of the configuration's file:
    the published keys at the layers held, and the adapters."""
    spec = {key: config[key] for key in PUBLISHED}
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

    from benchmark.reference import olmo_hybrid as ref

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
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    layers = sim.frozen["layers"]
    linear = layers[kinds.index("linear_attention")]
    full = layers[kinds.index("full_attention")]
    hidden = config["hidden_size"]
    keys = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    values = (config["linear_num_value_heads"]
              * config["linear_value_head_dim"])
    found = {"layers": ["linear_attention" if "w_qkvz" in layer
                        else "full_attention" for layer in layers],
             "untied": "head" in sim.frozen,
             "vocabulary rows": tuple(sim.frozen["embed"].shape),
             "in_proj_qkvz": tuple(linear["w_qkvz"].shape),
             "in_proj_ba": tuple(linear["w_ba"].shape),
             "conv": tuple(linear["conv_w"].shape),
             "out_proj": tuple(linear["w_out"].shape),
             "q": tuple(full["wq"].shape), "k": tuple(full["wk"].shape),
             "q and k norms": (tuple(full["q_norm"].shape),
                               tuple(full["k_norm"].shape)),
             "mlp": tuple(linear["mlp"]["w_gate"].shape),
             "chunk": sim.model.info["config"].chunk,
             "rule": sim.model.info["gdn_rule"]["kernel"],
             "window": int(sim.x.shape[-1])}
    kv = hidden // config["num_attention_heads"] \
        * config["num_key_value_heads"]
    stated = {"layers": list(kinds),
              "untied": not config["tie_word_embeddings"],
              "vocabulary rows": (config["vocab_size"], hidden),
              "in_proj_qkvz": (hidden, 2 * keys + 2 * values),
              "in_proj_ba": (hidden,
                             2 * config["linear_num_value_heads"]),
              "conv": (config["linear_conv_kernel_dim"], 2 * keys + values),
              "out_proj": (values, hidden),
              "q": (hidden, hidden), "k": (hidden, kv),
              "q and k norms": ((hidden,), (kv,)),
              "mlp": (hidden, config["intermediate_size"]),
              "chunk": want["rule_chunk"],
              "rule": want["rule_kernel"],
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
    durs, masks = [], []
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
    return {
        "cell": cell, "cfg": cfg, "sim": sim, "seed": seed, "seen": seen,
        "round_s": durs, "attempted": len(durs),
        "failed": int(np.sum(accepted != want)) if finite else len(durs),
        "compiles_in_window": meter.between(epoch0, epoch1),
        "peer_block": int(sim.peer_block),
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


def reference_logits(spec, frozen, w, x_val, dtype, variant=None):
    import jax.numpy as jnp

    from benchmark.reference import olmo_hybrid as ref

    variant = {k: v for k, v in (variant or {}).items() if k != "store"}
    _, run = ref.compiled(spec, dtype, variant)
    return np.asarray(run(frozen, jnp.asarray(w, dtype), jnp.asarray(x_val)),
                      np.float64)


def leaf_gaps(spec, got, ref):
    """|got - ref| (L2) of every adapter leaf over the larger of that
    leaf's reference norm and the median leaf's."""
    from benchmark.reference import olmo_hybrid as ro

    ref_leaves = ro.leaves(spec, np.asarray(ref, np.float64))
    got_leaves = ro.leaves(spec, np.asarray(got, np.float64))
    norms = [float(np.linalg.norm(r)) for _, r in ref_leaves]
    floor = float(np.median(norms))
    return {name: float(np.linalg.norm(g - r)) / max(nr, floor, 1e-300)
            for (name, g), (_, r), nr in zip(got_leaves, ref_leaves, norms)}


def compare(spec, mix, got, ref, logits):
    """One round's returns against the reference's of the same round."""
    from benchmark.reference import krum as rkrum

    beyond = rkrum.beyond_ties(ref["scores"], ref["accept"], got["mask"],
                               float(mix["tie_rel"])) \
        if ref["scores"].any() else []
    update = (np.asarray(got["w_next"], np.float64)
              - np.asarray(got["w_in"], np.float64))
    gaps = leaf_gaps(spec, update, ref["agg"])
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
            "update_norm": float(np.linalg.norm(ref["agg"])),
            "delta_norms": [float(np.linalg.norm(row))
                            for row in ref["deltas"][:4]]},
        "accept_beyond_ties": len(beyond),
        "stake_mismatch": int(np.sum(ref["stake_next"]
                                     != got["stake_next"])),
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

    from benchmark.reference import olmo_hybrid as ref

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
            got["logits"] = program_logits(sim, got["w_in"], x_val)
    with precision:
        for got in record["seen"]:
            if "_ref" not in got:  # once a record, whatever stands in
                got["_ref"] = ref.reference_round(
                    spec, rnd, record["seed"], got["it"], got["w_in"],
                    got["stake_in"], sim.frozen, shard_rows, x_val, y_val,
                    dtype, accept_from=got["mask"])
                got["_ref_logits"] = reference_logits(
                    spec, sim.frozen, got["w_in"], x_val, dtype)
            truth, truth_logits = got["_ref"], got["_ref_logits"]
            if control:
                variant = variant_of(control, config)
                logits = None
                if "store" not in variant:
                    # a departure of the forward: its logits first (two
                    # windows, seconds). Where they alone are over the
                    # limit the control is not correct already, and the
                    # 21 gradients of its round (minutes of token-by-token
                    # recurrence, a compile a variant) are not computed
                    logits = reference_logits(spec, sim.frozen, got["w_in"],
                                              x_val, dtype, variant)
                    gap = logit_gap(logits, truth_logits)
                    if limits["logit_gap"] is not None \
                            and gap > limits["logit_gap"]:
                        worst["logit_gap"] = max(worst.get("logit_gap", 0),
                                                 gap)
                        record.setdefault("detail", []).append(
                            {"control": control, "stopped_at": "logit_gap"})
                        continue
                low = ref.reference_round(
                    spec, rnd, record["seed"], got["it"], got["w_in"],
                    got["stake_in"], sim.frozen, shard_rows, x_val, y_val,
                    dtype, variant=variant)
                if logits is None:
                    logits = reference_logits(spec, sim.frozen,
                                              ref.bf16(got["w_in"]), x_val,
                                              dtype, variant)
                got = dict(got, w_next=low["w_next"], mask=low["accept"],
                           stake_next=low["stake_next"], err=low["err"],
                           logits=logits)
                # the oracle aggregates and pays with the set it is handed
                stake = np.array(got["stake_in"], np.int64)
                np.add.at(stake, truth["sampled"], np.where(
                    low["accept"], rnd["stake_unit"], -rnd["stake_unit"]))
                truth = dict(truth, stake_next=stake, agg=truth["deltas"][
                    low["accept"]].sum(axis=0))
            found = compare(spec, mix, got, truth, truth_logits)
            record.setdefault("detail", []).append(dict(
                found.pop("_detail"), peer_block=record["peer_block"]))
            for name, value in found.items():
                worst[name] = max(worst.get(name, 0), value)
    record["check_s"] = time.perf_counter() - t0
    return [(name, value, limits[name],
             limits[name] is not None and value <= limits[name])
            for name, value in worst.items()]
