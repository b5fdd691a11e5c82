"""The `device_round_ssm` driver end to end on the CPU at a tiny mix (the
chip check lifted here only), each control coming out not correct, the
scan's FLOP count against XLA's own, and the new cell's files found by the
harness with no edit to a file that was there."""

import importlib.util
import json
import os
import shutil

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "granite_h_fedlora.device_round"
NEW_METRICS = ["ssm_scan_ms.device", "ssm_proj_ms.device",
               "ssm_mix_ms.device", "granite_dense_ms.device",
               "ssm_scan_roofline_share.device", "granite_round_mfu.device"]


def load_run(here):
    spec = importlib.util.spec_from_file_location(
        "benchmark_run_under_test_ssm", os.path.join(here, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def published(cfg, length):
    """A GraniteHybridConfig in the published config.json's keys, as a
    configuration file states them."""
    return {
        "hidden_size": cfg.hidden, "layer_types": list(cfg.layer_types),
        "num_hidden_layers": cfg.layers,
        "num_attention_heads": cfg.heads,
        "num_key_value_heads": cfg.kv_heads,
        "shared_intermediate_size": cfg.mlp_width,
        "mamba_n_heads": cfg.ssm_heads, "mamba_d_head": cfg.ssm_head_dim,
        "mamba_d_state": cfg.ssm_state, "mamba_d_conv": cfg.conv,
        "mamba_chunk_size": cfg.chunk,
        "mamba_expand": cfg.inner // cfg.hidden,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "attention_multiplier": cfg.attention_multiplier,
        "logits_scaling": cfg.logits_scaling, "rms_norm_eps": cfg.eps,
        "rope_theta": 10000, "tie_word_embeddings": True,
        "vocab_size": cfg.vocab,
        "adapters": {"rank": cfg.rank, "alpha": cfg.alpha},
        "model": {"window_tokens": length},
    }


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """A copy of the benchmark with a tiny hybrid cell: new files and new
    entries only."""
    from biscotti_tpu.models import lm
    from biscotti_tpu.models.zoo import model_for_dataset

    tmp = tmp_path_factory.mktemp("ssm_cell")
    here = tmp / "benchmark"
    shutil.copytree(os.path.join(ROOT, "benchmark"), here,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    model = model_for_dataset("lm_tokens_tiny", "granite_h_tiny")
    config = published(model.info["config"], model.d_in)
    config["model"].update(num_params=model.num_params,
                           frozen_params=lm.frozen_count(model))
    config.update(
        name="granite_h_tiny", source="a test", reduced=[], assumed=[],
        guarantees=[],
        biscotti={"dataset": "lm_tokens_tiny",
                  "model_name": "granite_h_tiny",
                  "num_nodes": 12, "num_verifiers": 1, "num_miners": 1,
                  "num_noisers": 1, "sample_percent": 0.7, "epsilon": 1.0,
                  "batch_size": 2, "defense": "KRUM", "learning_rate": 0.1,
                  "grad_clip": 0.005},
        # float32 program against the float64 reference
        limits={"tiny_ssm": {"w_next_leaf_gap": 1e-4, "err_gap": 0.04,
                             "logit_gap": 1e-5}})
    with open(here / "configs" / "granite_h_tiny.json", "w") as f:
        json.dump(config, f)
    with open(here / "traffic" / "device_round_ssm_dp.json") as f:
        mix = json.load(f)
    mix.update(name="tiny_ssm", trace_seconds=1)
    with open(here / "traffic" / "tiny_ssm.json", "w") as f:
        json.dump(mix, f)
    bench["configs"].append({"name": "granite_h_tiny", "source": "a test",
                             "file": "benchmark/configs/granite_h_tiny.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append(
        {"name": "tiny.ssm", "config": "granite_h_tiny",
         "traffic": "tiny_ssm", "chips": 1, "why": "a test"})
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append("tiny.ssm")
    with open(tmp / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return load_run(str(here))


@pytest.fixture(scope="module")
def record(grown):
    """One sound run of the tiny cell, checked once: the reference's round
    stays on the record for every control."""
    from benchmark.compile_meter import CompileMeter

    cell = grown.load_cell("tiny.ssm")
    driver = grown.load_module("drivers", "device_round_ssm")
    record = driver.run(cell=cell, fields=grown.biscotti_fields(cell, 7),
                        seconds=0.3, trace_dir=None, meter=CompileMeter(),
                        t0=0.0)
    return driver, record, driver.check(record)


def test_ssm_driver_end_to_end(grown):
    result = grown.run_cell("tiny.ssm", 2**31 + 4321, 0.5, False,
                            require_tpu=False)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"device_round_ms",
                                      "device_round_ms.p95", "setup_s"}
    assert result["device"]["platform"] == "cpu"  # and says so


def test_sound_run_passes_every_check(record):
    driver, rec, sound = record
    assert all(ok for *_, ok in sound), sound
    assert {name for name, *_ in sound} == set(driver.LIMITS)
    assert "router_flips_beyond_ties" not in driver.LIMITS  # no router
    assert rec["peer_block"] == 8 and "moe" not in rec
    json.dumps([{n: v for n, v, *_ in sound}, rec["detail"],
                rec["end_to_end"], rec["peer_block"]])


@pytest.mark.parametrize("control,by", [
    ("bfloat16", "w_next_leaf_gap"),
    ("decay_bfloat16", "logit_gap"),
    ("no_carry", "logit_gap"),
    ("no_d", "logit_gap"),
    ("no_conv_bias", "logit_gap"),
    ("no_dt_bias", "logit_gap"),
    ("gate_after_norm", "logit_gap"),
    ("residual_one", "logit_gap"),
    ("embedding_one", "logit_gap"),
    ("logits_undivided", "logit_gap"),
    ("attention_sqrt", "logit_gap"),
    ("rotary", "logit_gap"),
])
def test_each_control_comes_out_not_correct(record, control, by):
    driver, rec, _ = record
    assert control in driver.CONTROLS
    found = driver.check(rec, control=control)
    failed = {name for name, *_, ok in found if not ok}
    assert by in failed, (control, found)
    # what benchmark/controls.py prints of it: plain numbers
    json.dumps([{n: v for n, v, *_ in found}, rec.pop("detail")])


def test_every_control_of_the_issue_has_a_test():
    run = load_run(os.path.join(ROOT, "benchmark"))
    driver = run.load_module("drivers", "device_round_ssm")
    assert len(driver.CONTROLS) == 12


def test_a_wrong_size_is_refused(grown):
    cell = grown.load_cell("tiny.ssm")
    driver = grown.load_module("drivers", "device_round_ssm")
    from benchmark.compile_meter import CompileMeter

    for key, value, said in (("num_params", 7, "states 7"),
                             ("window_tokens", 32, "the program holds")):
        wrong = dict(cell, config=dict(cell["config"], model=dict(
            cell["config"]["model"], **{key: value})))
        with pytest.raises(RuntimeError, match=said):
            driver.run(cell=wrong, fields=grown.biscotti_fields(cell, 1),
                       seconds=0.1, trace_dir=None, meter=CompileMeter(),
                       t0=0.0)


def test_scan_flops_against_xla():
    """The chunked form's products as XLA counts them: XLA takes the whole
    square of a chunk's (query, key) pairs, L^2 where the mask lets L (L +
    1) / 2 through; the count's other terms are exact. The backward is
    twice the forward (every product is bilinear in activations)."""
    import jax
    import jax.numpy as jnp

    from benchmark.flops.granite_hybrid import (scan_forward_flops,
                                                scan_step_flops)

    windows, t, heads, p, n, chunk = 2, 128, 3, 16, 24, 32
    k = t // chunk

    def products(x, b, c, before):  # the four matrix products, no decays
        cb = jnp.einsum("wkin,wkjn->wkij", c, b)
        y = jnp.einsum("wkij,wkjhp->wkihp", cb, x)
        own = jnp.einsum("wklhp,wkln->wkhpn", x, b)
        return jnp.sum(y) + jnp.sum(own) + jnp.sum(
            jnp.einsum("wkln,wkhpn->wklhp", c, before))

    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (windows, k, chunk, heads, p), (windows, k, chunk, n),
        (windows, k, chunk, n), (windows, k, heads, p, n))]
    forward = jax.jit(products).lower(*shapes).compile().cost_analysis()[
        "flops"]
    want = scan_forward_flops(windows, t, heads, p, n, chunk)
    inside = windows * t * (chunk + 1) // 2 * (2 * n + 2 * heads * p)
    square = want - inside + inside * 2 * chunk / (chunk + 1)
    assert square <= forward <= 1.05 * square
    assert scan_step_flops(windows, t, heads, p, n, chunk) == 3 * want
    # a window shorter than the chunk is one chunk
    assert scan_forward_flops(1, 16, heads, p, n, 256) == \
        scan_forward_flops(1, 16, heads, p, n, 16)


def test_round_flops_are_the_issues_count():
    """6.38 GFLOP a token forward in products with frozen weights (ISSUE
    33), twice that a stepped token, and the scan, the attention core and
    the adapters on top: a round of 21 + 2 windows."""
    from benchmark.flops.granite_hybrid import round_model_flops

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite_4.0_h_micro_fedlora.json")) as f:
        config = json.load(f)
    weights = 36 * (2 * 2048 * 8512 + 2 * 4096 * 2048) \
        + 4 * (2 * 2 * 2048 * 2048 + 2 * 2 * 2048 * 512) \
        + 40 * 6 * 2048 * 8192 + 2 * 2048 * 100352
    assert round(weights / 1e9, 2) == 6.38
    least = 1024 * (21 * 2 + 2) * weights
    got = round_model_flops(config, 21, 2)
    assert least < got < 1.08 * least
    assert round_model_flops(config, 42, 4) == 2 * got


def test_the_cell_is_found_with_no_edit_to_a_file_that_was_there():
    run = load_run(os.path.join(ROOT, "benchmark"))
    cell = run.load_cell(CELL)
    assert cell["chips"] == 1
    assert cell["mix"]["driver"] == "device_round_ssm"
    names = [m["name"] for m in cell["per_layer"]]
    assert set(NEW_METRICS) <= set(names) and len(names) == 17 + 6
    fields = run.biscotti_fields(cell, 2**31 + 9)
    assert fields["num_nodes"] == 30 and fields["batch_size"] == 1
    assert fields["dataset"] == "lm_tokens_granite"
    assert fields["noising"] is True
    # the other cells read none of the new metrics
    for other in ("emnist_softmax.device_round",
                  "laguna_fedlora.device_round",
                  "deepseek_v2_fedlora.device_round"):
        found = {m["name"] for m in run.load_cell(other)["per_layer"]}
        assert not set(NEW_METRICS) & found
    # every reader file loads, and finds nothing in an empty record
    for name in NEW_METRICS:
        assert run.load_module("layer_metrics", name).read({}) is None
    driver = run.load_module("drivers", "device_round_ssm")
    assert set(driver.limits_of(cell)) == set(driver.LIMITS)
    assert all(v is not None for v in driver.limits_of(cell).values())
    # a traced slice holds at least three rounds of up to 5 s
    assert cell["mix"]["trace_seconds"] >= 12
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # appended AFTER everything PR 32's file had, together and in the
    # issue's order (a later PR appends after them: "last" would turn this
    # test red, as PR 27's and PR 31's of the same name are)
    config = [c["name"] for c in bench["configs"]].index(
        "granite_4.0_h_micro_fedlora")
    assert config >= 3 and bench["configs"][config]["reduced"] == []
    assert [w["name"] for w in bench["workloads"]].index(CELL) >= 3
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW_METRICS[0])
    assert first >= 28 and names[first:first + 6] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "device_round_ms"
               and m["layer"] == "language model"
               for m in bench["per_layer"][first:first + 6])


def test_the_configuration_carries_every_published_number():
    """Every key of the catalog row's `config` under the same key,
    UNCHANGED (`reduced` is empty: the model is whole); the driver's sizes
    come out of the built model."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite_4.0_h_micro_fedlora.json")) as f:
        config = json.load(f)
    from biscotti_tpu.models import granite_hybrid, lm

    preset = granite_hybrid.PRESETS["granite_h_micro_fedlora"]
    for key, value in published(preset, 1024).items():
        if key not in ("adapters", "model"):
            assert config[key] == value, key
    assert preset.rank == config["adapters"]["rank"]
    assert preset.alpha == config["adapters"]["alpha"]
    assert config["reduced"] == []
    assert config["published"] == {"num_hidden_layers": 40,
                                   "vocab_size": 100352}
    model = granite_hybrid.granite_hybrid_model("granite_h_micro_fedlora",
                                                preset, 1024)
    assert model.num_params == config["model"]["num_params"] == 6410240
    assert lm.frozen_count(model) == config["model"]["frozen_params"] \
        == 3195459328
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-micro")
    assert config["source"].startswith(row["source_url"])
    assert {k for k, v in row["config"].items() if config.get(k) != v} \
        == set()
