"""Share of the device's bf16 peak that the attention core's MODEL FLOPs
reach in the core's own time: 128 heads x the T (T + 1) / 2 causal pairs x
(2 x 192 + 2 x 128), forward and backward (2.5 x the forward) for the
round's sampled windows and forward alone for the held-out windows the
round evaluates (`benchmark/flops/deepseek_v2.py`; the program's own
recomputation not counted), over `mla_core_ms.device` x the peak of the
device the run reports (`benchmark/peaks.py`; an unknown device is an
error). It counts the same work whatever implements the core. A share:
under 1."""

from benchmark.flops.deepseek_v2 import core_forward_flops, core_step_flops
from benchmark.lm_stages import scope_ms, scope_total
from benchmark.peaks import peak


def read(record):
    found = scope_ms(record)
    if found is None or not found["stages"].get("mla_core"):
        return None
    ms = scope_total(record, "mla_core")
    config, cfg = record["cell"]["config"], record["cfg"]
    shape = (config["num_attention_heads"], config["model"]["window_tokens"],
             config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
             config["v_head_dim"])
    layers = config["num_hidden_layers"]
    flops = layers * (
        core_step_flops(cfg.num_samples * cfg.batch_size, *shape)
        + core_forward_flops(len(record["sim"].x_val), *shape))
    return flops / (ms * 1e-3 * peak(record["device"]["kind"], "bf16_flops"))
