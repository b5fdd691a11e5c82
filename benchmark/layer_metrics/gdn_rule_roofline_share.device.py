"""Share of its roofline that the gated delta rule reaches in its own
time: the least time the chip could take for the rule's work, which is the
larger of its MODEL FLOPs over the bf16 peak and its least bytes over the
HBM peak (`benchmark/flops/qwen3_next.py`: from shapes alone, forward and
backward for the round's sampled windows, forward for the held-out ones,
the program's own recomputation not counted; the same whatever implements
the rule), over `gdn_rule_ms.device`. Peaks are those of the device the
run reports (`benchmark/peaks.py`; an unknown device is an error). A
share: under 1."""

from benchmark.flops.qwen3_next import rule_round
from benchmark.lm_stages import scope_ms
from benchmark.peaks import peak


def read(record):
    found = scope_ms(record)
    ms = found and found["stages"].get("gdn_rule")
    if not ms:
        return None
    cfg = record["cfg"]
    flops, moved = rule_round(record["cell"]["config"],
                              cfg.num_samples * cfg.batch_size,
                              len(record["sim"].x_val))
    kind = record["device"]["kind"]
    least_s = max(flops / peak(kind, "bf16_flops"),
                  moved / peak(kind, "hbm_bytes_s"))
    return least_s / (ms * 1e-3)
