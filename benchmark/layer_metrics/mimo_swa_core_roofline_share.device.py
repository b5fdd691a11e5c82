"""Share of its roofline that the WINDOW layers' attention core (a window of 128 and a learned sink)
reaches in its own time: the least time the chip could take for the core's
work, which is the larger of its MODEL FLOPs over the bf16 peak and its
least bytes over the HBM peak (`benchmark/flops/mimo_v2.py`: from shapes
alone, over the (query, key) pairs a query SEES, forward and backward for
the round's sampled windows, forward for the held-out ones; the program's
own recomputation and the hidden pairs of a visited block not counted; the
same whatever implements the core), over `mimo_swa_core_ms.device`.
Peaks are those of the device the run reports (`benchmark/peaks.py`; an
unknown device is an error). A share: under 1."""

from benchmark.flops.mimo_v2 import core_round
from benchmark.lm_substages import part_ms
from benchmark.peaks import peak


def read(record):
    ms = part_ms(record, "attn_core_swa")
    if not ms:
        return None
    cfg = record["cfg"]
    flops, moved = core_round(record["cell"]["config"], "window",
                              cfg.num_samples * cfg.batch_size,
                              len(record["sim"].x_val))
    kind = record["device"]["kind"]
    least_s = max(flops / peak(kind, "bf16_flops"),
                  moved / peak(kind, "hbm_bytes_s"))
    return least_s / (ms * 1e-3)
