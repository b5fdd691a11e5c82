"""Share of its roofline that Olmo-Hybrid-7B's gated delta rule reaches in
its own time: the least time the chip could take for the rule's work,
which is the larger of its MODEL FLOPs over the bf16 peak and its least
bytes over the HBM peak (`benchmark/flops/olmo_hybrid.py`: from shapes
alone at the MODEL's widths, 96 | 192, whatever the kernel pads to;
forward and backward for the round's sampled windows, forward for the
held-out ones, the program's own recomputation not counted), over
`olmo_gdn_rule_ms.device`. Peaks are those of the device the run reports
(`benchmark/peaks.py`; an unknown device is an error). A share: under 1."""

from benchmark.flops.olmo_hybrid import rule_round
from benchmark.olmo_stages import total, windows
from benchmark.peaks import peak


def read(record):
    ms = total(record, "gdn_rule")
    if not ms:
        return None
    flops, moved = rule_round(record["cell"]["config"], *windows(record))
    kind = record["device"]["kind"]
    least_s = max(flops / peak(kind, "bf16_flops"),
                  moved / peak(kind, "hbm_bytes_s"))
    return least_s / (ms * 1e-3)
