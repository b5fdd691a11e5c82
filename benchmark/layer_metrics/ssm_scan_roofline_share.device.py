"""Share of its roofline that the state-space scan reaches in its own
time: the least time the chip could take for the scan's work, which is the
larger of its MODEL FLOPs over the bf16 peak and its least bytes over the
HBM peak (`benchmark/flops/granite_hybrid.py`: from shapes alone, forward
and backward for the round's sampled windows, forward for the held-out
ones, the program's own recomputation not counted; the same whatever
implements the scan), over `ssm_scan_ms.device`. Peaks are those of the
device the run reports (`benchmark/peaks.py`; an unknown device is an
error). A share: under 1."""

from benchmark.flops.granite_hybrid import (scan_forward_bytes,
                                            scan_forward_flops, scan_shape,
                                            scan_step_bytes, scan_step_flops)
from benchmark.lm_stages import scope_ms
from benchmark.peaks import peak


def read(record):
    found = scope_ms(record)
    ms = found and found["stages"].get("ssm_scan")
    if not ms:
        return None
    config, cfg = record["cell"]["config"], record["cfg"]
    shape = scan_shape(config)
    layers = config["layer_types"].count("mamba")
    sampled = cfg.num_samples * cfg.batch_size
    held_out = len(record["sim"].x_val)
    chunk = config["mamba_chunk_size"]
    flops = layers * (scan_step_flops(sampled, *shape, chunk)
                      + scan_forward_flops(held_out, *shape, chunk))
    moved = layers * (scan_step_bytes(sampled, *shape)
                      + scan_forward_bytes(held_out, *shape))
    kind = record["device"]["kind"]
    least_s = max(flops / peak(kind, "bf16_flops"),
                  moved / peak(kind, "hbm_bytes_s"))
    return least_s / (ms * 1e-3)
