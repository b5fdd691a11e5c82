"""Device time (ms) one execution of the round's program spends on the 40
dense SwiGLUs of Granite-4.0-H-Micro (scope `lm_dense`: the block norm,
2,048 x 8,192 gate and up, 8,192 x 2,048 down, the residual), forward,
recomputation and backward. Read as `ssm_scan_ms.device` is; None where
the traced model is not the hybrid (it opens no `ssm_scan`)."""

from benchmark.lm_stages import scope_ms


def read(record):
    found = scope_ms(record)
    if found is None or "ssm_scan" not in found["stages"]:
        return None
    return found["stages"].get("lm_dense")
