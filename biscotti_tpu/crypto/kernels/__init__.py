"""Accelerator-resident crypto plane (ISSUE 13, ROADMAP open item #2).

After PR 6's batching, miner crypto is one big multi-scalar
multiplication per intake — CPU bigint work while the device idles. This
package moves the four hot kernels onto the accelerator as limb-
decomposed vmapped jnp programs (`field.py` → `group.py` → `msm.py`),
behind one process-wide arming switch:

    from biscotti_tpu.crypto import kernels
    kernels.set_enabled(True)          # what --device-crypto does
    kernels.active()                   # armed AND runnable here

**Default OFF.** Disarmed, every caller takes the CPU path
bit-identically. Arming a plane the backend cannot run (no jax, x64 mode
off, or a compiler that refuses the kernels — `available()` compiles the
smallest one to find out) is an error naming the reason, never a silent
CPU run. Armed, the seams PR 6
created — `cm.batch_verify_commitments`, `VssIntakeBatch` wave folds,
`cm.batch_schnorr_verify`, `ss.recover_coeffs` — compute their batch
verdicts on device; the CPU path stays the exact-verdict oracle, and
REJECTION evidence (bisection, per-worker fallback, stake debits) always
comes from the CPU recompute, so debits stay byte-identical
(docs/CRYPTO_KERNELS.md spells out the contract; the property suite in
tests/test_crypto_kernels.py pins every kernel against the python-int
oracles).

Importing this package is cheap (numpy only): jax loads lazily on first
`available()` / kernel call, so the disarmed runtime never pays for it.
"""

from __future__ import annotations

from typing import Optional

from biscotti_tpu.crypto.kernels.instrument import (  # noqa: F401
    device_calls, device_seconds, release_hooks, reset_counters,
    set_metrics_registry, set_span_hook)
from biscotti_tpu.crypto.kernels.primitives import (  # noqa: F401
    CompileError, ext_add, fixed_base_mult, grid_validate_sum, msm,
    pedersen_commit_point, point_neg_limbs, prewarm, shamir_recover)

_enabled = False
_avail: Optional[bool] = None
_avail_reason = ""


def set_enabled(on: bool) -> None:
    """Arm/disarm the device-crypto plane process-wide (the
    --device-crypto switch). Arming while unavailable raises with the
    reason: a run that asked for device crypto must not quietly do the
    work on the CPU and report otherwise."""
    global _enabled
    if on and not available():
        _enabled = False
        raise RuntimeError(
            f"--device-crypto requested but the device plane is "
            f"unavailable here: {_avail_reason}")
    _enabled = bool(on)


def available() -> bool:
    """True when the kernel plane can run here: jax imports, x64 mode is
    on (the limb accumulators are int64), and the default backend's
    compiler accepts the smallest kernel (one point addition — every
    kernel is built from its field multiply). Probed once per process;
    the compiler's message is kept for `availability_reason()`."""
    global _avail, _avail_reason
    if _avail is None:
        try:
            import jax
        except ImportError as e:  # pragma: no cover - env-dependent
            _avail, _avail_reason = False, f"jax unavailable: {e}"
            return False
        if not jax.config.jax_enable_x64:
            _avail = False
            _avail_reason = ("jax x64 mode disabled — int64 limb "
                             "accumulators need JAX_ENABLE_X64=1")
            return False
        from biscotti_tpu.crypto.kernels import group, instrument

        ident = group.IDENTITY_LIMBS[None]
        try:
            with instrument.suppressed():
                ext_add(ident, ident)
            _avail = True
        except CompileError as e:
            _avail, _avail_reason = False, str(e)
    return bool(_avail)


def availability_reason() -> str:
    available()
    return _avail_reason


def active() -> bool:
    """Armed — the one predicate every CPU/device dispatch seam
    consults. Arming already proved the plane runnable (set_enabled)."""
    return _enabled


def active_module():
    """This package when `active()`, else None — the shared body of the
    per-seam `_device_mod()` probes (commitments.py, secretshare.py), so
    the dispatch predicate lives in exactly one place."""
    import biscotti_tpu.crypto.kernels as _k

    return _k if active() else None
