"""Test harness: force an 8-device virtual CPU mesh so multi-chip sharding
paths compile and run without TPU hardware (see SURVEY.md environment notes).

Must run before jax is imported anywhere.
"""

import os

# Unconditional: the session env may leave JAX_PLATFORMS unset on a machine
# with a TPU, but the test suite always runs on the virtual 8-device CPU
# mesh (the chip is chip_smoke.py's). Override via jax.config too, in case
# jax was imported before this file.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", os.environ["JAX_ENABLE_X64"] == "1")


async def wait_until(cond, budget: float = 120.0, what: str = "",
                     poll: float = 0.05):
    """Shared condition-driven wait for the live-TCP suites (import with
    `from conftest import wait_until`): the de-flaked replacement for
    fixed-height/wall-clock waits (load-flaky, CHANGES PR 4/6) — a test
    advances the moment the OBSERVABLE state it needs appears, with the
    budget only as a generous backstop a loaded box stretches into.
    Pass poll=0 to react at event-loop granularity — required when the
    waiter must act INSIDE the round the condition marks (a warm suite
    finishes a whole round in less than the default poll interval)."""
    import asyncio

    loop = asyncio.get_event_loop()
    deadline = loop.time() + budget
    while not cond():
        assert loop.time() < deadline, f"timeout waiting for {what}"
        await asyncio.sleep(poll)
