"""Host-platform device-count helpers.

The engines call ``jax.shard_map`` / ``lax.pcast`` / ``lax.axis_size``
directly (jax 0.9, the one installed version); what remains here is the
forced multi-device CPU mesh the tests and smokes run on.
"""

from __future__ import annotations

import os
import re

import jax


def host_device_count_flags(flags: str | None, n: int) -> str:
    """``XLA_FLAGS`` string with the host-platform device-count flag
    forced to ``n`` (any existing count flag replaced) — shared by
    :func:`force_host_device_count` and ``run_chain``'s child-env
    rewrite so the flag format lives in one place."""
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   flags or "").strip()
    return f"{flags} --xla_force_host_platform_device_count={n}".strip()


def force_host_device_count(n: int) -> tuple[bool, str]:
    """Arrange for the host platform to expose ``n`` XLA devices
    (``--xla_force_host_platform_device_count``) — the test vehicle for
    same-mesh multi-device work (the ici transport tier, sharding
    tests) on hosts without a real accelerator mesh.

    The flag is read once, when jax constructs its backends, so this
    sets it and then counts ``jax.devices()`` — which constructs them
    if nothing has yet.  Returns ``(ok, reason)``: ``ok`` is False with
    a skip-worthy ``reason`` when jax had already initialized with
    fewer devices (callers like the conftest fixture turn that into a
    skip instead of a wrong-mesh test run); the environment is left as
    it was in that case.
    """
    n = int(n)
    before = os.environ.get("XLA_FLAGS")
    os.environ["XLA_FLAGS"] = host_device_count_flags(before, n)
    have = len(jax.devices())
    if have >= n:
        return True, f"backend exposes {have} host devices"
    if before is None:
        del os.environ["XLA_FLAGS"]
    else:
        os.environ["XLA_FLAGS"] = before
    return False, (f"jax already initialized with {have} host "
                   f"device(s) < {n}; set XLA_FLAGS="
                   f"--xla_force_host_platform_device_count={n} "
                   f"before the first jax call")
