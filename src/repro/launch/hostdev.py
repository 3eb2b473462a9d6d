"""XLA host-device forcing for CPU dev boxes.

Import-safe before jax: this module must never import jax (directly or
indirectly), because the whole point of :func:`force_host_devices` is to
mutate ``XLA_FLAGS`` before jax initializes.
"""
from __future__ import annotations

import os

_FLAG = "--xla_force_host_platform_device_count"


def force_host_devices(count: int) -> None:
    """Append ``--xla_force_host_platform_device_count=count`` to
    ``XLA_FLAGS``, preserving any flags already set; a no-op if the flag is
    already present (an explicit operator choice wins). Must run BEFORE any
    jax import/initialization to take effect."""
    flags = os.environ.get("XLA_FLAGS", "")
    if _FLAG not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {_FLAG}={int(count)}".strip()
