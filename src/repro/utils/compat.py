"""Process-environment helpers shared by the code that spawns JAX
subprocesses (distributed test cases, the mesh-gram bench children,
CLI tests)."""
from __future__ import annotations

import os
from typing import Optional


def force_host_devices_flags(devices: int, base: Optional[str] = None) -> str:
    """XLA_FLAGS value forcing ``devices`` fake host devices, REPLACING
    any force-count flag already in ``base`` (default: the current env).

    The last duplicated XLA flag wins, so naively prepending lets an
    inherited export override the requested count — every subprocess
    spawner that fakes a device count (distributed test cases, the
    mesh-gram bench children, CLI tests) must route through this.
    """
    kept = [f for f in (os.environ.get("XLA_FLAGS", "") if base is None
                        else base).split()
            if not f.startswith("--xla_force_host_platform_device_count")]
    return " ".join(
        [f"--xla_force_host_platform_device_count={devices}"] + kept)
