"""CPU-pinned environment for interpreters that must stay off the chip.

The tier-1 suite (testenv.py) and the multichip dry-run children
(__graft_entry__.py) run on a virtual CPU mesh: JAX pinned to the CPU
backend with N host devices. This module is the one definition of that
environment. The chip itself is reached only by a process that asks for
it (chip_smoke.py, cellbench, the deploy launcher's codec host).
"""

from __future__ import annotations


def cpu_env(environ, n_devices: int | None = None) -> dict:
    """A copy of ``environ`` with JAX pinned to CPU; with ``n_devices``,
    also pin the virtual host device count (overriding any pre-existing
    value, so the mesh size always matches the caller's request)."""
    env = dict(environ)
    env["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        flags = [
            f
            for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        flags.append(f"--xla_force_host_platform_device_count={int(n_devices)}")
        env["XLA_FLAGS"] = " ".join(flags)
    return env
