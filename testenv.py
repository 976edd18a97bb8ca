"""pytest bootstrap plugin (loaded via `-p testenv` in pytest.ini).

Imported during plugin registration — before jax is imported anywhere —
which is the window in which the suite's platform can still be chosen:
the CPU backend with a virtual 8-device mesh. Tier-1 never touches the
chip; the chip is reached only through `python chip_smoke.py`.
"""

import os

import tpuenv

# Respect an explicitly set device count (e.g. a developer reproducing a
# 4-device mesh bug); pin the suite's default of 8 otherwise.
_pinned = "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", "")
os.environ.update(tpuenv.cpu_env(os.environ, n_devices=None if _pinned else 8))
