"""Test fixtures. Env setup (CPU backend, 8-device virtual mesh) lives
in testenv.py, which pytest.ini loads as a `-p` plugin before any jax
import — see its docstring for why it can't live here."""

import os

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0xC0DEC)


@pytest.fixture(params=["bits", "fused"])
def program(request, monkeypatch):
    """Which of the device engine's two programs `rs_kernel.plan` hands
    it: the jnp bit-matmul (what it picks off the chip) or the fused
    Pallas program — interpreted here, at a tile of 256 so test-sized
    shards span several grid steps."""
    if request.param == "fused":
        from cubefs_tpu.ops import pallas_gf, rs_kernel

        monkeypatch.setattr(rs_kernel, "serves_fused", lambda coeff, s: True)
        monkeypatch.setattr(pallas_gf, "DEFAULT_TILE", 256)
    return request.param


class _StillTracker:
    """Empty SLO snapshot: the gate sees a healthy system."""

    def snapshot(self):
        return {}


@pytest.fixture(autouse=True)
def _qos_burn_isolated():
    """Pin the process-global QoS gate to a burn-free tracker per test.

    `qos.DEFAULT` closes the loop on `slo.DEFAULT_TRACKER`, which
    windows the process-global stage histogram — so slow samples
    observed by one test (chaos drills, injected RTTs) would brown out
    the gate and change behavior in unrelated tests minutes later
    (suppressed cache fills, shrunken repair steps). Tests that want
    the burn coupling build a private gate + tracker or use
    `force_level`, which this fixture leaves alone (and unpins)."""
    from cubefs_tpu.utils import qos

    saved_tracker = qos.DEFAULT._tracker
    saved_levels = qos.DEFAULT._levels
    saved_forced = dict(qos.DEFAULT._forced)
    qos.DEFAULT._tracker = _StillTracker()
    qos.DEFAULT._levels = {}
    qos.DEFAULT._last_refresh = float("-inf")
    yield
    qos.DEFAULT._tracker = saved_tracker
    qos.DEFAULT._levels = saved_levels
    qos.DEFAULT._forced = saved_forced
    qos.DEFAULT._last_refresh = float("-inf")


@pytest.fixture(autouse=True)
def kept(monkeypatch):
    """The process's kept arrays (`hostmem.KEPT`), empty for each test:
    what one test's PUTs, results and repair steps leave there is
    neither memory the next test holds nor a buffer it is handed."""
    from cubefs_tpu.utils import hostmem

    pool = hostmem.KeptArrays()
    monkeypatch.setattr(hostmem, "KEPT", pool)
    return pool


def pytest_sessionfinish(session, exitstatus):
    """When the run executed under CUBEFS_SANITIZE=1, persist the lock
    witness's evidence (order graph edges, acquisition counters, RPC
    checks) so `cubefs-cli sanitize status` — and the chaos-drill
    acceptance gate — can read what the dynamic sanitizer actually saw.
    A raise-free run with zero edges would mean the witness watched
    nothing; the dump makes that auditable instead of silent."""
    from cubefs_tpu.utils import lockwitness

    w = lockwitness.active()
    if w is None:
        return
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    w.dump(os.path.join(root, "artifacts", "SANITIZE_WITNESS.json"))
