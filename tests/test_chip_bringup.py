"""Tier-1 (CPU) coverage for the chip bring-up: where the compile cache
goes, who may own the chip in a launched topology, a policy load that
leaves the checkout alone, fallbacks that can no longer fire unseen —
and chip_smoke.py's own phases at tiny sizes, so the smoke cannot rot
between chip runs."""

import hashlib
import json
import logging
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------- compile cache placement ----------------

@pytest.fixture
def jax_cache_config():
    import jax

    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir", None)
    yield jax.config
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])


def test_compile_cache_env_set_sets_no_path(jax_cache_config):
    from cubefs_tpu import ops

    got = ops.configure_compile_cache(
        {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else"})
    assert got == "/somewhere/else"
    assert jax_cache_config.jax_compilation_cache_dir is None
    assert jax_cache_config.jax_persistent_cache_min_compile_time_secs == 0


def test_compile_cache_unset_is_fixed_in_checkout(jax_cache_config):
    from cubefs_tpu import ops

    want = os.path.join(ROOT, ".jax_cache")
    assert ops.configure_compile_cache({}) == want
    assert jax_cache_config.jax_compilation_cache_dir == want
    assert jax_cache_config.jax_persistent_cache_min_compile_time_secs == 0
    # fixed: a second process (or call) lands on the same directory
    assert ops.configure_compile_cache({}) == want


def test_compile_cache_left_alone_when_pinned_to_cpu(jax_cache_config):
    from cubefs_tpu import ops

    assert ops.configure_compile_cache({"JAX_PLATFORMS": "cpu"}) is None
    assert jax_cache_config.jax_compilation_cache_dir is None
    # this very suite runs pinned to CPU (testenv.py): nothing was placed
    assert ops.COMPILE_CACHE_DIR is None


def test_require_tpu_refuses_a_cpu_backend():
    from cubefs_tpu import ops

    with pytest.raises(RuntimeError, match="no TPU"):
        ops.require_tpu()


# ---------------- one chip owner per launched topology ----------------

def test_launcher_pins_every_role_but_one_to_cpu():
    from cubefs_tpu.deploy import cluster

    topo = {"blobnodes": 3, "access": True, "codec": True,
            "objectnode": True, "console": True}
    owner = cluster.codec_host(topo)
    assert owner == "codec"  # the sidecar where configured, else access
    assert cluster.codec_host({"blobnodes": 3}) == "access"
    assert cluster.codec_host({"metanodes": 1}) is None

    roles = ["master", "metanode", "datanode", "clustermgr", "blobnode",
             "access", "objectnode", "codec", "fsgateway", "console"]
    for environ in ({}, {"JAX_PLATFORMS": "tpu"}, {"JAX_PLATFORMS": "cpu"}):
        envs = {r: cluster.role_env(r, owner, environ) for r in roles}
        off_cpu = [r for r, e in envs.items() if e["JAX_PLATFORMS"] != "cpu"]
        # the operator's own platform wins; unset, the owner asks for
        # the TPU by name so JAX cannot hand it the CPU quietly
        assert off_cpu == ([] if environ.get("JAX_PLATFORMS") == "cpu"
                           else ["codec"])
        assert all(e["JAX_PLATFORMS"] for e in envs.values())
    assert cluster.role_env("codec", owner, {})["JAX_PLATFORMS"] == "tpu"


def test_proc_is_started_with_the_explicit_env(tmp_path, monkeypatch):
    from cubefs_tpu.deploy import cluster

    seen = {}

    class FakePopen:
        def __init__(self, argv, stdout, stderr, env):
            seen["env"] = env

    monkeypatch.setattr(cluster.subprocess, "Popen", FakePopen)
    env = cluster.role_env("blobnode", "access", {"PATH": "/bin"})
    cluster.Proc("blobnode", {"name": "bn0"}, str(tmp_path), env).log.close()
    assert seen["env"] == {"PATH": "/bin", "JAX_PLATFORMS": "cpu"}


# ---------------- a policy load does not dirty the checkout ----------------

def test_load_policy_mismatch_measures_in_memory(monkeypatch, caplog):
    """The shipped artifacts/CROSSOVER.json is cpu-stamped; a process
    that dispatches to a TPU re-measures for itself and writes nothing."""
    from cubefs_tpu.codec import engine as eng

    path = os.path.join(ROOT, "artifacts", "CROSSOVER.json")
    assert eng._policy_path() == path
    listing = sorted(os.listdir(os.path.dirname(path)))
    digest = hashlib.sha256(open(path, "rb").read()).hexdigest()

    monkeypatch.setattr(eng, "_platform", lambda: "tpu")
    monkeypatch.setattr(eng, "_policy", None)
    monkeypatch.setattr(eng, "_POLICY_SIZES", (4096,))
    real = eng.measure_crossover
    monkeypatch.setattr(
        eng, "measure_crossover",
        lambda **kw: real(sizes=(4096,), repeats=1, **kw))
    with caplog.at_level(logging.WARNING, logger="cubefs.codec"):
        table = eng._load_policy()
    assert table and table == eng._policy
    assert any("re-measuring in memory" in r.message for r in caplog.records)
    assert hashlib.sha256(open(path, "rb").read()).hexdigest() == digest
    assert sorted(os.listdir(os.path.dirname(path))) == listing


# ---------------- fallbacks are logged, and readable ----------------

def test_quarantine_is_logged_with_its_cause(monkeypatch, caplog, rng):
    from cubefs_tpu.codec import engine as eng

    class Exhausted:
        name = "tpu"

        def encode_parity(self, data, n_parity):
            raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")

    monkeypatch.setattr(eng, "_dead_engines", set())
    monkeypatch.setattr(eng, "_instances",
                        dict(eng._instances, tpu=Exhausted()))
    data = rng.integers(0, 256, (2, 6, 64), dtype=np.uint8)
    with caplog.at_level(logging.ERROR, logger="cubefs.codec"):
        out, served = eng._dispatch("tpu", "encode_parity", data, 3)
    assert served != "tpu" and "tpu" in eng._dead_engines
    assert np.array_equal(out, eng.get_engine("numpy").encode_parity(data, 3))
    rec = [r for r in caplog.records if "quarantined" in r.message]
    assert len(rec) == 1 and rec[0].exc_info is not None
    assert "RESOURCE_EXHAUSTED" in str(rec[0].exc_info[1])


def test_step_counter_names_the_engine_that_served(monkeypatch, rng):
    """cubefs_codec_batch_steps_total is stamped after dispatch: a step
    the device engine failed does not count as 'tpu'."""
    from cubefs_tpu.codec import batcher, engine as eng
    from cubefs_tpu.utils import metrics

    class Lost:
        name = "tpu"

        def encode_parity(self, data, n_parity):
            raise RuntimeError("DEVICE_LOST")

    monkeypatch.setattr(eng, "_dead_engines", set())
    monkeypatch.setattr(eng, "_instances", dict(eng._instances, tpu=Lost()))
    bc = batcher.BatchCodec(enabled=True)
    bc.dp_enabled = False
    before = dict(metrics.codec_batch_steps.samples())
    data = rng.integers(0, 256, (1, 6, 64), dtype=np.uint8)
    bc.submit_encode("tpu", data, 3)
    after = dict(metrics.codec_batch_steps.samples())
    grew = {k for k in after if after[k] != before.get(k, 0)}
    assert grew and all(k[1] != "tpu" for k in grew)


@pytest.mark.parametrize("how", ["mismatch", "raise"])
def test_gate_refusal_is_logged_and_readable(monkeypatch, caplog, how):
    from cubefs_tpu.ops import pallas_gf, rs_kernel

    def verify_tile(coeff, tile):
        if how == "raise":
            raise RuntimeError("Mosaic failed to compile")
        return False

    monkeypatch.setattr(pallas_gf, "verify_tile", verify_tile)
    monkeypatch.setattr(rs_kernel, "pallas_refusals", {})
    coeff = np.arange(1, 13, dtype=np.uint8).reshape(2, 6) + (
        7 if how == "raise" else 0)
    with caplog.at_level(logging.ERROR, logger="cubefs.codec"):
        ok = rs_kernel._pallas_verified(coeff.tobytes(), 2, 6)
    assert ok is False
    (key, cause), = rs_kernel.pallas_refusals.items()
    assert key[:2] == (2, 6)
    assert ("Mosaic failed to compile" in cause) == (how == "raise")
    assert any(str(key) in r.getMessage() for r in caplog.records)
    if how == "raise":
        assert any(r.exc_info for r in caplog.records)
    rs_kernel._pallas_verified.cache_clear()


def test_dp_failure_is_logged_not_silent(monkeypatch, caplog, rng):
    from cubefs_tpu.codec import batcher

    bc = batcher.BatchCodec(enabled=True)
    bc.dp_min_bytes = 0

    def boom(*a):
        raise ValueError("mesh does not fit")

    monkeypatch.setattr(bc, "_dp_fn", boom)
    data = rng.integers(0, 256, (8, 6, 64), dtype=np.uint8)
    with caplog.at_level(logging.ERROR, logger="cubefs.codec"):
        assert bc._maybe_dp("tpu", None, data, 3) is None
    rec = [r for r in caplog.records if "dp-sharded" in r.message]
    assert rec and rec[0].exc_info is not None


def test_dp_counter_reports_devices_holding_input(rng):
    import jax

    from cubefs_tpu.codec import batcher
    from cubefs_tpu.utils import metrics

    n_dev = len(jax.devices())
    if n_dev < 2:
        pytest.skip("needs the virtual multi-device mesh")
    bc = batcher.BatchCodec(enabled=True)
    bc.dp_min_bytes = 0
    before = metrics.codec_batch_dp_steps.value(dp=n_dev)
    bc.submit_encode("tpu", rng.integers(
        0, 256, (2 * n_dev, 6, 64), dtype=np.uint8), 3)
    assert metrics.codec_batch_dp_steps.value(dp=n_dev) == before + 1


# ---------------- chip_smoke.py itself, at tiny sizes ----------------

def test_chip_smoke_phases_at_tiny_sizes(tmp_path, capsys):
    import chip_smoke

    out = chip_smoke.run(chip_smoke.TINY, str(tmp_path / "disks"),
                         device_checks=False)
    assert out["ok"] is True and out["claim"] is None
    assert list(out)[-1] == "claim"
    assert set(out["phases"]) == {"put", "get", "reference", "break_repair",
                                  "sidecar", "device_proof",
                                  "checkout_clean"}
    assert all(p["ok"] for p in out["phases"].values())
    assert set(out["phases"]["reference"]["stripes"]) == {
        "EC12P4", "EC6P6", "EC3P3", "EC6P10L2", "EC6P6MSR"}
    assert out["bytes_rebuilt"] > 0
    assert not os.path.exists(tmp_path / "disks")  # data dirs removed
    first = capsys.readouterr().out.splitlines()[0]
    assert first.startswith("platform=cpu ") and "compile_cache=" in first
    # the driver's result object: exactly these keys, device as JAX says
    import jax

    last = json.loads(chip_smoke.result_line(out))
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    assert isinstance(last["device"]["count"], int)


def test_chip_smoke_fails_loudly_when_the_device_was_bypassed(monkeypatch):
    import chip_smoke
    from cubefs_tpu.codec import engine as eng
    from cubefs_tpu.ops import rs_kernel

    monkeypatch.setattr(eng, "_dead_engines", {"tpu"})
    with pytest.raises(RuntimeError, match="quarantined"):
        chip_smoke.phase_device_proof(1, device_checks=True)
    monkeypatch.setattr(eng, "_dead_engines", set())
    monkeypatch.setattr(rs_kernel, "pallas_refusals",
                        {(4, 12, "abc"): "mismatch"})
    with pytest.raises(RuntimeError, match="refused"):
        chip_smoke.phase_device_proof(1, device_checks=True)


def test_full_sizes_are_the_issue_sizes():
    import chip_smoke

    f = chip_smoke.FULL
    assert f.blob_size is None  # AccessConfig() as shipped: 8 MiB blobs
    assert f.large[0] * f.large[1] >= 1 << 30 and f.large[1] == 64 << 20
    assert f.mid == (64, 1 << 20) and f.small == (256, 64 << 10)
    assert f.nodes >= 6 and f.nodes * f.disks_per_node >= 18
    assert f.put_threads == 4 and f.special_bytes == 64 << 20
    assert f.sidecar_shard == 4 << 20
    assert (f.crc_blocks, f.crc_block_len) == (1024, 128 << 10)
