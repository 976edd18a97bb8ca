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

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_traceback_in_locations_limit")
    saved = [getattr(jax.config, name) for name in names]
    jax.config.update("jax_compilation_cache_dir", None)
    yield jax.config
    for name, value in zip(names, saved):
        jax.config.update(name, value)


def test_compile_cache_env_set_sets_no_path(jax_cache_config):
    from cubefs_tpu import ops

    got = ops.configure_compile_cache(
        {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else"})
    assert got == "/somewhere/else"
    assert jax_cache_config.jax_compilation_cache_dir is None
    assert jax_cache_config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax_cache_config.jax_traceback_in_locations_limit == 1


def test_compile_cache_unset_is_fixed_in_checkout(jax_cache_config):
    from cubefs_tpu import ops

    want = os.path.join(ROOT, ".jax_cache")
    assert ops.configure_compile_cache({}) == want
    assert jax_cache_config.jax_compilation_cache_dir == want
    assert jax_cache_config.jax_persistent_cache_min_compile_time_secs == 0
    # fixed: a second process (or call) lands on the same directory
    assert ops.configure_compile_cache({}) == want


def test_a_fused_programs_cache_key_does_not_name_its_callers(
        jax_cache_config):
    """What keys a Pallas program in the persistent cache is its Mosaic
    payload, source locations and all. A process that keeps a cache
    lowers the same payload whoever calls the program first; with ten
    frames of call stack in the locations (JAX's default) it did not,
    and runs of one tree kept missing each other's entries. (That the
    kernel keeps its name in the compiled program: tests/test_aot_tpu.py.)"""
    import re

    import jax
    import jax.numpy as jnp

    from cubefs_tpu import ops
    from cubefs_tpu.ops import pallas_gf

    ops.configure_compile_cache({"JAX_COMPILATION_CACHE_DIR": "/elsewhere"})
    assert jax_cache_config.jax_traceback_in_locations_limit == 1
    w = jax.ShapeDtypeStruct((32, 96), jnp.int8)
    x = jax.ShapeDtypeStruct((2, 12, 1024), jnp.uint8)

    def payload(call) -> list[str]:
        # a program built anew: it is traced on first use, by this caller
        program = pallas_gf._apply_fn.__wrapped__(
            4, 12, (2, 12, 1024), 256, False)
        text = jax.jit(lambda w, x: call(program, w, x)).trace(w, x).lower(
            lowering_platforms=("tpu",)).as_text()
        return re.findall(r'backend_config\s*=\s*"((?:[^"\\]|\\.)*)"', text)

    def bare(program, w, x):
        return program(w, x)

    def phased(program, w, x):
        return (lambda fn, *args: fn(*args))(program, w, x)

    direct = payload(bare)
    assert direct and payload(phased) == direct


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
    bc = batcher.BatchCodec()
    bc.dp_enabled = False
    before = dict(metrics.codec_batch_steps.samples())
    data = rng.integers(0, 256, (1, 6, 64), dtype=np.uint8)
    bc.submit_encode("tpu", data, 3)
    after = dict(metrics.codec_batch_steps.samples())
    grew = {k for k in after if after[k] != before.get(k, 0)}
    assert grew and all(k[1] != "tpu" for k in grew)


@pytest.mark.parametrize("how", ["mismatch", "raise"])
def test_gate_refusal_is_logged_and_readable(monkeypatch, caplog, how):
    """The gate is per program (rows, cols, tile): a refusal — a
    mismatch, or the gate itself raising — is logged with its cause,
    recorded once in ``pallas_refusals`` under the program's key, and
    holds for every matrix of that shape without running again."""
    from cubefs_tpu.ops import pallas_gf, rs_kernel
    from cubefs_tpu.utils import metrics

    seen = []

    def verify_tile(coeff, tile, seed=0):
        seen.append(np.asarray(coeff).copy())
        if how == "raise":
            raise RuntimeError("Mosaic failed to compile")
        return False

    monkeypatch.setattr(pallas_gf, "verify_tile", verify_tile)
    monkeypatch.setattr(rs_kernel, "pallas_refusals", {})
    monkeypatch.setattr(rs_kernel, "_gate", {})
    coeff = np.arange(1, 13, dtype=np.uint8).reshape(2, 6)
    refused = metrics.codec_pallas_gate.value(result="refused")
    with caplog.at_level(logging.ERROR, logger="cubefs.codec"):
        ok = rs_kernel._pallas_verified(2, 6, 512, coeff)
    assert ok is False
    (key, cause), = rs_kernel.pallas_refusals.items()
    assert key == (2, 6, 512)
    assert ("Mosaic failed to compile" in cause) == (how == "raise")
    assert any(str(key) in r.getMessage() for r in caplog.records)
    if how == "raise":
        assert any(r.exc_info for r in caplog.records)
    assert metrics.codec_pallas_gate.value(result="refused") == refused + 1
    # the first matrix the gate tries is a seeded random one, not the
    # caller's: what is blessed or refused is the program
    assert seen[0].shape == (2, 6) and not np.array_equal(seen[0], coeff)
    n = len(seen)
    assert rs_kernel._pallas_verified(2, 6, 512, coeff + 7) is False
    assert len(seen) == n and len(rs_kernel.pallas_refusals) == 1


def test_gate_tries_seeded_matrices_and_the_first_real_one(monkeypatch):
    from cubefs_tpu.ops import pallas_gf, rs_kernel

    seen = []

    def verify_tile(coeff, tile, seed=0):
        seen.append((np.asarray(coeff).copy(), tile, seed))
        return True

    monkeypatch.setattr(pallas_gf, "verify_tile", verify_tile)
    monkeypatch.setattr(rs_kernel, "pallas_refusals", {})
    monkeypatch.setattr(rs_kernel, "_gate", {})
    real = np.arange(1, 25, dtype=np.uint8).reshape(2, 12)
    assert rs_kernel._pallas_verified(2, 12, 256, real) is True
    assert len(seen) == rs_kernel.GATE_MATRICES + 1
    assert np.array_equal(seen[-1][0], real)
    assert all(m.shape == (2, 12) and t == 256 for m, t, _ in seen)
    assert len({m.tobytes() for m, _, _ in seen}) == len(seen)
    assert not rs_kernel.pallas_refusals
    # blessed once per process: no matrix of the shape runs it again,
    # another tile is another program
    assert rs_kernel._pallas_verified(2, 12, 256, real + 1) is True
    assert len(seen) == rs_kernel.GATE_MATRICES + 1
    assert rs_kernel._pallas_verified(2, 12, 512, real) is True
    assert len(seen) == 2 * (rs_kernel.GATE_MATRICES + 1)


@pytest.mark.parametrize("planted", [False, True])
def test_a_mismatching_fused_program_is_refused_and_the_jnp_path_serves(
        monkeypatch, caplog, rng, planted):
    """The gate on the real kernel (Pallas interpreter here): a fused
    program whose result differs in one bit from the jnp path's is
    refused, logged, listed in ``pallas_refusals`` and its shapes are
    served by the jnp program — bit-identical to the table engine; the
    honest program is blessed and serves."""
    from cubefs_tpu.codec.engine import get_engine
    from cubefs_tpu.ops import gf256, pallas_gf, rs_kernel
    from cubefs_tpu.utils import metrics

    real_fn = pallas_gf._apply_fn

    def apply_fn(rows, cols, shape, tile, interpret):
        fn = real_fn(rows, cols, shape, tile, True)  # no Mosaic here
        if not planted:
            return fn
        return lambda w, x: fn(w, x).at[..., 0, 0].add(1)

    monkeypatch.setattr(pallas_gf, "_apply_fn", apply_fn)
    monkeypatch.setattr(pallas_gf, "DEFAULT_TILE", 256)
    monkeypatch.setattr(rs_kernel, "_use_pallas", lambda: True)
    monkeypatch.setattr(rs_kernel, "pallas_refusals", {})
    monkeypatch.setattr(rs_kernel, "_gate", {})
    rows = gf256.decode_matrix(6, 9, [0, 2, 3, 5, 6, 8])[:2]
    # 1500 B of shard run at the 6-tile rung, a shape of this test's own
    data = rng.integers(0, 256, (3, 6, 1500), dtype=np.uint8)
    built = {k: metrics.codec_programs.value(kernel=k)
             for k in ("bits", "gf256_apply")}
    with caplog.at_level(logging.ERROR, logger="cubefs.codec"):
        got = get_engine("tpu").matrix_apply(rows, data)
    assert np.array_equal(got, get_engine("numpy").matrix_apply(rows, data))
    assert rs_kernel.rung_width(1500) == 1536
    assert rs_kernel.serves_fused(rows, 1536) is (not planted)
    if planted:
        assert list(rs_kernel.pallas_refusals) == [(2, 6, 256)]
        assert "mismatch" in rs_kernel.pallas_refusals[(2, 6, 256)]
        assert any("(2, 6, 256)" in r.getMessage() for r in caplog.records)
        # the step itself ran the jnp program of its shape
        assert metrics.codec_programs.value(kernel="bits") > built["bits"]
    else:
        assert not rs_kernel.pallas_refusals and not caplog.records


def test_dp_failure_is_logged_not_silent(monkeypatch, caplog, rng):
    from cubefs_tpu.codec import batcher

    bc = batcher.BatchCodec()
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
    bc = batcher.BatchCodec()
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
                                  "sidecar", "two_loss", "any_size",
                                  "repair_any_size", "device_proof",
                                  "checkout_clean"}
    # a mixed-size volume per size class, two units of each rebuilt
    # after the worker's ready, from one read of the survivors a volume:
    # by width rung, and nothing built
    repair = out["phases"]["repair_any_size"]
    assert repair["tasks"] == 6 and repair["ready_steps"] == 9
    assert repair["shared_reads"] == 3
    assert repair["rebuilt_shards_checked"] >= 30
    assert repair["programs_built_after_ready"] == 0
    assert 6 <= repair["decode_steps"] < 2 * sum(
        repair["distinct_shard_sizes"].values())
    # two disks under one EC12P4 volume lost together: its two tasks
    # came in one lease and the second was decoded from the first's read
    broken = out["phases"]["break_repair"]
    assert len(broken["rounds"][0]["disks"]) == 2
    assert all(len(r["disks"]) == 1 for r in broken["rounds"][1:])
    assert broken["shared_reads"] >= 1
    # sizes nobody named, after the front door's ready: nothing built
    any_size = out["phases"]["any_size"]
    assert any_size["objects"] == 6 and any_size["ready_steps"] > 0
    assert any_size["codemodes"] == ["EC12P4", "EC3P3", "EC6P6"]
    assert any_size["programs_built_after_ready"] == 0
    assert out["phases"]["two_loss"]["matrices"] == 16 + 240
    assert out["phases"]["two_loss"]["compiles_after_first_step"] == 0
    # 256 (lost, also lost) pairs are 209 distinct matrices: a second
    # loss past the 13th survivor changes nothing
    assert out["phases"]["two_loss"]["distinct_matrices"] == 209
    assert out["phases"]["two_loss"]["matrix_cache_misses"] <= 209
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
                        {(4, 12, 32768): "mismatch"})
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
