"""Encoder/LRC semantics round-trips over every production codemode —
the analog of the reference's encoder unit suite (blobstore/common/ec/
encoder_test.go round-trips every codemode)."""

import numpy as np
import pytest

from cubefs_tpu.codec import codemode as cm
from cubefs_tpu.codec.encoder import CodecConfig, ECError, LrcEncoder, new_encoder
from cubefs_tpu.codec.engine import get_engine

EC_MODES = [
    m
    for m, t in cm.TACTICS.items()
    if not t.is_replicate() and m.value < 100  # production EC modes
]


def make_encoder(mode, engine="tpu", verify=False):
    return new_encoder(CodecConfig(mode=mode, enable_verify=verify, engine=engine))


@pytest.mark.parametrize("mode", EC_MODES)
@pytest.mark.parametrize("engine", ["numpy", "tpu"])
def test_encode_verify_roundtrip(mode, engine, rng):
    enc = make_encoder(mode, engine)
    t = enc.t
    # 60 is divisible by every production alpha (1, 3, 5, 6): MSR modes
    # need alpha-divisible shard widths (beta = S / alpha sub-shards)
    stripe = np.zeros((t.total, 60), dtype=np.uint8)
    stripe[: t.n] = rng.integers(0, 256, (t.n, 60))
    enc.encode(stripe)
    assert enc.verify(stripe)
    stripe[0, 0] ^= 0xFF
    assert not enc.verify(stripe)


@pytest.mark.parametrize("mode", EC_MODES)
def test_engines_bit_identical(mode, rng):
    t = cm.tactic(mode)
    data = rng.integers(0, 256, (t.total, 60)).astype(np.uint8)
    data[t.n :] = 0
    a = make_encoder(mode, "numpy").encode(data.copy())
    b = make_encoder(mode, "tpu").encode(data.copy())
    assert np.array_equal(a, b)


@pytest.mark.parametrize("mode", [cm.CodeMode.EC12P4, cm.CodeMode.EC6P6, cm.CodeMode.EC24P8])
def test_reconstruct_roundtrip(mode, rng):
    enc = make_encoder(mode)
    t = enc.t
    stripe = np.zeros((t.total, 48), dtype=np.uint8)
    stripe[: t.n] = rng.integers(0, 256, (t.n, 48))
    enc.encode(stripe)
    golden = stripe.copy()
    bad = [1, t.n, t.n + t.m - 1][: t.m]
    stripe[bad] = 0
    enc.reconstruct(stripe, bad)
    assert np.array_equal(stripe, golden)


def test_reconstruct_data_only(rng):
    enc = make_encoder(cm.CodeMode.EC6P3)
    t = enc.t
    stripe = enc.split(rng.integers(0, 256, 6 * 2048).astype(np.uint8).tobytes())
    enc.encode(stripe)
    golden = stripe.copy()
    bad = [0, t.n + 1]  # one data, one parity
    stripe[bad] = 0
    enc.reconstruct_data(stripe, bad)
    assert np.array_equal(stripe[0], golden[0])  # data restored
    assert not np.array_equal(stripe[t.n + 1], golden[t.n + 1])  # parity untouched


def test_too_many_missing_raises(rng):
    enc = make_encoder(cm.CodeMode.EC6P3)
    stripe = np.zeros((9, 16), dtype=np.uint8)
    with pytest.raises(ECError):
        enc.reconstruct(stripe, [0, 1, 2, 3])


@pytest.mark.parametrize("mode", [cm.CodeMode.EC6P10L2, cm.CodeMode.EC6P3L3, cm.CodeMode.EC4P4L2])
def test_lrc_encode_verify(mode, rng):
    enc = make_encoder(mode)
    assert isinstance(enc, LrcEncoder)
    t = enc.t
    stripe = np.zeros((t.total, 32), dtype=np.uint8)
    stripe[: t.n] = rng.integers(0, 256, (t.n, 32))
    enc.encode(stripe)
    assert enc.verify(stripe)
    # each AZ's local stripe verifies standalone
    for az in range(t.az_count):
        assert enc.verify(enc.get_shards_in_idc(stripe, az).copy())


def test_lrc_local_stripe_reconstruct(rng):
    # EC6P10L2 local stripe layout (codemode.go doc): stripe1 is
    # [0,1,2, 6..10, 16] with n=8 local-data, m=1 local-parity.
    enc = make_encoder(cm.CodeMode.EC6P10L2)
    t = enc.t
    stripe = np.zeros((t.total, 32), dtype=np.uint8)
    stripe[: t.n] = rng.integers(0, 256, (t.n, 32))
    enc.encode(stripe)
    idx, ln, lm = t.local_stripe_in_az(0)
    assert idx == [0, 1, 2, 6, 7, 8, 9, 10, 16] and (ln, lm) == (8, 1)
    local = enc.get_shards_in_idc(stripe, 0).copy()
    golden = local.copy()
    local[2] = 0  # lose one shard inside the AZ
    enc.reconstruct(local, [2])
    assert np.array_equal(local, golden)


def test_lrc_full_reconstruct_with_local_parity_loss(rng):
    enc = make_encoder(cm.CodeMode.EC6P3L3)
    t = enc.t
    stripe = np.zeros((t.total, 16), dtype=np.uint8)
    stripe[: t.n] = rng.integers(0, 256, (t.n, 16))
    enc.encode(stripe)
    golden = stripe.copy()
    bad = [0, t.n, t.n + t.m + 1]  # data + global parity + local parity
    stripe[bad] = 0
    enc.reconstruct(stripe, bad)
    assert np.array_equal(stripe, golden)


def test_split_join_roundtrip(rng):
    enc = make_encoder(cm.CodeMode.EC6P6)
    payload = rng.integers(0, 256, 100_000).astype(np.uint8).tobytes()
    stripe = enc.split(payload)
    assert stripe.shape[1] == max(-(-len(payload) // 6), 2048)
    enc.encode(stripe)
    assert enc.join(stripe, len(payload)) == payload


def test_split_min_shard_size():
    enc = make_encoder(cm.CodeMode.EC6P6)  # min shard 2KB
    stripe = enc.split(b"x" * 100)
    assert stripe.shape == (12, 2048)
    enc2 = make_encoder(cm.CodeMode.EC6P6Align0)
    stripe2 = enc2.split(b"x" * 100)
    assert stripe2.shape == (12, -(-100 // 6))


def test_batched_stripes(rng):
    enc = make_encoder(cm.CodeMode.EC12P4)
    t = enc.t
    batch = np.zeros((8, t.total, 64), dtype=np.uint8)
    batch[:, : t.n] = rng.integers(0, 256, (8, t.n, 64))
    enc.encode(batch)
    assert enc.verify(batch)
    golden = batch.copy()
    bad = [3, 14]
    batch[:, bad] = 0
    enc.reconstruct(batch, bad)
    assert np.array_equal(batch, golden)


def test_codemode_quorum_constraint():
    # PutQuorum invariant from Tactic doc: (N+M)/AZ + N <= quorum <= N+M.
    for mode, t in cm.TACTICS.items():
        if t.is_replicate() or t.m == 0:
            continue
        assert t.put_quorum <= t.n + t.m, mode


def test_policy_selection():
    policies = [
        cm.Policy("EC6P6", min_size=0, max_size=1 << 20),
        cm.Policy("EC15P12", min_size=(1 << 20) + 1, max_size=1 << 40),
    ]
    assert cm.select_codemode(policies, 1024) == cm.CodeMode.EC6P6
    assert cm.select_codemode(policies, 100 << 20) == cm.CodeMode.EC15P12


def test_join_rejects_batch(rng):
    enc = make_encoder(cm.CodeMode.EC6P6)
    batch = np.zeros((4, 12, 16), dtype=np.uint8)
    with pytest.raises(ECError):
        enc.join(batch, 10)


def test_non_uint8_rejected():
    enc = make_encoder(cm.CodeMode.EC6P6)
    with pytest.raises(ECError):
        enc.encode(np.zeros((12, 16), dtype=np.int64))


# ---------------- crossover policy + device-loss degradation ----------


def test_policy_refuses_cpu_table_in_tpu_process(tmp_path, monkeypatch):
    """A crossover table measured on a CPU-only host must not be
    trusted by a TPU-attached process: it pins every size class to the
    host engine exactly where the device path wins. The policy loader
    re-measures lazily instead."""
    import json

    from cubefs_tpu.codec import engine as eng

    path = tmp_path / "CROSSOVER.json"
    path.write_text(json.dumps(
        {"table": [[1 << 62, "cpp"]], "platform": "cpu"}))
    monkeypatch.setattr(eng, "_policy_path", lambda: str(path))
    monkeypatch.setattr(eng, "_platform", lambda: "tpu")
    monkeypatch.setattr(eng, "_policy", None)
    remeasured = [[1 << 62, "tpu"]]
    calls = []

    def fake_measure(*a, **kw):
        calls.append(1)
        eng._policy = remeasured
        return remeasured

    monkeypatch.setattr(eng, "measure_crossover", fake_measure)
    assert eng._load_policy() == remeasured
    assert calls == [1]
    # the re-measured table is cached — no repeat measurement
    assert eng._load_policy() == remeasured
    assert calls == [1]

    # same table, tpu-stamped: trusted as-is in a tpu process
    path.write_text(json.dumps(
        {"table": [[1 << 62, "cpp"]], "platform": "tpu"}))
    monkeypatch.setattr(eng, "_policy", None)
    assert eng._load_policy() == [[1 << 62, "cpp"]]
    assert calls == [1]


def test_measure_crossover_stamps_platform(tmp_path, monkeypatch):
    import json

    from cubefs_tpu.codec import engine as eng

    path = tmp_path / "CROSSOVER.json"
    monkeypatch.setattr(eng, "_policy_path", lambda: str(path))
    monkeypatch.setattr(eng, "_policy", None)
    table = eng.measure_crossover(sizes=(4096,), repeats=1)
    saved = json.loads(path.read_text())
    assert saved["table"] == table
    assert saved["platform"] == eng._platform()


def test_autoengine_degrades_on_device_loss(monkeypatch, rng):
    """Device loss mid-call: the auto engine falls down the
    pallas→jax→cpp→numpy chain, quarantines the dead engine, and the
    answer stays bit-identical to the host golden."""
    from cubefs_tpu.codec import engine as eng

    class DyingEngine:
        name = "tpu"

        def matrix_apply(self, coeff, shards):
            raise RuntimeError("DEVICE_LOST: accelerator went away")

        def encode_parity(self, data, n_parity):
            raise RuntimeError("DEVICE_LOST: accelerator went away")

    monkeypatch.setattr(eng, "_dead_engines", set())
    monkeypatch.setattr(eng, "_instances", {"tpu": DyingEngine()})
    monkeypatch.setattr(eng, "_policy", [[1 << 62, "tpu"]])
    auto = eng.AutoEngine()
    data = rng.integers(0, 256, (6, 64)).astype(np.uint8)
    parity = auto.encode_parity(data, 3)
    assert np.array_equal(parity, eng.NumpyEngine().encode_parity(data, 3))
    # the dead engine is quarantined: the router skips it from now on
    assert "tpu" in eng._dead_engines
    assert eng.engine_for(64).name != "tpu"
    # a semantic error must NOT trigger fallback/quarantine
    monkeypatch.setattr(eng, "_dead_engines", set())
    with pytest.raises(ValueError):
        eng._call_with_fallback(
            "cpp" if "cpp" in eng._REGISTRY else "numpy", "matrix_apply",
            np.zeros((3, 9), dtype=np.uint8), data)
    assert not eng._dead_engines


def test_fallback_lands_on_numpy_when_cpp_unavailable(monkeypatch, rng):
    """A host without the native .so (or with a broken one) degrades
    cpp -> numpy-xor, the host leg below it; the healthy engines are
    NOT quarantined along the way — only the engine that actually
    failed is."""
    from cubefs_tpu.codec import engine as eng

    class BrokenNative:
        def __init__(self, name):
            self.name = name

        def encode_parity(self, data, n_parity):
            raise OSError("libgfcpu.so: cannot open shared object file")

        def matrix_apply(self, coeff, shards):
            raise OSError("libgfcpu.so: cannot open shared object file")

    monkeypatch.setattr(eng, "_dead_engines", set())
    monkeypatch.setattr(eng, "_instances", {"cpp": BrokenNative("cpp")})
    data = rng.integers(0, 256, (6, 64)).astype(np.uint8)
    parity, served = eng._dispatch("cpp", "encode_parity", data, 3)
    assert served == "numpy-xor"
    assert np.array_equal(parity, eng.NumpyEngine().encode_parity(data, 3))
    # the broken native leg quarantined; tpu/numpy stay in rotation
    assert eng._dead_engines == {"cpp"}
    # the router now routes around the dead native engine too
    monkeypatch.setattr(eng, "_policy", [[1 << 62, "cpp"]])
    assert eng.engine_for(64).name in ("tpu", "numpy", "numpy-xor")


def test_crossover_policy_routes_by_size(monkeypatch, rng):
    """engine_for honors the measured table's size classes exactly at
    the boundary, and 'auto' dispatch through it stays bit-identical
    to the host engine."""
    from cubefs_tpu.codec import engine as eng

    monkeypatch.setattr(eng, "_dead_engines", set())
    # a table's leg is served as the table names it: no alias
    monkeypatch.setattr(eng, "_policy", [[1 << 62, "numpy"]])
    assert eng.engine_for(64).name == "numpy"
    monkeypatch.setattr(eng, "_policy",
                        [[1024, "numpy-xor"], [1 << 62, "tpu"]])
    assert eng.engine_for(1024).name == "numpy-xor"  # inclusive bound
    assert eng.engine_for(1025).name == "tpu"
    auto = eng.AutoEngine()
    small = rng.integers(0, 256, (4, 64)).astype(np.uint8)   # 256 B
    big = rng.integers(0, 256, (4, 2048)).astype(np.uint8)   # 8 KiB
    golden = eng.NumpyEngine()
    assert np.array_equal(auto.encode_parity(small, 2),
                          golden.encode_parity(small, 2))
    assert np.array_equal(auto.encode_parity(big, 2),
                          golden.encode_parity(big, 2))


def test_chaos_drill_full_fallback_chain(monkeypatch):
    """Seeded device-loss drill: with the device and the native leg
    declared transiently dead (CUBEFS_CODEC_DEAD), a tpu-requested
    decode walks the tpu→cpp→numpy-xor chain and lands on the compiled
    XOR leg — byte-identical, reproducible schedule digest, and NO
    permanent quarantine (a drill is not an engine failure)."""
    from cubefs_tpu.codec import engine as eng
    from cubefs_tpu.ops import gf256, xorprog

    rng = np.random.default_rng(0xD12)
    t = cm.tactic("EC6P6MSR")
    k, total, d = t.n, t.n + t.m, t.d
    from cubefs_tpu.ops import msr
    helpers = tuple(h for h in range(total) if h != 0)[:d]
    rows = msr.repair_rows(k, total, d, 0, helpers)
    recv = rng.integers(0, 256, (d, 3 * 64), dtype=np.uint8)
    gold = gf256.gf_matmul(rows, recv)

    monkeypatch.setattr(eng, "_dead_engines", set())
    monkeypatch.setenv("CUBEFS_CODEC_DEAD", "tpu, cpp")

    out, served = eng._dispatch("tpu", "matrix_apply", rows, recv)
    assert served == "numpy-xor"
    assert np.array_equal(out, gold)
    digest1 = xorprog.program_for(rows).schedule_digest

    again, served = eng._dispatch("tpu", "matrix_apply", rows, recv)
    assert served == "numpy-xor" and np.array_equal(again, out)
    digest2 = xorprog.program_for(rows).schedule_digest
    assert digest1 == digest2  # the drill replays ONE schedule
    assert eng._dead_engines == set()  # transient death ≠ quarantine


@pytest.mark.parametrize("gone", ["cpp-xor", "tpu-pallas"])
def test_the_registry_is_the_four_legs_and_auto(gone):
    from cubefs_tpu.codec import engine as eng

    assert sorted(eng._REGISTRY) == [
        "auto", "cpp", "numpy", "numpy-xor", "tpu"]
    assert set(eng._FALLBACK_CHAIN) == set(eng._REGISTRY) - {"auto"}
    with pytest.raises(KeyError, match="unknown ec engine"):
        eng.get_engine(gone)


@pytest.mark.parametrize("op", ["encode", "apply"])
def test_chain_drilled_dead_one_leg_at_a_time(op, rng, monkeypatch):
    """The one fallback order, leg by leg: with the legs above it
    drilled dead, a step pinned to `tpu` is served by — and stamped
    with — the next one down, bit-identical to the table reference."""
    from cubefs_tpu.codec import engine as eng
    from cubefs_tpu.codec.batcher import BatchCodec
    from cubefs_tpu.ops import gf256
    from cubefs_tpu.utils import metrics

    monkeypatch.setattr(eng, "_dead_engines", set())
    eng.get_engine("cpp")  # the native leg is built here
    chain = ("tpu", "cpp", "numpy-xor", "numpy")
    assert eng._FALLBACK_CHAIN == chain
    bc = BatchCodec()
    x = rng.integers(0, 256, (1, 6, 512), dtype=np.uint8)
    rows = gf256.decode_matrix(6, 9, [0, 2, 3, 5, 6, 8])[:2]
    want = (eng.NumpyEngine().encode_parity(x, 3) if op == "encode"
            else eng.NumpyEngine().matrix_apply(rows, x))
    for k, leg in enumerate(chain):
        monkeypatch.setenv("CUBEFS_CODEC_DEAD", ",".join(chain[:k]))
        before = metrics.codec_batch_steps.value(op=op, engine=leg)
        got = (bc.submit_encode("tpu", x, 3) if op == "encode"
               else bc.submit_apply("tpu", rows, x))
        assert np.array_equal(got, want), leg
        assert metrics.codec_batch_steps.value(
            op=op, engine=leg) == before + 1, leg
    assert eng._dead_engines == set()
    monkeypatch.setenv("CUBEFS_CODEC_DEAD", ",".join(chain))
    with pytest.raises(RuntimeError, match="no fallback left"):
        bc.submit_apply("tpu", rows, x)


def test_stale_policy_is_logged_not_silently_kept(tmp_path, monkeypatch,
                                                  caplog):
    """A policy file whose platform stamp mismatches the running
    process must be LOGGED as stale and re-measured — never silently
    trusted (satellite: the refusal now covers every mismatch
    direction, not just cpu-table-in-tpu-process)."""
    import json
    import logging

    from cubefs_tpu.codec import engine as eng

    path = tmp_path / "CROSSOVER.json"
    path.write_text(json.dumps(
        {"table": [[1 << 62, "tpu"]], "platform": "tpu"}))
    monkeypatch.setattr(eng, "_policy_path", lambda: str(path))
    monkeypatch.setattr(eng, "_platform", lambda: "cpu")
    monkeypatch.setattr(eng, "_policy", None)
    remeasured = [[1 << 62, "numpy-xor"]]

    def fake_measure(*a, **kw):
        eng._policy = remeasured
        return remeasured

    monkeypatch.setattr(eng, "measure_crossover", fake_measure)
    with caplog.at_level(logging.WARNING, logger="cubefs.codec"):
        assert eng._load_policy() == remeasured
    assert any("stale crossover policy" in r.message for r in caplog.records)


def test_measure_crossover_times_xor_legs(tmp_path, monkeypatch):
    """The refreshed sweep must time the compiled-XOR host legs as
    first-class candidates and persist per-size timings, so the saved
    policy documents WHY each size class routes where it does."""
    import json

    from cubefs_tpu.codec import engine as eng

    path = tmp_path / "CROSSOVER.json"
    monkeypatch.setattr(eng, "_policy_path", lambda: str(path))
    monkeypatch.setattr(eng, "_policy", None)
    eng.measure_crossover(sizes=(4096,), repeats=1)
    saved = json.loads(path.read_text())
    timed = set(saved["timings_s"]["4096"])
    assert "numpy-xor" in timed
    assert "device_crossover_bytes" in saved


def test_lrc_local_reconstruct_edge_cases(rng):
    enc = make_encoder(cm.CodeMode.EC6P10L2)
    t = enc.t
    stripe = np.zeros((t.total, 16), dtype=np.uint8)
    stripe[: t.n] = rng.integers(0, 256, (t.n, 16))
    enc.encode(stripe)
    local = enc.get_shards_in_idc(stripe, 0).copy()
    golden = local.copy()
    assert np.array_equal(enc.reconstruct(local, []), golden)  # no-op
    with pytest.raises(ECError):
        enc.reconstruct(local.copy(), [0, 1])  # > local parity budget


# ---------------- the engine call from inside (PR 26) ----------------

PHASES = ("matrix", "h2d", "launch", "wait", "d2h")


def _phase_counts(engine):
    from cubefs_tpu.utils import metrics

    return {(k[1], k[2]): s["count"]
            for k, s in metrics.codec_engine_phase.samples()
            if k[0] == engine}


@pytest.fixture
def every_call_phased(monkeypatch):
    """An engine takes apart at most one call in PHASE_EVERY_S seconds;
    these tests count samples, so every call."""
    from cubefs_tpu.codec import engine

    monkeypatch.setattr(engine, "PHASE_EVERY_S", 0.0)
    monkeypatch.setattr(get_engine("tpu"), "_phase_due", 0.0, raising=False)


def _grew(before, after):
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


def _device_engine_call(op, rng):
    from cubefs_tpu.ops import gf256

    eng = get_engine("tpu")
    data = rng.integers(0, 256, (3, 6, 4096), dtype=np.uint8)
    if op == "encode":
        return (eng.encode_parity(data, 3),
                get_engine("numpy").encode_parity(data, 3))
    rows = gf256.decode_matrix(6, 9, [0, 2, 3, 5, 6, 8])[:2]
    return (eng.matrix_apply(rows, data),
            get_engine("numpy").matrix_apply(rows, data))


@pytest.mark.parametrize("op", ["encode", "apply"])
def test_device_engine_call_observes_its_five_phases_once(
        program, op, rng, monkeypatch, every_call_phased):
    """Through either program plan hands the one device engine."""
    monkeypatch.delenv("CUBEFS_TRACE", raising=False)
    before = _phase_counts("tpu")
    got, want = _device_engine_call(op, rng)
    assert np.array_equal(got, want)  # bit-identical to the table path
    assert _grew(before, _phase_counts("tpu")) == {
        (op, p): 1 for p in PHASES}


def test_trace_door_off_is_the_bare_engine_call(program, rng, monkeypatch,
                                                every_call_phased):
    """CUBEFS_TRACE=0: no phase sample, no extra wait — the same bytes."""
    got_on, want = _device_engine_call("encode", rng)
    monkeypatch.setenv("CUBEFS_TRACE", "0")
    before = _phase_counts("tpu")
    got_off, _ = _device_engine_call(
        "encode", np.random.default_rng(0xC0DEC))
    assert _phase_counts("tpu") == before
    assert np.array_equal(got_off, got_on) and np.array_equal(got_off, want)


def test_coalesced_step_observes_one_gather_and_names_its_engine(
        rng, every_call_phased):
    """A step of several submissions observes ONE `gather` (the
    concatenate) beside its engine phases, and its `codec_step` span is
    tagged with the engine that served it."""
    from cubefs_tpu.codec.batcher import BatchCodec
    from cubefs_tpu.utils import trace as tracelib

    bc = BatchCodec()
    datas = [rng.integers(0, 256, (1, 6, 2048), dtype=np.uint8)
             for _ in range(3)]
    bc.submit_encode("tpu", datas[0], 3)  # compiles the 1-stripe program
    before = _phase_counts("tpu")
    tracelib.reset_collector()
    with tracelib.path_span("blob.put", "test.put"):
        futs = [bc.submit_encode_async("tpu", d, 3) for d in datas]
        outs = [f.result() for f in futs]  # first collector drains all 3
    for d, out in zip(datas, outs):
        assert np.array_equal(out, get_engine("numpy").encode_parity(d, 3))
    assert _grew(before, _phase_counts("tpu")) == {
        ("encode", p): 1 for p in PHASES + ("gather",)}
    steps = [s for s in tracelib.finished_spans()
             if s["op"] == "stage:codec_step"]
    assert len(steps) == 1
    assert steps[0]["tags"]["engine"] == "tpu"
    assert steps[0]["tags"]["stripes"] == 3


def test_an_engine_takes_apart_one_call_in_an_interval(rng, monkeypatch):
    """What a phased call costs is bounded per second, not per step: the
    next call inside PHASE_EVERY_S is the bare call, with the same bytes."""
    from cubefs_tpu.codec import engine

    eng = get_engine("tpu")
    monkeypatch.setattr(engine, "PHASE_EVERY_S", 3600.0)
    monkeypatch.setattr(eng, "_phase_due", 0.0, raising=False)
    data = rng.integers(0, 256, (2, 6, 1024), dtype=np.uint8)
    before = _phase_counts("tpu")
    first = eng.encode_parity(data, 3)
    assert _grew(before, _phase_counts("tpu")) == {
        ("encode", p): 1 for p in PHASES}
    second = eng.encode_parity(data, 3)
    assert _grew(before, _phase_counts("tpu")) == {
        ("encode", p): 1 for p in PHASES}
    assert np.array_equal(first, second)


@pytest.mark.parametrize("path,scopes", [
    ("fused", ("gf256.pad", "gf256.relayout", "gf256.unpad",
               "gf256_apply")),
    ("jnp", ("gf256.bits.unpack", "gf256.bits.dot", "gf256.bits.pack")),
])
def test_device_work_carries_the_names_the_program_chose(path, scopes, rng):
    """The lowered text of both kernels names its parts: what a device
    trace shows for an op is the scope it was traced under."""
    import jax

    from cubefs_tpu.ops import gf256, pallas_gf, rs_kernel

    coeff = np.ascontiguousarray(gf256.parity_matrix(6, 3), dtype=np.uint8)
    # two leading axes and S=1000 under a tile of 512: the fused path
    # pads, flattens the lead, runs the kernel and slices back
    x = rng.integers(0, 256, (2, 2, 6, 1000), dtype=np.uint8)
    if path == "fused":
        fn = jax.jit(lambda a: pallas_gf.gf_matrix_apply_pallas(
            coeff, a, tile=512, interpret=True))
    else:
        w = rs_kernel.device_bits(coeff, False)
        program = rs_kernel._bits_fn(3, 6, x.shape)
        fn = jax.jit(lambda a: program(w, a))
    text = fn.lower(x).as_text(debug_info=True)
    for scope in scopes:
        assert scope in text, scope
    assert np.array_equal(np.asarray(fn(x)),
                          get_engine("numpy").matrix_apply(coeff, x))


def test_cli_codec_view_reads_decode_legs_from_the_step_counter(rng):
    """`cubefs-cli metrics codec` names the legs that served decode
    steps from cubefs_codec_batch_steps_total{op="apply",engine} — the
    count the batcher stamps after dispatch, not a process-wide dict."""
    from cubefs_tpu import cli
    from cubefs_tpu.codec.batcher import BatchCodec
    from cubefs_tpu.ops import gf256
    from cubefs_tpu.utils import metrics

    def legs():
        return cli._codec_view(metrics.DEFAULT.render_text()).get(
            "repair_decode_by_leg", {})

    before = legs()
    rows = gf256.decode_matrix(6, 9, [0, 2, 3, 5, 6, 8])[:1]
    bc = BatchCodec()
    x = rng.integers(0, 256, (2, 6, 512), dtype=np.uint8)
    bc.submit_apply("numpy", rows, x)  # the reference, served as named
    bc.submit_apply("numpy-xor", rows, x)
    assert _grew(before, legs()) == {"numpy": 1, "numpy-xor": 1}
