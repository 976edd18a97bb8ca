"""Tier-1 hook + unit tests for the cubefs-tpu lint suite (tool/lint).

Each checker family gets at least one true-positive test (the known-bad
fixture fires exactly the expected codes) and one true-negative test
(the known-good fixture is silent). Fixtures live in
tests/fixtures/lint/ — a directory `iter_py_files` skips, so the
intentional violations in them never leak into a real lint run.

`test_tree_is_lint_clean` is the tier-1 gate: the repo must lint clean
under the shipped baseline, and the baseline must not carry stale
fingerprints for findings that no longer exist.
"""

import os
import subprocess
import sys

import pytest

from tool.lint import cli, core
from tool.lint import graph as graphlib
from tool.lint.checkers.admission_discipline import AdmissionDisciplineChecker
from tool.lint.checkers.batch_discipline import (BatchDisciplineChecker,
                                                 XorProgFenceChecker)
from tool.lint.checkers.fanout_discipline import FanoutDisciplineChecker
from tool.lint.checkers.fs_placement import FsPlacementChecker
from tool.lint.checkers.fsm_purity import FsmPurityChecker, apply_roots
from tool.lint.checkers.geo_discipline import GeoDisciplineChecker
from tool.lint.checkers.integrity_discipline import (
    IntegrityDisciplineChecker)
from tool.lint.checkers.lock_discipline import LockDisciplineChecker
from tool.lint.checkers.lock_graph import LockGraphChecker
from tool.lint.checkers.placement_discipline import PlacementDisciplineChecker
from tool.lint.checkers.retry_discipline import RetryDisciplineChecker
from tool.lint.checkers.rpc_idempotency import (RpcIdempotencyChecker,
                                                is_mutating)
from tool.lint.checkers.split_discipline import SplitDisciplineChecker
from tool.lint.checkers.tier1_purity import Tier1PurityChecker
from tool.lint.checkers.tiering_discipline import TieringDisciplineChecker
from tool.lint.checkers.tracer_safety import (TraceClockChecker,
                                              TracerSafetyChecker)
from tool.lint.checkers.wire_discipline import WireDisciplineChecker
from tool.lint.checkers.witness_discipline import WitnessDisciplineChecker

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "lint")


def _module(fixture: str, relpath: str) -> core.Module:
    """Parse a fixture under a relpath that puts it in a checker's dirs."""
    with open(os.path.join(FIXTURES, fixture), encoding="utf-8") as f:
        return core.Module(relpath, f.read())


def _codes(violations):
    return sorted(v.code for v in violations)


# ---------------- tracer-safety ----------------

def test_tracer_safety_true_positives():
    mod = _module("tracer_bad.py", "cubefs_tpu/ops/fx.py")
    found = TracerSafetyChecker().check(mod)
    assert _codes(found) == ["CFT001", "CFT002", "CFT003", "CFT004",
                             "CFT005"]


def test_tracer_safety_true_negative():
    mod = _module("tracer_good.py", "cubefs_tpu/ops/fx.py")
    assert TracerSafetyChecker().check(mod) == []


def test_tracer_safety_scoped_to_accel_dirs():
    c = TracerSafetyChecker()
    assert c.applies("cubefs_tpu/ops/pallas_gf.py")
    assert not c.applies("cubefs_tpu/fs/master.py")


# ---------------- trace-clock (CFT006) ----------------

def test_trace_clock_true_positives():
    mod = _module("trace_clock_bad.py", "cubefs_tpu/utils/trace.py")
    found = TraceClockChecker().check(mod)
    assert _codes(found) == ["CFT006", "CFT006", "CFT006"]


def test_trace_clock_true_negative():
    mod = _module("trace_clock_good.py", "cubefs_tpu/utils/trace.py")
    assert TraceClockChecker().check(mod) == []


def test_trace_clock_scoped_to_instrumented_modules():
    c = TraceClockChecker()
    assert c.applies("cubefs_tpu/utils/trace.py")
    assert c.applies("cubefs_tpu/blob/access.py")
    # wall-clock ts fields (mtime/ctime) are legitimate in the meta layer
    assert not c.applies("cubefs_tpu/fs/metanode.py")
    assert not c.applies("cubefs_tpu/fs/client.py")


# ---------------- lock-discipline ----------------

def test_lock_discipline_true_positives():
    mod = _module("lock_bad.py", "cubefs_tpu/fs/fx.py")
    found = LockDisciplineChecker().check(mod)
    assert _codes(found) == ["CFL001", "CFL002", "CFL002", "CFL002",
                             "CFL003"]


def test_lock_discipline_true_negative():
    mod = _module("lock_good.py", "cubefs_tpu/fs/fx.py")
    assert LockDisciplineChecker().check(mod) == []


# ---------------- rpc-idempotency ----------------

def test_rpc_idempotency_true_positives():
    mod = _module("rpc_bad.py", "cubefs_tpu/fs/fx.py")
    found = RpcIdempotencyChecker().check(mod)
    assert _codes(found) == ["CFR001", "CFR001"]


def test_rpc_idempotency_true_negative():
    mod = _module("rpc_good.py", "cubefs_tpu/fs/fx.py")
    assert RpcIdempotencyChecker().check(mod) == []


def test_rpc_empty_justification_is_cfr002(monkeypatch):
    from tool.lint import rpc_allowlist
    monkeypatch.setitem(rpc_allowlist.ALLOWLIST, ("*", "truncate"), "  ")
    mod = _module("rpc_bad.py", "cubefs_tpu/fs/fx.py")
    found = RpcIdempotencyChecker().check(mod)
    # the truncate site degrades CFR001 -> CFR002; alloc_bids stays CFR001
    assert _codes(found) == ["CFR001", "CFR002"]


def test_rpc_allowlist_justifications_nonempty():
    from tool.lint.rpc_allowlist import ALLOWLIST
    for key, why in ALLOWLIST.items():
        assert str(why).strip(), f"empty justification for {key}"


def test_mutating_classifier():
    assert is_mutating("alloc_bids")
    assert is_mutating("set_quota")
    assert is_mutating("submit")
    assert not is_mutating("heartbeat")
    assert not is_mutating("vol_view")


# ---------------- tier1-purity ----------------

def test_tier1_purity_true_positives():
    mod = _module("tier1_bad.py", "tests/test_fx.py")
    found = Tier1PurityChecker().check(mod)
    assert _codes(found) == ["CFP001", "CFP002", "CFP002", "CFP003",
                             "CFP003"]


def test_tier1_purity_true_negative():
    mod = _module("tier1_good.py", "tests/test_fx.py")
    assert Tier1PurityChecker().check(mod) == []


def test_tier1_purity_slow_modules_exempt():
    mod = _module("tier1_slow_exempt.py", "tests/test_fx.py")
    assert Tier1PurityChecker().check(mod) == []


# ---------------- retry-discipline ----------------

def test_retry_discipline_true_positives():
    mod = _module("retry_bad.py", "cubefs_tpu/fs/fx.py")
    found = RetryDisciplineChecker().check(mod)
    assert _codes(found) == ["CFB001", "CFB002"]


def test_retry_discipline_true_negative():
    mod = _module("retry_good.py", "cubefs_tpu/fs/fx.py")
    assert RetryDisciplineChecker().check(mod) == []


def test_retry_discipline_exempts_retry_module_itself():
    c = RetryDisciplineChecker()
    assert c.applies("cubefs_tpu/fs/datanode.py")
    assert not c.applies("cubefs_tpu/utils/retry.py")
    assert not c.applies("tool/bench.py")


# ---------------- placement-discipline ----------------

def test_placement_discipline_true_positives():
    mod = _module("placement_bad.py", "cubefs_tpu/blob/fx.py")
    found = PlacementDisciplineChecker().check(mod)
    assert _codes(found) == ["CFZ001", "CFZ001"]


def test_placement_discipline_true_negative():
    mod = _module("placement_good.py", "cubefs_tpu/blob/fx.py")
    assert PlacementDisciplineChecker().check(mod) == []


def test_placement_discipline_exempts_topology_itself():
    c = PlacementDisciplineChecker()
    assert c.applies("cubefs_tpu/blob/scheduler.py")
    assert not c.applies("cubefs_tpu/blob/topology.py")
    assert not c.applies("cubefs_tpu/fs/master.py")


# ---------------- fs-placement ----------------

def test_fs_placement_true_positives():
    mod = _module("fsplace_bad.py", "cubefs_tpu/fs/fx.py")
    found = FsPlacementChecker().check(mod)
    assert _codes(found) == ["CFZ002", "CFZ002", "CFZ002", "CFZ002",
                             "CFZ003", "CFZ003"]


def test_fs_placement_true_negative():
    mod = _module("fsplace_good.py", "cubefs_tpu/fs/fx.py")
    assert FsPlacementChecker().check(mod) == []


def test_fs_placement_load_sorts_scoped_to_fs_plane():
    # the SAME bad source outside cubefs_tpu/fs/ keeps only the
    # cache_put fence (blob load-sorts are CFZ001's job)
    mod = _module("fsplace_bad.py", "cubefs_tpu/blob/fx.py")
    assert _codes(FsPlacementChecker().check(mod)) == ["CFZ003", "CFZ003"]


def test_fs_placement_remotecache_is_sanctioned():
    # ...and inside remotecache.py the population fence is silent
    # (load-sorts still fire: topology.py is the only sort exemption)
    mod = _module("fsplace_bad.py", "cubefs_tpu/fs/remotecache.py")
    assert _codes(FsPlacementChecker().check(mod)) == [
        "CFZ002", "CFZ002", "CFZ002", "CFZ002"]


def test_fs_placement_scope():
    c = FsPlacementChecker()
    assert c.applies("cubefs_tpu/fs/master.py")
    assert c.applies("cubefs_tpu/fs/topology.py")  # CFZ003 still applies
    assert not c.applies("tool/lint/cli.py")
    assert not c.applies("tests/test_fs_e2e.py")


# ---------------- batch-discipline ----------------

def test_batch_discipline_true_positives():
    mod = _module("batch_bad.py", "cubefs_tpu/blob/fx.py")
    found = BatchDisciplineChecker().check(mod)
    assert _codes(found) == ["CFC001", "CFC001", "CFC002", "CFC002"]


def test_batch_discipline_true_negative():
    mod = _module("batch_good.py", "cubefs_tpu/blob/fx.py")
    assert BatchDisciplineChecker().check(mod) == []


def test_batch_discipline_cfc003_true_positives():
    mod = _module("subshard_bad.py", "cubefs_tpu/blob/fx.py")
    found = BatchDisciplineChecker().check(mod)
    assert _codes(found) == ["CFC003", "CFC003", "CFC003"]


def test_batch_discipline_cfc003_true_negative():
    mod = _module("subshard_good.py", "cubefs_tpu/blob/fx.py")
    assert BatchDisciplineChecker().check(mod) == []


def test_batch_discipline_cfc003_worker_is_sanctioned():
    # the SAME bad source is clean when it IS the repair worker
    mod = _module("subshard_bad.py", "cubefs_tpu/blob/worker.py")
    assert BatchDisciplineChecker().check(mod) == []


def test_batch_discipline_scoped_to_blob_plane():
    c = BatchDisciplineChecker()
    assert c.applies("cubefs_tpu/blob/worker.py")
    # the codec package itself holds raw engines by design
    assert not c.applies("cubefs_tpu/codec/batcher.py")
    assert not c.applies("cubefs_tpu/fs/master.py")


def test_xorprog_fence_true_positives():
    mod = _module("xorprog_bad.py", "cubefs_tpu/codec/fx.py")
    found = XorProgFenceChecker().check(mod)
    assert _codes(found) == ["CFC004", "CFC004", "CFC004", "CFC004"]


def test_xorprog_fence_true_negative():
    mod = _module("xorprog_good.py", "cubefs_tpu/codec/fx.py")
    assert XorProgFenceChecker().check(mod) == []


def test_xorprog_fence_scope():
    c = XorProgFenceChecker()
    # both the blob plane and the codec package are fenced...
    assert c.applies("cubefs_tpu/blob/worker.py")
    assert c.applies("cubefs_tpu/codec/engine.py")
    # ...but the ops plane is not: xorprog.py IS the fenced module, and
    # rs_kernel.py expands bitmatrices for the device path by design
    assert not c.applies("cubefs_tpu/ops/xorprog.py")
    assert not c.applies("cubefs_tpu/ops/rs_kernel.py")


# ---------------- suppressions ----------------

def test_ops_layer_imports_nothing_from_the_codec_layer():
    """cubefs_tpu/ops is below cubefs_tpu/codec: the kernels and their
    dispatch know nothing of the engines that call them."""
    import ast

    pkg = ["cubefs_tpu", "ops"]
    ops = os.path.join(core.REPO_ROOT, *pkg)
    for name in sorted(os.listdir(ops)):
        if not name.endswith(".py"):
            continue
        with open(os.path.join(ops, name)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                seen = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                base = pkg[:len(pkg) - node.level + 1] if node.level else []
                mod = ".".join(base + ([node.module] if node.module else []))
                seen = [mod] + [f"{mod}.{a.name}" for a in node.names]
            else:
                continue
            assert not any(m == "cubefs_tpu.codec"
                           or m.startswith("cubefs_tpu.codec.")
                           for m in seen), (name, node.lineno, seen)


def test_bare_allow_is_cfa001_and_does_not_suppress():
    mod = _module("allow_bare.py", "cubefs_tpu/fs/fx.py")
    lock = LockDisciplineChecker().check(mod)
    assert _codes(lock) == ["CFL001"]
    assert not mod.suppressed(lock[0])          # bare allow is inert
    assert _codes(core.bare_allow_violations(mod)) == ["CFA001"]


def test_justified_allow_suppresses():
    mod = _module("allow_ok.py", "cubefs_tpu/fs/fx.py")
    lock = LockDisciplineChecker().check(mod)
    assert _codes(lock) == ["CFL001"]
    assert mod.suppressed(lock[0])              # comment on line above
    assert core.bare_allow_violations(mod) == []


# ---------------- baseline mechanics ----------------

def test_baseline_roundtrip_is_a_multiset(tmp_path):
    v = core.Violation("CFL001", "lock-discipline", "a.py", 3, "m")
    w = core.Violation("CFL001", "lock-discipline", "a.py", 3, "m2")
    path = str(tmp_path / "baseline.json")
    core.save_baseline([v, w], path)
    baseline = core.load_baseline(path)
    assert baseline == {"CFL001:a.py:3": 2}
    # two identical fingerprints absorbed, a third is fresh
    fresh = core.apply_baseline([v, w, v], baseline)
    assert len(fresh) == 1


# ---------------- tier-1 gate: the tree itself ----------------

def test_tree_is_lint_clean():
    """The repo lints clean AND the shipped baseline has no stale
    entries — regenerate with `python -m tool.lint --update-baseline`
    after intentionally accepting a finding."""
    violations, errors = cli.run_lint()
    assert errors == [], f"unparseable files: {errors}"
    baseline = core.load_baseline()
    fresh = core.apply_baseline(violations, baseline)
    assert fresh == [], "new lint findings:\n" + "\n".join(
        v.render() for v in fresh)
    current: dict[str, int] = {}
    for v in violations:
        current[v.fingerprint] = current.get(v.fingerprint, 0) + 1
    stale = {fp: n for fp, n in baseline.items()
             if current.get(fp, 0) < n}
    assert not stale, f"baseline entries no longer in the tree: {stale}"


def test_cli_entrypoint_exits_clean():
    rc = subprocess.run(
        [sys.executable, "-m", "tool.lint", "-q"],
        cwd=core.REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert rc.returncode == 0, rc.stdout + rc.stderr


# ---------------- admission-discipline ----------------

def test_admission_discipline_true_positives_s3():
    # do_DELETE bypasses _begin/_admit_qos; _helper is a second admit
    mod = _module("admission_bad.py", "cubefs_tpu/fs/objectnode.py")
    found = AdmissionDisciplineChecker().check(mod)
    assert _codes(found) == ["CFQ001", "CFQ002"]
    assert "do_DELETE" in found[0].message


def test_admission_discipline_true_positives_access():
    # the SAME source under the access front door: rpc_put bypasses
    # the admitted public methods; do_DELETE is not a handler here
    mod = _module("admission_bad.py", "cubefs_tpu/blob/access.py")
    found = AdmissionDisciplineChecker().check(mod)
    assert _codes(found) == ["CFQ001", "CFQ002"]
    assert any("rpc_put" in v.message for v in found)


def test_admission_discipline_true_negative_both_doors():
    for relpath in ("cubefs_tpu/fs/objectnode.py",
                    "cubefs_tpu/blob/access.py"):
        mod = _module("admission_good.py", relpath)
        assert AdmissionDisciplineChecker().check(mod) == []


def test_admission_discipline_scoped_to_front_doors():
    c = AdmissionDisciplineChecker()
    assert c.applies("cubefs_tpu/fs/objectnode.py")
    assert c.applies("cubefs_tpu/blob/access.py")
    # internal services are not client-facing front doors
    assert not c.applies("cubefs_tpu/fs/master.py")
    assert not c.applies("cubefs_tpu/blob/worker.py")


# ---------------- fanout-discipline ----------------

def test_fanout_discipline_true_positives():
    mod = _module("fanout_bad.py", "cubefs_tpu/fs/fx.py")
    found = FanoutDisciplineChecker().check(mod)
    assert _codes(found) == ["CFW001", "CFW001", "CFW002", "CFW002"]


def test_fanout_discipline_true_negative():
    mod = _module("fanout_good.py", "cubefs_tpu/fs/fx.py")
    assert FanoutDisciplineChecker().check(mod) == []


def test_fanout_discipline_scope():
    c = FanoutDisciplineChecker()
    assert c.applies("cubefs_tpu/fs/metanode.py")
    assert c.applies("cubefs_tpu/fs/client.py")
    # data plane replication has its own door, not the meta coalescer
    assert not c.applies("cubefs_tpu/fs/datanode.py")
    assert not c.applies("cubefs_tpu/blob/worker.py")


# ---------------- tiering-discipline ----------------

def test_tiering_discipline_true_positives():
    mod = _module("tiering_bad.py", "cubefs_tpu/fs/lcnode.py")
    found = TieringDisciplineChecker().check(mod)
    assert _codes(found) == ["CFD001", "CFD001", "CFD001",
                             "CFD002", "CFD002", "CFD002"]
    assert any("blob_access.get" in v.message for v in found)


def test_tiering_discipline_true_negative():
    mod = _module("tiering_good.py", "cubefs_tpu/fs/lcnode.py")
    assert TieringDisciplineChecker().check(mod) == []


def test_tiering_discipline_sanctions_only_the_bridge():
    c = TieringDisciplineChecker()
    assert c.applies("cubefs_tpu/fs/client.py")
    assert c.applies("cubefs_tpu/fs/tiering.py")
    # ...but the bridge module itself is exempt from its own rule
    mod = _module("tiering_bad.py", "cubefs_tpu/fs/tiering.py")
    assert c.check(mod) == []
    # the blob plane talking to itself is out of scope
    assert not c.applies("cubefs_tpu/blob/worker.py")


# ---------------- integrity-discipline ----------------

def test_integrity_discipline_true_positives():
    mod = _module("integrity_bad.py", "cubefs_tpu/blob/blobnode.py")
    found = IntegrityDisciplineChecker().check(mod)
    assert _codes(found) == ["CFI001", "CFI001", "CFI002"]
    assert any("verified_get_shard" in v.message for v in found)
    assert any("verified_read" in v.message for v in found)


def test_integrity_discipline_true_negative():
    mod = _module("integrity_good.py", "cubefs_tpu/blob/blobnode.py")
    assert IntegrityDisciplineChecker().check(mod) == []


def test_integrity_discipline_sanctions_the_store_modules():
    c = IntegrityDisciplineChecker()
    assert c.applies("cubefs_tpu/fs/datanode.py")
    assert c.applies("cubefs_tpu/blob/blobnode.py")
    # the store modules' own raw reads sit under the CRC checks
    for sanctioned in ("cubefs_tpu/fs/extent_store.py",
                      "cubefs_tpu/blob/chunkstore.py"):
        mod = _module("integrity_bad.py", sanctioned)
        assert c.check(mod) == []
    # outside the two planes the rule has no opinion
    assert not c.applies("cubefs_tpu/utils/fsm.py")
    assert not c.applies("tests/test_fx.py")


# ---------------- lock-graph (interprocedural, CFL1xx) ----------------

def _graph(*pairs):
    """Build a linked ProjectGraph from (fixture, relpath) pairs."""
    modules = {rp: _module(fx, rp) for fx, rp in pairs}
    g = graphlib.ProjectGraph.build(modules, cache_dir=None, parallel=False)
    return g, modules


def test_lock_graph_transitive_blocking_fires():
    g, mods = _graph(("graph_trans_bad.py", "cubefs_tpu/fs/fx.py"))
    found = LockGraphChecker().check_project(g, mods)
    assert _codes(found) == ["CFL101", "CFL101"]
    msgs = " | ".join(v.message for v in found)
    # the chain is rendered down to the blocking site, helper included
    assert "Repairer._lock" in msgs
    assert "_helper" in msgs and "_pause" in msgs
    assert "_measure" in msgs


def test_lock_graph_transitive_blocking_true_negative():
    g, mods = _graph(("graph_trans_good.py", "cubefs_tpu/fs/fx.py"))
    assert LockGraphChecker().check_project(g, mods) == []


def test_lock_graph_two_lock_cycle():
    g, mods = _graph(("graph_cycle2_bad.py", "cubefs_tpu/fs/fx.py"))
    found = LockGraphChecker().check_project(g, mods)
    assert _codes(found) == ["CFL102"]
    msg = found[0].message
    assert "Pool._map_lock" in msg and "Pool._stats_lock" in msg


def test_lock_graph_three_lock_cycle():
    g, mods = _graph(("graph_cycle3_bad.py", "cubefs_tpu/fs/fx.py"))
    found = LockGraphChecker().check_project(g, mods)
    assert _codes(found) == ["CFL102"]
    msg = found[0].message
    for lock in ("Trio._a_lock", "Trio._b_lock", "Trio._c_lock"):
        assert lock in msg


def test_lock_graph_cycle_allow_on_one_edge_suppresses():
    g, mods = _graph(("graph_cycle_allow.py", "cubefs_tpu/fs/fx.py"))
    assert LockGraphChecker().check_project(g, mods) == []


def test_lock_graph_scope():
    c = LockGraphChecker()
    assert c.applies("cubefs_tpu/parallel/raft.py")
    assert c.applies("cubefs_tpu/utils/fsm.py")
    assert not c.applies("cubefs_tpu/utils/rpc.py")
    assert not c.applies("tests/test_fx.py")


# ---------------- fsm-purity (CFM00x) ----------------

def test_fsm_purity_clock_via_helper():
    g, mods = _graph(("graph_fsm_clock_bad.py", "cubefs_tpu/fs/fakefsm.py"))
    found = FsmPurityChecker().check_project(g, mods)
    assert _codes(found) == ["CFM001"]
    msg = found[0].message
    # chain shows WHY the helper is in the blast radius
    assert "_apply_touch" in msg and "_now" in msg


def test_fsm_purity_random_in_default_arg():
    g, mods = _graph(("graph_fsm_default_bad.py", "cubefs_tpu/fs/fakefsm.py"))
    found = FsmPurityChecker().check_project(g, mods)
    assert _codes(found) == ["CFM002"]
    assert "default-arg" in found[0].message


def test_fsm_purity_injected_clock_is_clean():
    g, mods = _graph(("graph_fsm_good.py", "cubefs_tpu/fs/fakefsm.py"))
    # the root IS detected (base matched by final name) ...
    assert any(q.endswith("._apply_touch") for q in apply_roots(g))
    # ... but record-carried ts + injected clock leave nothing to report
    assert FsmPurityChecker().check_project(g, mods) == []


# ---------------- witness-discipline (CFS001) ----------------

def test_witness_discipline_true_positives():
    mod = _module("witness_bad.py", "cubefs_tpu/fs/fx.py")
    found = WitnessDisciplineChecker().check(mod)
    assert _codes(found) == ["CFS001", "CFS001", "CFS001"]


def test_witness_discipline_true_negative():
    mod = _module("witness_good.py", "cubefs_tpu/fs/fx.py")
    assert WitnessDisciplineChecker().check(mod) == []


def test_witness_discipline_scope():
    c = WitnessDisciplineChecker()
    assert c.applies("cubefs_tpu/parallel/raft.py")
    assert c.applies("cubefs_tpu/utils/fsm.py")
    # rpc.py's pools live outside the witnessed planes (the witness
    # itself must not recurse into the transport's own locks) ...
    assert not c.applies("cubefs_tpu/utils/rpc.py")
    # ... and the witness module is exempt from its own rule
    assert not c.applies("cubefs_tpu/utils/lockwitness.py")


# ---------------- wire-discipline (CFX00x) ----------------

def test_wire_discipline_true_positives():
    mod = _module("wire_bad.py", "cubefs_tpu/tool/fx.py")
    found = WireDisciplineChecker().check(mod)
    assert _codes(found) == ["CFX001", "CFX001", "CFX001", "CFX002"]


def test_wire_discipline_true_negative():
    mod = _module("wire_good.py", "cubefs_tpu/tool/fx.py")
    assert WireDisciplineChecker().check(mod) == []


def test_wire_discipline_sanctums_exempt():
    c = WireDisciplineChecker()
    assert c.applies("cubefs_tpu/tool/loadgen.py")
    assert c.applies("cubefs_tpu/fs/metanode.py")
    # the transport itself and its two sanctioned consumers are home
    assert not c.applies("cubefs_tpu/utils/packet.py")
    assert not c.applies("cubefs_tpu/fs/client.py")
    assert not c.applies("cubefs_tpu/sdk/clients.py")


# ---------------- geo-discipline ----------------

def test_geo_discipline_true_positives():
    mod = _module("geo_bad.py", "cubefs_tpu/fs/fx.py")
    found = GeoDisciplineChecker().check(mod)
    # two raw-door calls in rpc handlers + two ungated commit doors
    # (submit_many carries its gate and must stay silent)
    assert _codes(found) == ["CFG001", "CFG001", "CFG002", "CFG002"]
    assert any("geo_apply" in v.message for v in found)
    assert any("Partition.submit" in v.message for v in found)
    assert any("Partition.alloc_ino" in v.message for v in found)
    assert not any("submit_many" in v.message for v in found)


def test_geo_discipline_true_negative():
    mod = _module("geo_good.py", "cubefs_tpu/fs/fx.py")
    assert GeoDisciplineChecker().check(mod) == []


def test_geo_discipline_applier_modules_sanctioned():
    # the SAME raw-door handler source is legal where the applier
    # lives: the gateway IS the one sanctioned entry point
    mod = _module("geo_bad.py", "cubefs_tpu/fs/georepl.py")
    found = GeoDisciplineChecker().check(mod)
    assert "CFG001" not in _codes(found)  # CFG002 still applies


def test_geo_mutations_classified_for_idempotency():
    # the geo stream surface rides the same transport retry; its
    # mutating ops must be classified so CFR001 sees bare call sites
    assert is_mutating("geo_ship")
    assert is_mutating("geo_resync")
    assert is_mutating("geo_transition")
    assert not is_mutating("geo_status")


# ---------------- split-discipline ----------------

def test_split_discipline_true_positives():
    mod = _module("split_bad.py", "cubefs_tpu/fs/fx.py")
    found = SplitDisciplineChecker().check(mod)
    # direct append + aliased sort + aliased rewrite + wholesale swap,
    # and ONE unfenced mutation door (rpc_submit_batch is fenced)
    assert _codes(found) == ["CFE001", "CFE001", "CFE001", "CFE001",
                             "CFE002"]
    assert any("rpc_grow" in v.message for v in found)
    assert any("mps.sort()" in v.message for v in found)
    assert any("BadMetaNode.rpc_submit" in v.message for v in found)
    assert not any("_apply_add_mp" in v.message for v in found)
    assert not any("rpc_submit_batch" in v.message for v in found)


def test_split_discipline_true_negative():
    mod = _module("split_good.py", "cubefs_tpu/fs/fx.py")
    assert SplitDisciplineChecker().check(mod) == []


def test_split_discipline_scope():
    c = SplitDisciplineChecker()
    assert c.applies("cubefs_tpu/fs/master.py")
    assert c.applies("cubefs_tpu/fs/split.py")
    assert not c.applies("cubefs_tpu/sdk/clients.py")
    assert not c.applies("tool/snapshot.py")


# ---------------- baseline ordering + summary cache + wall time ----------------

def test_update_baseline_sorted_by_position(tmp_path):
    import json

    vs = [
        core.Violation("CFZ001", "r", "b.py", 12, "m"),
        core.Violation("CFZ002", "r", "a.py", 1, "m"),
        core.Violation("CFZ001", "r", "b.py", 3, "m"),
        core.Violation("CFZ001", "r", "a.py", 9, "m"),
    ]
    path = str(tmp_path / "baseline.json")
    core.save_baseline(vs, path)
    fps = json.load(open(path))["violations"]
    # (path, code, line) with the LINE compared numerically: b.py:3
    # precedes b.py:12 even though "12" < "3" as text
    assert fps == [vs[3].fingerprint, vs[1].fingerprint,
                   vs[2].fingerprint, vs[0].fingerprint]


def test_graph_summary_cache_round_trip(tmp_path):
    cache = str(tmp_path / "cache")
    pairs = (("graph_trans_bad.py", "cubefs_tpu/fs/fx.py"),
             ("graph_fsm_clock_bad.py", "cubefs_tpu/fs/fakefsm.py"))
    mods1 = {rp: _module(fx, rp) for fx, rp in pairs}
    g1 = graphlib.ProjectGraph.build(mods1, cache_dir=cache, parallel=False)
    assert [f for f in os.listdir(cache) if f.endswith(".json")], \
        "summary cache was not populated"
    # a second build (fresh parse) must land on the cache and agree
    mods2 = {rp: _module(fx, rp) for fx, rp in pairs}
    g2 = graphlib.ProjectGraph.build(mods2, cache_dir=cache, parallel=False)
    assert set(g1.funcs) == set(g2.funcs)
    for q, f in g1.funcs.items():
        assert g2.funcs[q].effects == f.effects
    # the cached build finds the same violations
    assert _codes(LockGraphChecker().check_project(g2, mods2)) == \
        _codes(LockGraphChecker().check_project(g1, mods1))


def test_lint_wall_time_within_budget():
    """Perf gate for the interprocedural engine: a full lint of the
    tree (summary cache warm or cold) must stay within 1.2x of the
    pre-engine wall time measured on this tier (8.7s -> 10.4s budget).
    The engine's one-parse-pass + content-hash cache keeps the real
    figure far below that; this guards against an accidental
    per-checker re-parse creeping back in."""
    import time

    t0 = time.perf_counter()
    violations, errors = cli.run_lint()
    elapsed = time.perf_counter() - t0
    assert errors == []
    assert elapsed < 10.4, f"lint took {elapsed:.1f}s (budget 10.4s)"
