"""Tier-1 (CPU) coverage of what PR 36 added to the cell benchmark: the
``repair_backlog_sizes`` generator and the cell ``disk-repair-randsize``
at its tiny traffic file — 42 sizes of 4 KiB-512 KiB in volumes of four
blobs, the fullest disk lost — with the two ready doors as the only
warm-up."""

import os

import numpy as np
import pytest

from cellbench import run, spec
from cellbench.generators import repair_backlog_sizes as gen

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "traffic", "disk-repair-randsize.json")


@pytest.fixture(autouse=True)
def small_volumes(monkeypatch):
    """One chip, as the cell runs (the test process has 8 virtual
    devices), and volumes of four blobs, so 42 objects fill eleven."""
    from cubefs_tpu.blob.proxy import ProxyAllocator
    from cubefs_tpu.codec import batcher

    monkeypatch.setattr(batcher.DEFAULT, "dp_enabled", False)
    monkeypatch.setattr(ProxyAllocator, "VOLUME_REUSE", 4)


def run_tiny(seed: int, trace: bool) -> dict:
    return run.run_cell("disk-repair-randsize", seed, 60.0, trace,
                        device_checks=False, traffic_path=TINY)


def test_the_cell_repairs_mixed_volumes_and_is_correct():
    result = run_tiny(7, True)
    detail = result["detail"]
    assert result["correct"] is True, detail
    assert detail["device_faults"] == [] and detail["checks"]["faults"] == []
    checks, notes = detail["checks"], detail["notes"]
    assert checks["tasks_done"] == checks["tasks"] == notes["backlog"]["tasks"]
    assert checks["tasks"] >= 3 and checks["rebuilt_shards_checked"] >= 4
    assert checks["programs_built_after_ready"] == 0 and checks["gets"] >= 1
    assert checks["shortest_shard_checked"] < 32_768  # under one tile
    assert notes["ready"]["steps"] == {"worker": 25, "access": 20}
    assert notes["fill"]["objects"] == 42
    assert notes["fill"]["volumes"] == {"EC3P3": 9, "EC6P6": 2}
    assert result["attempted"] == sum(detail["ops_in_window"].values()) > 0
    assert detail["compiles_window"]["compiles"] == 0
    m = {k: v["value"] for k, v in result["metrics"].items()}
    bench = spec.load_benchmark()
    want = {e["name"] for e in spec.metric_entries(
        bench, "disk-repair-randsize", "per_layer")}
    # no device trace on the CPU: the two metrics that read it stay out
    off_chip = {"gf_apply_roofline-repair", "pallas_gf_roofline-repair"}
    # an engine takes apart at most one call in PHASE_EVERY_S
    sampled = {f"engine.{p}_ms-repair"
               for p in ("matrix", "h2d", "launch", "wait", "d2h")}
    assert len(want) == 27 and want - set(m) <= off_chip | sampled
    assert m["dispatch.compiles_in_window-repair"] == 0
    assert m["dispatch.device_step_share-repair"] == 100
    # volumes of four blobs: a task is a step or two of 1-4 bids of as
    # many sizes, each array zero stripes up to eight
    assert 1 <= m["repair.steps_per_task"] <= 3
    assert 1 <= m["repair.widths_per_step"] <= 4
    assert m["repair.widths_per_step"] <= m[
        "batcher.stripes_per_step-repair"] <= 4
    assert 50 < m["batcher.pad_share-repair"] < 100
    assert m["repair.rebuilt_rate"] > 0 and m["engine.step_ms-repair"] > 0
    # the kept step array (PR 40): the ramp's step made it
    assert 0 <= m["repair.step_array_reuse_share"] <= 100
    shares = [m[f"repair.{s}_share"]
              for s in ("read", "decode", "writeback")]
    assert 90 < sum(shares) <= 100.5


def test_the_end_to_end_metrics_are_repair_rate_and_setup_s():
    result = run_tiny(8, False)
    assert result["correct"] is True, result["detail"]
    assert set(result["metrics"]) == {"repair_rate", "setup_s"}
    assert result["metrics"]["repair_rate"]["value"] > 0


def test_every_seed_fills_the_same_volumes_and_breaks_the_same_disk():
    a, b = (run_tiny(seed, False)["detail"]["notes"]
            for seed in (21, 2147483999))
    assert a["backlog"] == b["backlog"] and a["backlog"]["tasks"] >= 3
    assert a["fill"]["volumes"] == b["fill"]["volumes"]
    assert a["fill"]["bytes"] != b["fill"]["bytes"]  # other sizes


def test_the_plan_is_the_files_and_every_stratum_is_of_one_class():
    """The full-size cell's fill, planned without a deployment's disks:
    every seed has the strata in the same codemodes, 1200 / 800 / 400 by
    count of which 200 two blobs, so the same ~42 volumes."""
    from cubefs_tpu.blob.access import AccessConfig

    class Dep:
        class access:
            cfg = AccessConfig()

    tr = spec.load_json(spec.traffic_file("disk-repair-randsize"))
    assert tr["sizes"] == spec.load_json(
        spec.traffic_file("mix-continuous"))["sizes"]
    order = np.random.default_rng([tr["fill_order_seed"], 6]).permutation(
        tr["fill_objects"])
    plans = []
    for seed in (1, 2, 2147483999, 4294967295):
        sizes, shapes = gen.class_sizes(Dep, seed, tr["sizes"])
        assert not plans or shapes == plans[0][1]
        by = {}
        for mode, blobs in shapes:
            by[(mode, blobs)] = by.get((mode, blobs), 0) + 1
        assert sorted(by.values()) == [200, 200, 800, 1200]
        assert abs(int(sizes.sum()) - 4.84e9) < 0.01 * 4.84e9
        plans.append((gen.plan_volumes(order, shapes, 64), shapes))
    assert all(p[0] == plans[0][0] for p in plans)
    volumes = plans[0][0]
    per_mode = {}
    for mode, objects in volumes:
        per_mode[mode] = per_mode.get(mode, 0) + 1
        blobs = sum(plans[0][1][k][1] for k in objects)
        assert 1 <= blobs <= 64
    assert sorted(per_mode.values()) == [10, 13, 19]
    assert sorted(k for _, objects in volumes for k in objects) == list(
        range(2400))


def test_a_size_on_a_class_boundary_is_taken_one_byte_larger(monkeypatch):
    from cubefs_tpu.blob.access import AccessConfig

    class Dep:
        class access:
            cfg = AccessConfig()

    tr = spec.load_json(spec.traffic_file("disk-repair-randsize"))
    real = gen.closed_loop_sizes.draw_sizes

    def on_the_edge(seed, sizes):
        out = real(seed, sizes)
        out[1200], out[2000], out[2200] = 262144, 4194304, 8388608
        return out

    monkeypatch.setattr(gen.closed_loop_sizes, "draw_sizes", on_the_edge)
    sizes, shapes = gen.class_sizes(Dep, 5, tr["sizes"])
    assert [int(sizes[k]) for k in (1200, 2000, 2200)] == [
        262145, 4194305, 8388609]
    assert shapes[1199][0] != shapes[1200][0] == shapes[1201][0]
    assert shapes[2199][1] == 1 and shapes[2200][1] == 2


def test_one_flipped_byte_of_a_rebuilt_shard_is_not_correct(monkeypatch):
    from cellbench.deployment import Deployment

    real = Deployment.unit_call

    def rotten(self, unit, method, bid=None):
        meta, body = real(self, unit, method, bid)
        if method == "get_shard":
            body = body[:-1] + bytes([body[-1] ^ 1])
        return meta, body

    monkeypatch.setattr(Deployment, "unit_call", rotten)
    result = run_tiny(9, False)
    assert result["correct"] is False
    assert any("differs from the reference stripe" in f
               for f in result["detail"]["checks"]["faults"])


def test_a_rebuilt_shard_with_pad_left_on_is_not_correct(monkeypatch):
    """One byte of the step's pad written back with every shard: the
    stored CRC is right for what is stored, its length is not."""
    from cubefs_tpu.blob.worker import RepairWorker

    real = RepairWorker._write_back

    def padded(self, task, dest, writes):
        real(self, task, dest, [(bid, shard + b"\0")
                                for bid, shard in writes])

    monkeypatch.setattr(RepairWorker, "_write_back", padded)
    result = run_tiny(10, False)
    assert result["correct"] is False
    assert any("the rebuilt shard holds" in f
               for f in result["detail"]["checks"]["faults"])


def test_a_program_built_after_the_ready_doors_is_not_correct(monkeypatch):
    """A worker door that builds nothing: the first task of every shape
    builds its program, as before this PR."""
    from cubefs_tpu.blob.worker import RepairWorker
    from cubefs_tpu.ops import progcache

    progcache.SHARED.clear()  # what earlier tests of this process built
    monkeypatch.setattr(RepairWorker, "ready", lambda self, largest: 0)
    result = run_tiny(11, False)
    assert result["correct"] is False
    assert any("built after the ready doors" in f
               for f in result["detail"]["checks"]["faults"])
    assert result["detail"]["checks"]["programs_built_after_ready"] > 0


def test_a_worker_without_the_ready_door_fails_at_once(monkeypatch):
    """What the parent commit does with this cell."""
    from cubefs_tpu.blob.worker import RepairWorker

    monkeypatch.delattr(RepairWorker, "ready")
    with pytest.raises(AttributeError, match="ready"):
        run_tiny(12, False)


def test_the_configuration_file_states_the_deployment():
    bench = spec.load_benchmark()
    cell, cfg = spec.find_cell(bench, "disk-repair-randsize")
    assert cfg["name"] == "repair-tpu-1az-randsize" and cell["chips"] == 1
    state = spec.load_json(cfg["file"])
    assert set(cfg["reduced"]) == set(state["reduced"]) == {
        "disks", "transport", "storage_medium", "fleet_fill"}
    assert cfg["source"] == state["source"] and len(cfg["source"]) <= 200
    assert len(cfg["why"]) <= 200 and len(cell["why"]) <= 200
    assert len(state["guarantees"]) == 6
    repair = spec.load_json("cellbench/configs/repair-tpu-1az.json")
    assert state["deployment"] == repair["deployment"]
    assert state["codemodes"] == repair["codemodes"]
    tr = spec.load_json(spec.traffic_file("disk-repair-randsize"))
    assert state["object_sizes"] == {
        k: tr["sizes"][k] for k in ("dist", "min_bytes", "max_bytes")}
    assert "disk-repair-randsize" in next(
        m for m in bench["end_to_end"]
        if m["name"] == "repair_rate")["workloads"]
