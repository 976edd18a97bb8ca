"""Tier-1 (CPU) coverage of what PR 28 added to the cell benchmark: the
``most_units_pair`` break rule, the ``counter_total`` reader, and the
new cell's own checks at its tiny traffic file, and the harness's GET
mix with a shard of every blob stalling past the hedge."""

import os

import pytest

from cellbench import registry, run, spec
from cellbench.generators import repair_backlog_disks
from cubefs_tpu.ops import rs_kernel

HERE = os.path.dirname(os.path.abspath(__file__))


def tiny(cell: str) -> str:
    return os.path.join(HERE, "traffic", f"{cell}.json")


class _FakeCm:
    def __init__(self, placement: dict[int, list[int]]):
        self.disks = {d: None for d in placement}
        self._placement = placement

    def volumes_on_disk(self, disk_id):
        return [(vid, 0) for vid in self._placement[disk_id]]


class _FakeDep:
    def __init__(self, placement):
        self.cm = _FakeCm(placement)


@pytest.mark.parametrize("placement,want", [
    # disk 2 holds the most; 4 shares two volumes with it, 3 only one
    ({1: [10], 2: [10, 11, 12], 3: [12, 13, 14], 4: [10, 11]}, [2, 4]),
    # ties: the lower disk id, for the first disk and for the second
    ({5: [1, 2], 3: [1, 2], 7: [1, 2], 9: [3]}, [3, 5]),
    # a third disk continues the rule over the volumes already hit
    ({1: [1, 2, 3], 2: [1], 3: [2, 3], 4: [4]}, [1, 3, 2]),
])
def test_most_units_pair_rule(placement, want):
    got = repair_backlog_disks._pick_disks(_FakeDep(placement), len(want))
    assert got == want


def test_counter_total_reads_nothing_from_a_program_without_the_counter():
    reader = spec.reader("counter_total")
    cell = run.Cell({}, {}, 1, 4.0, False)
    name = "cubefs_codec_matrix_cache_total"
    cell.registry = {("other_total", frozenset()): 3.0}
    assert reader.read(cell, name, {"result": "miss"}) is None
    miss = frozenset({("op", "apply"), ("result", "miss")})
    hit = frozenset({("op", "apply"), ("result", "hit")})
    enc = frozenset({("op", "encode"), ("result", "miss")})
    cell.registry = {(name, hit): 9.0}
    assert reader.read(cell, name, {"result": "miss"}) == 0.0
    cell.registry = {(name, hit): 9.0, (name, miss): 4.0, (name, enc): 1.0}
    assert reader.read(cell, name, {"result": "miss"}) == 5.0
    assert reader.read(cell, name) == 14.0
    sp = spec.metric_spec("per_layer", "codec.matrix_misses")
    assert reader.read(cell, **sp["params"]) == 5.0
    # one entry for the repair cells, this one among them
    assert "codec.matrix_misses" in {e["name"] for e in spec.metric_entries(
        spec.load_benchmark(), "disk-repair-2disk", "per_layer")}


def test_two_disk_cell_rebuilds_both_units_of_a_two_loss_stripe(monkeypatch):
    """The tiny cell: two disks that share a volume are lost, set-up
    warms a shape and no matrix, and the window's tasks bring matrices
    the process had not seen — without a compile, so the run is
    ``correct``; both rebuilt units are compared with the reference."""
    from cubefs_tpu.codec import batcher, engine

    # the test process has 8 virtual devices and a dp-sharded step
    # records no engine phase: one chip, as the cell runs
    monkeypatch.setattr(batcher.DEFAULT, "dp_enabled", False)
    # the tiny tasks are a fraction of PHASE_EVERY_S apart: take every
    # call apart, so the phases read whatever the machine's pace
    monkeypatch.setattr(engine, "PHASE_EVERY_S", 0.0)
    # and matrices an earlier test of this process served are new again
    rs_kernel.matrices.clear()
    result = run.run_cell("disk-repair-2disk", 11, 4.0, True,
                          device_checks=False,
                          traffic_path=tiny("disk-repair-2disk"))
    detail = result["detail"]
    assert result["correct"] is True, detail
    backlog = detail["notes"]["backlog"]
    assert len(backlog["disks"]) == 2 and backlog["volumes_two_lost"] >= 1
    assert all(len(s) == 4 for s in backlog["warmed_shapes"])
    checks = detail["checks"]
    assert checks["two_loss_stripes_checked"] >= 1
    assert checks["rebuilt_shards_checked"] >= 2 and checks["gets"] >= 1
    assert checks["faults"] == [] and detail["device_faults"] == []
    m = result["metrics"]
    assert m["codec.matrix_misses"]["value"] >= 1
    assert m["dispatch.compiles_in_window-2disk"]["value"] == 0
    assert m["dispatch.device_step_share-2disk"]["value"] == 100
    assert m["engine.matrix_ms-repair"]["value"] > 0
    assert m["batcher.stripes_per_step-repair"]["value"] >= 1


def test_two_disk_cell_fails_correct_when_a_rebuilt_unit_is_wrong(
        monkeypatch):
    from cellbench.deployment import Deployment

    real = Deployment.unit_call

    def rotten(self, unit, method, bid=None):
        meta, body = real(self, unit, method, bid)
        if method == "get_shard":
            body = bytes([body[0] ^ 1]) + body[1:]
        return meta, body

    monkeypatch.setattr(Deployment, "unit_call", rotten)
    result = run.run_cell("disk-repair-2disk", 12, 4.0, False,
                          device_checks=False,
                          traffic_path=tiny("disk-repair-2disk"))
    assert result["correct"] is False
    assert any("differs from the reference" in f
               for f in result["detail"]["checks"]["faults"])


def test_hedged_gets_decode_without_a_compile(monkeypatch):
    """The GET mix of ``closed_loop`` with one data shard of every blob
    stalling past the hedge (50 ms, as shipped), a different one from
    bid to bid: every GET decodes from the survivors that came first —
    survivor sets nobody warmed, served by the decode program that came
    with the geometry's encode. Nothing compiles in the window, so the
    run is ``correct`` (before PR 28 each survivor set was a program and
    a Pallas gate of its own, compiled inside the request)."""
    import time

    from cubefs_tpu.blob.access import AccessHandler

    def counted() -> tuple[float, float]:
        now = registry.snapshot()
        return (registry.total(now, "cubefs_codec_matrix_cache_total",
                               result="miss"),
                registry.total(now, "cubefs_reconstruct_total",
                               path="global"))

    real = AccessHandler._read_shard

    def stalling(self, vol, idx, bid):
        if idx == bid % 3:
            time.sleep(2 * self.HEDGE_DELAY)
        return real(self, vol, idx, bid)

    monkeypatch.setattr(AccessHandler, "_read_shard", stalling)
    rs_kernel.matrices.clear()
    before = counted()
    result = run.run_cell("put-small", 13, 1.5, True, device_checks=False,
                          traffic_path=tiny("closed-loop-gets"))
    detail = result["detail"]
    assert result["correct"] is True, detail
    assert detail["ops_in_window"]["get"] > 0 < detail["ops_in_window"]["put"]
    assert detail["checks"]["gets_compared"] >= detail["ops_in_window"]["get"]
    missed, decoded = (a - b for a, b in zip(counted(), before))
    assert decoded >= detail["ops_in_window"]["get"]
    # set-up's two geometries miss twice each (the encode's rows and
    # ready_decode's identity); every miss beyond is a survivor set that
    # a GET brought
    assert missed > 4, missed
    m = result["metrics"]
    assert m["dispatch.compiles_in_window-small"]["value"] == 0
    assert m["dispatch.device_step_share-small"]["value"] == 100
    assert registry.total(registry.snapshot(),
                          "cubefs_codec_programs_total") > 0
