"""The `lrc-disk-repair` cell at its tiny traffic file: a disk of the
two-AZ EC16P20L2 fleet lost, its unit rebuilt from the local stripe of
its AZ and checked through the global code. Its two entries read 100 and
0, and `correct` holds the run to the guarantees the configuration adds:
nothing read across AZs, every rebuilt shard checked, every unit back in
its AZ. The program's side is tests/test_lrc_repair.py's."""

import os

import pytest

from cellbench import run, spec

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "traffic", "lrc-disk-repair.json")


def run_tiny(seed: int, trace: bool, seconds: float = 2.0) -> dict:
    return run.run_cell("lrc-disk-repair", seed, seconds, trace,
                        device_checks=False, traffic_path=TINY)


def test_the_tiny_run_reads_every_unit_local_and_no_byte_across_azs():
    result = run_tiny(2147604201, True)
    detail = result["detail"]
    assert result["correct"] is True, detail
    checks = detail["checks"]
    assert checks["faults"] == [] and detail["device_faults"] == []
    assert checks["tasks_done"] == checks["tasks"] >= 1
    assert checks["rebuilt_shards_checked"] >= 1 and checks["gets"] == 1
    assert checks["programs_built_after_ready"] == 0
    assert checks["bytes_pulled"]["cross_az"] == 0
    assert checks["sources"] == {"local": checks["tasks_done"], "global": 0}
    assert checks["checks"]["none"] == checks["checks"]["survivor"] == 0
    assert checks["checks"]["derived"] > 0
    for homes in checks["local_stripe_azs"]:
        assert sorted(homes) == [["az0"], ["az1"]]
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["repair.local_source_share"] == 100.0
    assert m["repair.cross_az_read_share"] == 0.0
    assert m["dispatch.compiles_in_window-repair"] == 0
    assert m["dispatch.device_step_share-repair"] == 100
    # both ready doors, and nothing else, before the fill
    ready = detail["notes"]["ready"]
    assert ready["steps"]["worker"] > 0 and ready["steps"]["access"] > 0


def test_the_end_to_end_metrics_are_repair_rate_and_setup_s():
    result = run_tiny(2147604202, False)
    assert result["correct"] is True, result["detail"]
    assert set(result["metrics"]) == {"repair_rate", "setup_s"}
    assert result["metrics"]["repair_rate"]["value"] > 0


def test_a_rebuilt_shard_written_back_unchecked_is_not_correct(monkeypatch):
    """A worker that checks nothing: its shards are right, the guarantee
    is not kept."""
    from cubefs_tpu.blob.worker import RepairWorker

    real = RepairWorker._repair_rows

    def unchecked(self, *args, **kw):
        rows, out_pos, _, _ = real(self, *args, **kw)
        return rows, out_pos, None, "none"

    monkeypatch.setattr(RepairWorker, "_repair_rows", unchecked)
    result = run_tiny(2147604203, False)
    assert result["correct"] is False
    assert any("checked before its write-back" in f
               for f in result["detail"]["checks"]["faults"])


def test_a_repair_from_the_global_stripe_is_not_correct(monkeypatch):
    """A worker that reads the global stripe: right shards, read across
    the AZs."""
    from cubefs_tpu.codec import codemode as cm

    monkeypatch.setattr(cm.Tactic, "local_stripe",
                        lambda self, index: ([], 0, 0))
    result = run_tiny(2147604204, False)
    assert result["correct"] is False
    faults = result["detail"]["checks"]["faults"]
    assert any("from the lost unit's AZ" in f for f in faults)
    assert any("from its local stripe" in f for f in faults)


def test_a_worker_whose_door_skips_lrc_fails_at_once(monkeypatch):
    """What the parent commit does with this cell: its door builds no
    LRC repair program, and set-up stops before the fill."""
    from cubefs_tpu.blob.worker import RepairWorker

    monkeypatch.setattr(RepairWorker, "ready", lambda self, *a, **kw: 0)
    with pytest.raises(RuntimeError, match="ready door builds no repair"):
        run_tiny(2147604205, False)


def test_the_configuration_file_states_the_deployment():
    bench = spec.load_benchmark()
    cell, cfg = spec.find_cell(bench, "lrc-disk-repair")
    assert cfg["name"] == "repair-tpu-2az-lrc" and cell["chips"] == 1
    state = spec.load_json(cfg["file"])
    put = spec.load_json("cellbench/configs/access-tpu-2az-lrc.json")
    assert set(cfg["reduced"]) == set(state["reduced"]) == set(
        put["reduced"]) | {"fleet_fill"}
    assert cfg["source"] == state["source"] and len(cfg["source"]) <= 200
    assert state["deployment"] == {**put["deployment"], "broken_disks": 1}
    assert (state["codemodes"], state["policies"]) == (put["codemodes"],
                                                       put["policies"])
    assert state["guarantees"][:len(put["guarantees"])] == put["guarantees"]
    assert len(state["guarantees"]) == len(put["guarantees"]) + 3
    assert set(put["assumed"]) <= set(state["assumed"])
    tr = spec.load_json(spec.traffic_file("lrc-disk-repair"))
    assert tr["generator"] == "repair_backlog_lrc"
    assert (tr["object_bytes"], tr["fill_objects"], tr["fill_clients"],
            tr["payload_pool"]) == (64 << 20, 56, 4, 8)
    assert "lrc-disk-repair" in next(
        m for m in bench["end_to_end"]
        if m["name"] == "repair_rate")["workloads"]
    mine = {m["name"] for m in spec.metric_entries(bench, "lrc-disk-repair",
                                                   "per_layer")}
    randsize = {m["name"] for m in spec.metric_entries(
        bench, "disk-repair-randsize", "per_layer")}
    assert mine == randsize | {"repair.local_source_share",
                               "repair.cross_az_read_share"}
