"""Tier-1 (CPU) coverage of what PR 34 added to the cell benchmark: the
``closed_loop_sizes`` generator and the cell ``mix-continuous`` at its
tiny traffic file — 32 sizes across the three codemodes, one of them a
two-blob object — with the ladder's warm-up as the only set-up."""

import importlib
import os

import numpy as np
import pytest

from cellbench import run, spec
from cellbench.generators import closed_loop_sizes

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "traffic", "mix-continuous.json")


@pytest.fixture(autouse=True)
def one_chip(monkeypatch):
    """The test process has 8 virtual devices and a dp-sharded step
    records no engine phase: one chip, as the cell runs."""
    from cubefs_tpu.codec import batcher

    monkeypatch.setattr(batcher.DEFAULT, "dp_enabled", False)


def run_tiny(seed: int, trace: bool) -> dict:
    return run.run_cell("mix-continuous", seed, 60.0, trace,
                        device_checks=False, traffic_path=TINY)


def test_the_cell_runs_on_the_ladder_and_is_correct():
    result = run_tiny(7, True)
    detail = result["detail"]
    assert result["correct"] is True, detail
    assert detail["device_faults"] == [] and detail["checks"]["faults"] == []
    checks = detail["checks"]
    assert checks["puts_in_window"] == 32 == result["attempted"]
    assert checks["puts_late"] == 0
    assert checks["codemodes_checked"] == 3
    assert checks["objects_checked"] == 6 and checks["read_back"] == 16
    assert checks["several_blob_objects_checked"] == 1
    assert checks["programs_built_in_window"] == 0
    ready = detail["notes"]["ready"]
    # the whole ladder of objects up to 12 MiB, built before the window
    assert 60 <= ready["steps"] < 80
    assert detail["compiles_window"]["compiles"] == 0
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["dispatch.compiles_in_window"] == 0
    assert m["dispatch.device_step_share"] == 100
    assert m["batcher.stripes_per_step"] > 1  # the two-blob PUTs
    assert m["batcher.widths_per_step"] >= 1
    assert 0 < m["batcher.pad_share"] <= 30
    assert m["engine.step_ms"] > 0 and m["access.staged_share"] > 90
    for name in ("batcher.wait_ms", "batcher.gather_ms",
                 "access.stripe_fill_share", "engine.h2d_ms",
                 "access.encode_wait_share", "engine.launch_ms",
                 "access.quorum_write_share", "storage.node_put_ms"):
        assert name in m, name


def test_the_end_to_end_metrics_are_put_rate_and_setup_s():
    result = run_tiny(8, False)
    assert result["correct"] is True, result["detail"]
    assert set(result["metrics"]) == {"put_rate", "setup_s"}
    assert result["metrics"]["put_rate"]["value"] > 0
    assert result["detail"]["notes"]["offered_bytes"] > 40_000_000


def test_a_put_the_window_closed_on_is_late_not_wrong(monkeypatch):
    """A host that stalls (the VM's write-behind, PERF.md section 6) can
    close the window on the last PUTs: the run reads a slow ``put_rate``
    and stays ``correct``; only a failed or a wrong PUT is for
    ``correct``."""
    real = closed_loop_sizes.run

    def cut_short(cell):
        real(cell)
        for k in sorted(cell.state.done)[-3:]:
            del cell.state.done[k]

    monkeypatch.setattr(closed_loop_sizes, "run", cut_short)
    result = run_tiny(9, False)
    checks = result["detail"]["checks"]
    assert result["correct"] is True, result["detail"]
    assert checks["puts_in_window"] == 29 and checks["puts_late"] == 3
    assert checks["faults"] == [] and result["failed"] == 0


def test_one_flipped_stored_byte_is_not_correct(monkeypatch):
    from cellbench.deployment import Deployment

    real = Deployment.unit_call

    def rotten(self, unit, method, bid=None):
        meta, body = real(self, unit, method, bid)
        if method == "get_shard" and unit.index == 1:
            body = body[:-1] + bytes([body[-1] ^ 1])
        return meta, body

    monkeypatch.setattr(Deployment, "unit_call", rotten)
    result = run_tiny(9, False)
    assert result["correct"] is False
    assert any("differ from the reference stripe" in f
               for f in result["detail"]["checks"]["faults"])


def test_a_pad_byte_left_dirty_in_the_rows_is_not_correct(monkeypatch):
    """A fill that leaves the last byte of a stripe's pad as the array
    held it (a reused array holds another PUT's bytes): the stored
    shard carries it and the reference does not."""
    from cubefs_tpu.blob import access

    real = access.fill_stripe_rows

    def forgetful(rows, data, blob_size, shard_size):
        real(rows, data, blob_size, shard_size)
        if len(data) % rows.shape[1]:  # the stripe has a pad byte
            rows[-1, -1, shard_size - 1] = 0xFF

    monkeypatch.setattr(access, "fill_stripe_rows", forgetful)
    result = run_tiny(10, False)
    assert result["correct"] is False
    assert any("differ from the reference stripe" in f
               for f in result["detail"]["checks"]["faults"])


def test_a_program_without_the_ready_door_fails_at_once(monkeypatch):
    """What the parent commit does with this cell: no ladder, no door."""
    from cubefs_tpu.blob.access import AccessHandler

    monkeypatch.delattr(AccessHandler, "ready")
    with pytest.raises(AttributeError, match="ready"):
        run_tiny(11, False)


def test_every_seed_offers_the_same_bytes():
    """Stratified: one size from each of 12 x 200 log-size strata, so
    five seeds agree to 0.2% in total and per doubling to 1%, and no
    two bring the same sizes."""
    tr = spec.load_json(spec.traffic_file("mix-continuous"))
    draws = [closed_loop_sizes.draw_sizes(seed, tr["sizes"])
             for seed in (1, 2, 3, 2147483999, 4294967295)]
    totals = [int(d.sum()) for d in draws]
    assert max(totals) - min(totals) < 0.002 * min(totals)
    assert abs(totals[0] - 4.84e9) < 0.01 * 4.84e9
    for d in draws:
        assert len(d) == 2400 == tr["max_ops"]
        assert d.min() >= 4096 and d.max() < 16777216
        assert np.all(np.diff(d) >= 0)  # one a stratum, ascending
        per = d.reshape(12, 200).sum(axis=1)
        assert np.allclose(per / per[0], 2.0 ** np.arange(12), rtol=0.01)
        # by count: half EC3P3, a third EC6P6, a sixth EC12P4
        assert (d <= 256 << 10).sum() == 1200
        assert ((d > 256 << 10) & (d <= 4 << 20)).sum() == 800
        assert (d > 8 << 20).sum() == 200  # the two-blob objects
    assert len({int(d[1234]) for d in draws}) == 5


def test_benchmark_json_names_only_files_that_exist():
    bench = spec.load_benchmark()
    for cfg in bench["configs"]:
        assert os.path.exists(os.path.join(spec.ROOT, cfg["file"])), cfg
    for cell in bench["workloads"]:
        path = spec.traffic_file(cell["traffic"])
        assert os.path.exists(path), cell
        importlib.import_module(
            f"cellbench.generators.{spec.load_json(path)['generator']}")
        groups = [spec.metric_entries(bench, cell["name"], g)
                  for g in ("end_to_end", "per_layer")]
        assert len(groups[0]) >= 2 and len(groups[1]) >= 1, cell["name"]
        for group, entries in zip(("end_to_end", "per_layer"), groups):
            for m in entries:
                sp = spec.metric_spec(group, m["name"])
                spec.reader(sp["reader"])
    cell, cfg = spec.find_cell(bench, "mix-continuous")
    assert cfg["name"] == "access-tpu-1az-randsize" and cell["chips"] == 1
    state = spec.load_json(cfg["file"])
    assert set(cfg["reduced"]) == set(state["reduced"])
    assert state["object_sizes"] == {
        k: spec.load_json(spec.traffic_file("mix-continuous"))["sizes"][k]
        for k in ("dist", "min_bytes", "max_bytes")}
