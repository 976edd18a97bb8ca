"""Tier-1 (CPU) coverage of the per-layer metrics that read what the
program records about itself (PR 26: stages of a PUT and of a repair's
decode, the engine call's phases, the blobnode's own shard I/O time, the
rebuilt-bytes counter): every cell's traced tiny run reports each of
them, and the two readers they brought are checked on a synthetic
registry — also one that lacks the series, as the parent commit's does."""

import os

import pytest

from cellbench import registry, run, spec

HERE = os.path.dirname(os.path.abspath(__file__))
STAGES = "cubefs_request_stage_seconds_sum"

NEW = {
    "ingest-large": {
        "access.stripe_fill_share", "access.location_crc_share",
        "access.staged_share", "engine.h2d_ms", "engine.launch_ms",
        "engine.wait_ms", "engine.d2h_ms", "storage.node_put_ms"},
    "put-small": {
        "access.staged_share-small", "engine.launch_ms-small",
        "engine.wait_ms-small", "batcher.gather_ms-small",
        "storage.node_put_ms-small"},
    "disk-repair": {
        "engine.h2d_ms-repair", "engine.wait_ms-repair",
        "engine.d2h_ms-repair", "storage.node_get_ms-repair",
        "repair.stack_share", "repair.verify_share", "repair.rebuilt_rate"},
}


@pytest.mark.parametrize("cell", sorted(NEW))
def test_traced_tiny_run_reports_the_programs_own_metrics(cell, monkeypatch):
    from cubefs_tpu.codec import batcher, engine

    # an engine takes apart one call in PHASE_EVERY_S; a tiny window has
    # few steps, so every call here
    monkeypatch.setattr(engine, "PHASE_EVERY_S", 0.0)
    # the test process has 8 virtual devices and a step of two stripes
    # or more would ride the dp path, which is no engine call; a cell
    # has one chip
    monkeypatch.setattr(batcher.DEFAULT, "dp_enabled", False)
    bench = spec.load_benchmark()
    listed = {m["name"] for m in spec.metric_entries(bench, cell,
                                                     "per_layer")}
    assert NEW[cell] <= listed
    result = run.run_cell(cell, 11, 2.0, True, device_checks=False,
                          traffic_path=os.path.join(HERE, "traffic",
                                                    f"{cell}.json"))
    assert result["correct"] is True, result["detail"]["checks"]
    got = result["metrics"]
    assert NEW[cell] <= set(got), sorted(NEW[cell] - set(got))
    for name in NEW[cell]:
        assert got[name]["value"] >= 0.0, name
    staged = next((got[n]["value"] for n in got
                   if n.startswith("access.staged_share")), None)
    if staged is not None:  # six disjoint stages inside the PUT's total
        assert 50.0 < staged <= 100.0
    if cell == "disk-repair":
        assert got["repair.rebuilt_rate"]["unit"] == "MB/s"
        assert got["repair.stack_share"]["value"] \
            + got["repair.verify_share"]["value"] \
            <= got["repair.decode_share"]["value"]


def _cell(series):
    cell = run.Cell({}, {}, 1, 4.0, True)
    cell.registry = series
    return cell


def _stage(path, stage):
    return (STAGES, frozenset({("path", path), ("stage", stage)}))


def test_stage_cover_share_sums_only_the_listed_stages():
    read = spec.reader("stage_cover_share").read
    series = {_stage("blob.put", "total"): 10.0,
              _stage("blob.put", "stripe_fill"): 2.0,
              _stage("blob.put", "quorum_write"): 3.0,
              _stage("blob.put", "codec_step"): 4.0,  # overlaps, not listed
              _stage("blob.get", "stripe_fill"): 50.0}  # another path
    assert read(_cell(series), "blob.put",
                ["stripe_fill", "quorum_write"]) == pytest.approx(50.0)
    # a stage the program does not have (the parent) adds nothing
    assert read(_cell(series), "blob.put",
                ["stripe_fill", "location_crc"]) == pytest.approx(20.0)
    assert read(_cell(series), "blob.repair", ["decode"]) is None
    assert read(_cell({}), "blob.put", ["stripe_fill"]) is None


def test_counter_over_stage_seconds_is_work_per_second_worked():
    read = spec.reader("counter_over_stage_seconds").read
    series = {_stage("blob.repair", "total"): 5.0,
              _stage("blob.repair", "decode"): 1.0,
              ("cubefs_repair_bytes_rebuilt_total", frozenset()): 45e6}
    assert read(_cell(series), "cubefs_repair_bytes_rebuilt_total",
                "blob.repair", "total", scale=1e-6) == pytest.approx(9.0)
    # the parent has the stage and not the counter: nothing, not 0
    del series[("cubefs_repair_bytes_rebuilt_total", frozenset())]
    assert read(_cell(series), "cubefs_repair_bytes_rebuilt_total",
                "blob.repair", "total") is None
    assert read(_cell({}), "cubefs_repair_bytes_rebuilt_total",
                "blob.repair", "total") is None


def test_the_new_entries_read_the_program_and_not_a_wrapper():
    """Every metric this file lists comes from the program's registry
    (`program_span` / `program_counter`), through a reader that takes no
    benchmark span, and is moved by the cell's own end-to-end metric: the
    one entry lists the cell beside the others of its group."""
    bench = spec.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for cell, names in NEW.items():
        for name in names:
            m = by_name[name]
            assert cell in m["workloads"], name
            assert set(m["workloads"]) <= set(
                e2e[m["moves"]]["workloads"]), name
            want = ("program_counter" if name == "repair.rebuilt_rate"
                    else "program_span")
            assert m["source"] == want, name
            sp = spec.metric_spec("per_layer", name)
            assert sp["reader"] in ("stage_share", "stage_cover_share",
                                    "hist_mean",
                                    "counter_over_stage_seconds"), name
            assert "span" not in sp.get("params", {}), name
    assert sum(len(v) for v in NEW.values()) == 20
