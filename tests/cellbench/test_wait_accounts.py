"""Tier-1 (CPU) coverage of what PR 39 added to the cell benchmark: the
per-layer metrics that read the program's three accounts of waiting (the
engine seam, the drainer's streak, the access pool's queue) at the tiny
traffic files, the ``hist_quantile`` reader against histograms counted
by hand, and every new metric against a program that lacks its series
(the parent commit): nothing, and no error."""

import glob
import os
from types import SimpleNamespace

import pytest

from cellbench import run, spec
from cellbench.readers import hist_quantile

HERE = os.path.dirname(os.path.abspath(__file__))
SHARES = ("engine.busy_share", "engine.handoff_share", "engine.starved_share")
NEW = SHARES + ("engine.step_ms", "batcher.drained_share",
                "batcher.drain_steps", "batcher.drain_others_ms",
                "batcher.drain_others_p99_ms", "storage.pool_wait_ms")


@pytest.fixture(autouse=True)
def one_chip(monkeypatch):
    """As the cells run: the test process has 8 virtual devices."""
    from cubefs_tpu.codec import batcher

    monkeypatch.delenv("CUBEFS_TRACE", raising=False)
    monkeypatch.setattr(batcher.DEFAULT, "dp_enabled", False)


@pytest.mark.parametrize("cell, seconds", [("put-small", 3.0),
                                          ("disk-repair-2disk", 4.0)])
def test_a_tiny_cell_reports_every_new_metric_of_its_own(cell, seconds):
    result = run.run_cell(
        cell, 7, seconds, True, device_checks=False,
        traffic_path=os.path.join(HERE, "traffic", f"{cell}.json"))
    assert result["correct"] is True, result["detail"]
    mine = [e["name"] for e in spec.metric_entries(
        spec.load_benchmark(), cell, "per_layer")
        if e["name"].split("-")[0] in NEW]
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert len(mine) >= 3 and set(mine) <= set(m), sorted(set(mine) - set(m))
    tag = mine[0][len(mine[0].split("-")[0]):]
    shares = [m[s + tag] for s in SHARES]
    assert all(0 <= s <= 100 for s in shares)
    assert sum(shares) == pytest.approx(100.0, abs=1.0)
    assert m["engine.busy_share" + tag] > 0  # a step ran in the window
    if cell == "put-small":
        assert len(mine) == 9
        # every step from inside: a mean over the window's steps
        assert 0 < m["engine.step_ms-small"] < 1e3 * seconds
        assert m["storage.pool_wait_ms-small"] >= 0
        assert 0 < m["batcher.drained_share-small"] <= 100
        assert m["batcher.drain_steps-small"] >= 1
        assert m["batcher.drain_others_ms-small"] >= 0
        assert m["batcher.drain_others_p99_ms-small"] >= 0


def _cell(buckets: dict[str, float], labels=(("part", "others"),)):
    """A window's registry delta holding one histogram's bucket series
    (cumulative, as the exposition text has them)."""
    reg = {("h_seconds_bucket", frozenset(labels + (("le", le),))): v
           for le, v in buckets.items()}
    return SimpleNamespace(registry=reg)


BOUNDS = {"0.01": 10.0, "0.02": 60.0, "0.05": 90.0, "0.1": 100.0,
          "+Inf": 100.0}


@pytest.mark.parametrize("q, want", [
    (0.5, 0.018),  # rank 50: 40 of the 50 in (0.01, 0.02]
    (0.99, 0.095),  # rank 99: 9 of the 10 in (0.05, 0.1]
    (0.05, 0.005),  # rank 5: half of the first bucket, from 0
    (0.6, 0.02),  # a rank on a bound reads the bound
])
def test_hist_quantile_interpolates_inside_the_ranks_bucket(q, want):
    got = hist_quantile.read(_cell(BOUNDS), "h_seconds", q,
                             labels={"part": "others"})
    assert got == pytest.approx(want)
    assert hist_quantile.read(_cell(BOUNDS), "h_seconds", q,
                              scale=1000.0) == pytest.approx(1000 * want)


@pytest.mark.parametrize("buckets, labels, want", [
    ({"0.01": 19.0, "+Inf": 19.0}, {}, None),  # under 20 samples
    ({"0.01": 5.0, "0.02": 5.0, "+Inf": 25.0}, {}, 0.02),  # past the bounds
    ({"0.01": 0.0, "0.02": 30.0, "+Inf": 30.0}, {"part": "own"}, None),
    ({}, {}, None),  # the program has no such histogram
], ids=["few_samples", "past_last_bound", "other_labels", "no_series"])
def test_hist_quantile_reads_nothing_it_cannot_stand_behind(
        buckets, labels, want):
    got = hist_quantile.read(_cell(buckets), "h_seconds", 0.99, labels=labels)
    assert got == (want if want is None else pytest.approx(want))


def test_hist_quantile_sums_the_series_that_carry_the_labels():
    cell = _cell({"0.01": 10.0, "0.02": 20.0, "+Inf": 20.0},
                 labels=(("part", "others"), ("op", "encode")))
    cell.registry.update(_cell(
        {"0.01": 0.0, "0.02": 20.0, "+Inf": 20.0},
        labels=(("part", "others"), ("op", "apply"))).registry)
    # 40 samples, 10 under 0.01: the median is a third into (0.01, 0.02]
    assert hist_quantile.read(cell, "h_seconds", 0.5,
                              labels={"part": "others"}) \
        == pytest.approx(0.01 + 0.01 / 3)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_series_leaves_the_metric_out(name):
    """The parent commit under this PR's benchmark files: its registry
    has none of the new series, the reader returns nothing and the
    result line leaves the metric out."""
    sp = spec.metric_spec("per_layer", name)
    parent = SimpleNamespace(registry={
        ("cubefs_codec_batch_steps_total",
         frozenset({("op", "encode"), ("engine", "tpu")})): 12.0})
    assert spec.reader(sp["reader"]).read(parent, **sp["params"]) is None


def test_every_new_layer_file_is_read_by_an_entry_and_nothing_else_moved():
    bench = spec.load_benchmark()
    bases = {e["name"].split("-")[0] for e in bench["per_layer"]}
    files = {os.path.basename(p)[:-5] for p in glob.glob(
        os.path.join(spec.HERE, "layers", "*.json"))}
    assert set(NEW) <= bases and set(NEW) <= files
    assert len(bench["per_layer"]) <= 128  # the contract's cap
    for e in bench["per_layer"]:
        if e["name"].split("-")[0] in NEW:
            assert e["source"] in ("program_counter", "program_span")
            assert e["layer"] in ("engine call", "admission", "storage")
