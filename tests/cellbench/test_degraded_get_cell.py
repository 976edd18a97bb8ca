"""Tier-1 (CPU) coverage of the cell ``degraded-get`` at its tiny traffic
file: the ``degraded_gets`` generator fills, loses the fullest disk and
GETs the objects that lost a data unit with it, with the front door's
``ready`` as the only warm-up; what it checks, and what the cell's
per-layer entries read."""

import os

import pytest

from cellbench import run, spec
from cellbench.generators import degraded_gets

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "traffic", "degraded-get.json")


@pytest.fixture(autouse=True)
def one_chip(monkeypatch):
    """The test process has 8 virtual devices and a dp-sharded step
    records no engine phase: one chip, as the cell runs."""
    from cubefs_tpu.codec import batcher

    monkeypatch.setattr(batcher.DEFAULT, "dp_enabled", False)


def run_tiny(seed: int, trace: bool, seconds: float = 2.0) -> dict:
    return run.run_cell("degraded-get", seed, seconds, trace,
                        device_checks=False, traffic_path=TINY)


def test_the_cell_decodes_every_get_and_is_correct():
    result = run_tiny(7, True)
    detail = result["detail"]
    assert result["correct"] is True, detail
    assert detail["device_faults"] == [] and detail["checks"]["faults"] == []
    checks, notes = detail["checks"], detail["notes"]
    assert checks["gets_in_window"] > 0
    assert checks["global_reconstructs"] >= checks["blobs_in_window"] > 0
    assert checks["units_checked"] >= checks["lost_indexes_checked"] >= 1
    assert checks["stripes_checked"] == 1
    assert checks["decode_steps"] >= 1
    assert 1 <= checks["decode_stripes_per_step"] <= 8
    assert notes["backlog"]["objects"] > 0 and notes["ready"]["steps"] > 0
    assert detail["compiles_window"]["compiles"] == 0
    listed = {m["name"] for m in spec.metric_entries(
        spec.load_benchmark(), "degraded-get", "per_layer")}
    m = {k: v["value"] for k, v in result["metrics"].items()}
    # all but the roofline, which needs the chip's trace
    assert set(m) == listed - {"gf_apply_roofline-small"}
    assert m["access.get_assemble_share-small"] > 0
    assert m["access.reconstruct_share-small"] > 0
    assert m["dispatch.compiles_in_window-small"] == 0
    assert m["dispatch.device_step_share-small"] == 100


def test_the_end_to_end_metrics_are_op_rate_and_setup_s():
    result = run_tiny(8, False)
    assert result["correct"] is True, result["detail"]
    assert set(result["metrics"]) == {"op_rate", "setup_s"}
    assert result["metrics"]["op_rate"]["value"] > 0


def test_a_wrong_byte_in_a_get_is_not_correct(monkeypatch):
    from cubefs_tpu.blob.access import AccessHandler

    real = AccessHandler.get

    def rotten(self, loc, **kw):
        data = real(self, loc, **kw)
        return bytes([data[0] ^ 1]) + data[1:]

    monkeypatch.setattr(AccessHandler, "get", rotten)
    result = run_tiny(9, False, seconds=0.5)
    assert result["correct"] is False
    faults = result["detail"]["checks"]["faults"]
    assert any("did not return the payload" in f for f in faults)
    assert any("differs from the reference decode's" in f for f in faults)


def test_a_decode_that_differs_from_the_reference_is_not_correct(
        monkeypatch):
    """The reference decode is held to the reference stripe: a decode
    that reads a survivor wrong fails the cell."""
    real = degraded_gets.reference_decode.decode

    def off(units, n, m):
        out = real(units, n, m)
        out[0, 0] ^= 1
        return out

    monkeypatch.setattr(degraded_gets.reference_decode, "decode", off)
    result = run_tiny(10, False, seconds=0.5)
    assert result["correct"] is False
    assert any("differs from the reference stripe" in f
               for f in result["detail"]["checks"]["faults"])


def test_a_disk_that_holds_no_data_unit_is_refused(monkeypatch):
    """No GET would decode: set-up refuses instead of measuring healthy
    reads."""
    monkeypatch.setattr(degraded_gets, "_lost_index",
                        lambda dep, disk, vid: None)
    with pytest.raises(RuntimeError, match="no GET would decode"):
        run_tiny(11, False, seconds=0.5)
