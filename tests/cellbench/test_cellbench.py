"""Tier-1 (CPU) coverage of the cell benchmark (cellbench/): every
cell's generator at a tiny traffic file through the library entry (the
CLI refuses without a TPU), the arithmetic that turns records and traces
into metrics, and a lint of BENCHMARK.json against the contract's
character and file rules. No topology call, no chip."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from cellbench import (devtrace, reference, registry, roofline, run, spec,
                       stats)

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = tuple(w["name"] for w in spec.load_benchmark()["workloads"])
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
# the end-to-end metric an entry moves names its group of cells, and the
# group the tags its entries' names may carry (repair.* carry none)
GROUPS = {"put_rate": "put", "op_rate": "small", "put_p99_ms": "small",
          "repair_rate": "repair"}
TAGS = {"put": {""}, "small": {"-small"}, "repair": {"-repair", ""}}
# five entries that tests outside the benchmark's paths pin as they stood
# before the fold (tests/test_put_fork.py, tests/test_repair_lease.py): a
# benchmark PR may not edit those; the PR that renames them there folds these
PINNED_OUTSIDE = {"access.early_write_share", "access.early_write_share-cont",
                  "repair.shared_read_share-2disk",
                  "dispatch.compiles_in_window-2disk",
                  "dispatch.device_step_share-2disk"}


def tiny(cell: str) -> str:
    return os.path.join(HERE, "traffic", f"{cell}.json")


def run_tiny(cell: str, trace: bool, seconds: float = 1.5) -> dict:
    return run.run_cell(cell, 7, seconds, trace, device_checks=False,
                        traffic_path=tiny(cell))


# ------------------------------------------------ cells, end to end

@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end_line(cell):
    bench = spec.load_benchmark()
    result = run_tiny(cell, trace=False)
    detail = result.pop("detail")
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True, detail["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    want = {m["name"] for m in spec.metric_entries(bench, cell, "end_to_end")}
    assert set(result["metrics"]) == want
    assert "setup_s" in want and len(want) >= 2
    for name, m in result["metrics"].items():
        assert m["value"] > 0 and UNIT.match(m["unit"]), name
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    json.dumps(result)  # the last line must serialise
    assert detail["compiles_window"]["compiles"] >= 0


@pytest.mark.parametrize("cell", CELLS)
def test_cell_traced_line(cell):
    bench = spec.load_benchmark()
    result = run_tiny(cell, trace=True, seconds=2.0)
    detail = result.pop("detail")
    assert set(result) == RESULT_KEYS | {"breakdown"}
    assert result["correct"] is True, detail["checks"]
    allowed = {m["name"] for m in spec.metric_entries(bench, cell,
                                                      "per_layer")}
    assert result["metrics"] and set(result["metrics"]) <= allowed
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    # a reader that finds nothing to read is left out, not reported as 0
    assert not any(k.startswith("gf_apply_roofline")
                   for k in result["metrics"])
    # the engine wrappers came off the process-wide engine again
    from cubefs_tpu.codec.engine import get_engine

    assert "encode_parity" not in vars(get_engine("tpu"))


@pytest.mark.parametrize("cell", CELLS)
def test_an_entry_lists_a_cell_only_where_its_reader_finds_its_series(
        cell, monkeypatch):
    """An entry that lists a cell in which it reads nothing stands as
    ``null`` in the ledger: every per-layer entry of a cell prints a
    number in the cell's tiny traced run, but for the two rooflines (no
    device trace on the CPU) and a quantile that wants 20 samples."""
    from cubefs_tpu.codec import batcher, engine

    # one chip, as the cell runs, and every engine call taken apart (a
    # tiny window has few steps; the chip's has one in PHASE_EVERY_S)
    monkeypatch.setattr(batcher.DEFAULT, "dp_enabled", False)
    monkeypatch.setattr(engine, "PHASE_EVERY_S", 0.0)
    result = run_tiny(cell, trace=True, seconds=3.0)
    assert result["correct"] is True, result["detail"]["checks"]
    listed = {m["name"] for m in spec.metric_entries(
        spec.load_benchmark(), cell, "per_layer")}
    silent = {n for n in listed - set(result["metrics"])
              if not n.split("-")[0].endswith("_roofline")
              and not n.startswith("batcher.drain_others_p99_ms")}
    assert not silent, sorted(silent)


def test_closed_loop_get_path_compares_every_get():
    """The GET side of the general generator (no listed cell issues a
    GET yet: PERF.md section 7, mix-small): Zipf keys over prefilled
    objects, every reply compared. A hedged GET decodes with a matrix
    nobody warmed, so this run may compile in its window; then it is
    not ``correct``, and for that reason alone."""
    result = run.run_cell("put-small", 7, 1.5, False, device_checks=False,
                          traffic_path=tiny("closed-loop-gets"))
    detail = result["detail"]
    checks = detail["checks"]
    assert checks["faults"] == [] and result["failed"] == 0
    assert checks["gets_compared"] > 0
    assert detail["ops_in_window"].get("get", 0) > 0
    assert all("compiled inside the window" in f
               for f in detail["device_faults"])
    assert result["correct"] is (not detail["device_faults"])


def test_max_ops_is_a_fixed_amount_of_work():
    """``max_ops``: the clients share one count, the run ends with the
    last reply, and the rate is over the time the work took."""
    result = run.run_cell("ingest-large", 7, 30.0, False,
                          device_checks=False,
                          traffic_path=tiny("ingest-large-capped"))
    detail = result.pop("detail")
    assert result["correct"] is True, detail
    assert detail["ops_in_window"] == {"put": 5} == {"put":
                                                     result["attempted"]}
    assert detail["drain_after_window_s"] < -10  # ended with the work
    size = spec.load_json(tiny("ingest-large-capped"))["sizes"][0]["bytes"]
    took = 5 * size / 1e6 / result["metrics"]["put_rate"]["value"]
    assert 0 < took < 30.0 + detail["drain_after_window_s"]


# ------------------------------------------------ honesty about the device

def test_a_step_served_off_the_device_fails_correct(monkeypatch):
    """``engine._dispatch`` serves from the next engine when ``tpu``
    fails, and in a cell the host bounds that can be faster: a run with
    any codec step off the configuration's engine is not ``correct``,
    in the end-to-end mode too. Planted with the program's own drill."""
    monkeypatch.setenv("CUBEFS_CODEC_DEAD", "tpu")
    result = run_tiny("put-small", trace=False, seconds=0.5)
    detail = result["detail"]
    assert result["correct"] is False
    assert detail["checks"]["faults"] == []  # the bytes are right
    assert "tpu" not in detail["steps_by_engine"]["window"]
    assert sum(detail["steps_by_engine"]["window"].values()) > 0
    text = " ".join(detail["device_faults"])
    assert "served off the configuration's engine 'tpu'" in text
    assert "no codec step of the window was served by 'tpu'" in text


def test_device_faults_name_compiles_and_foreign_steps():
    series = {(run.STEPS, frozenset({("op", "encode"),
                                     ("engine", "tpu")})): 9.0,
              (run.STEPS, frozenset({("op", "apply"),
                                     ("engine", "cpp")})): 0.0}
    cell = run.Cell({"deployment": {"engine": "tpu"}}, {}, 1, 4.0, False)
    cell.registry_setup, cell.registry = dict(series), dict(series)
    cell.compiles_window = {"compiles": 0}
    assert run.steps_by_engine(series) == {"tpu": 9}
    assert run.device_faults(cell) == []
    cell.compiles_window = {"compiles": 2}
    assert run.device_faults(cell) == [
        "2 programs compiled inside the window"]
    cell.compiles_window = {"compiles": 0}
    cell.registry_setup[(run.STEPS, frozenset({("op", "apply"),
                                               ("engine", "cpp")}))] = 1.0
    assert "set-up" in run.device_faults(cell)[0]
    cell.registry = {}
    assert any("no codec step" in f for f in run.device_faults(cell))


def test_the_port_is_held_to_the_configuration_file():
    import copy

    from cellbench.deployment import hold_to_file
    from cubefs_tpu.blob.access import AccessConfig

    config = spec.load_json("cellbench/configs/access-tpu-1az.json")
    hold_to_file(AccessConfig(engine="tpu"), config["deployment"],
                 config["codemodes"])
    for key, value in (("put_quorum", 14), ("max_object_bytes", 1 << 20)):
        wrong = copy.deepcopy(config["codemodes"])
        wrong["EC12P4"][key] = value
        with pytest.raises(RuntimeError, match="EC12P4"):
            hold_to_file(AccessConfig(engine="tpu"), config["deployment"],
                         wrong)
    with pytest.raises(RuntimeError, match="blob_size"):
        hold_to_file(AccessConfig(engine="tpu"),
                     dict(config["deployment"], blob_size=4 << 20),
                     config["codemodes"])


def test_a_cell_tagged_metric_reads_its_base_entry():
    base = spec.metric_spec("per_layer", "engine.step_ms")
    assert spec.metric_spec("per_layer", "engine.step_ms-small") == base
    assert spec.metric_spec("per_layer", "engine.step_ms-repair") == base
    # a file of the metric's own name wins over the base
    own = spec.metric_spec("per_layer", "batcher.stripes_per_step-repair")
    assert own["params"]["labels"] == {"op": "apply"}
    assert spec.metric_spec("per_layer", "batcher.stripes_per_step")[
        "params"]["labels"] == {"op": "encode"}
    with pytest.raises(FileNotFoundError, match="nope"):
        spec.metric_spec("per_layer", "nope-small")
    # no copy of a base entry is kept beside it
    layers = os.path.join(spec.HERE, "layers")
    for f in os.listdir(layers):
        stem = f[:-len(".json")]
        if "-" in stem:
            base_file = os.path.join(layers, stem.split("-", 1)[0] + ".json")
            if os.path.exists(base_file):
                assert spec.load_json(os.path.join(layers, f)) != \
                    spec.load_json(base_file), f


def test_planted_wrong_shard_fails_correct(monkeypatch):
    from cellbench.deployment import Deployment

    real = Deployment.unit_call

    def rotten(self, unit, method, bid=None):
        meta, body = real(self, unit, method, bid)
        if method == "get_shard" and unit.index == 1:
            body = bytes([body[0] ^ 1]) + body[1:]
        return meta, body

    monkeypatch.setattr(Deployment, "unit_call", rotten)
    result = run_tiny("ingest-large", trace=False, seconds=0.5)
    assert result["correct"] is False
    assert any("differ" in f for f in result["detail"]["checks"]["faults"])


def test_cli_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "cellbench.run", "--workload", "ingest-large",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_unknown_workload_names_the_cells():
    with pytest.raises(KeyError, match="ingest-large"):
        spec.find_cell(spec.load_benchmark(), "nope")


# ------------------------------------------------ arithmetic

def test_percentile_matches_numpy_and_handles_edges():
    xs = list(np.random.default_rng(3).random(257))
    for q in (0, 50, 95, 99, 100):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert stats.percentile([], 99) is None
    assert stats.percentile([4.0], 99) == 4.0


def test_rate_and_interval_union():
    assert stats.rate(10.0, 4.0) == 2.5
    assert stats.rate(10.0, 0.0) is None
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6), (5.0, 5.0)]
    assert stats.union_length(iv) == pytest.approx(3.0)
    assert stats.merged(iv) == [(0.0, 2.0), (3.0, 4.0)]
    assert stats.clip(iv, 1.5, 3.25) == [(1.5, 2.0), (3.0, 3.25)]


def _cell_with_ops(ops, t0=10.0, seconds=4.0):
    cell = run.Cell({}, {}, 1, seconds, False)
    cell.t0, cell.t1, cell.ops = t0, t0 + seconds, ops
    return cell


def test_rate_and_percentile_readers_count_only_the_window():
    ops = [("put", 10.1, 10.5, 100, True), ("put", 11.0, 13.0, 100, True),
           ("put", 13.5, 14.5, 100, True),  # ends after the window
           ("put", 12.0, 12.5, 100, False),  # failed: no latency, no bytes
           ("get", 10.2, 10.3, 50, True)]
    cell = _cell_with_ops(ops)
    assert spec.reader("byte_rate").read(cell, ["put"], scale=1.0) == 50.0
    assert spec.reader("byte_rate").read(
        cell, ["put"], scale=1.0, over="last_completion") == \
        pytest.approx(200 / 3.0)
    assert spec.reader("op_rate").read(cell, ["get", "put"]) == 0.75
    assert spec.reader("op_percentile").read(cell, ["put"], 100) == \
        pytest.approx(2000.0)
    assert spec.reader("byte_rate").read(cell, ["repair_shard"]) is None


def test_registry_parse_and_delta():
    text = ('# HELP x y\n# TYPE x counter\n'
            'x_total{op="encode",engine="tpu"} 5\n'
            'x_total{op="apply",engine="cpp"} 2\n'
            'h_sum{path="blob.put",stage="total"} 1.5\nplain 3\n')
    a = registry.parse(text)
    assert registry.total(a, "x_total") == 7
    assert registry.total(a, "x_total", engine="tpu") == 5
    b = registry.parse(text.replace(" 5\n", " 9\n"))
    d = registry.delta(a, b)
    assert registry.total(d, "x_total", engine="tpu") == 4
    assert registry.total(d, "plain") == 0
    cell = _cell_with_ops([])
    cell.registry = d
    assert spec.reader("counter_share").read(
        cell, "x_total", {"engine": "tpu"}) == 100.0


# ------------------------------------------------ trace reduction

def test_trace_reduction_busy_idle_and_gap_labels():
    # one chip, a 10 s traced window; device busy 1-2, 1.5-3 (overlap),
    # 6-7; events outside the window are clipped away
    device = {"/device:TPU:0": [("fusion.1", 1.0, 2.0),
                                ("pallas_call", 1.5, 3.0),
                                ("fusion.1", 6.0, 7.0),
                                ("fusion.1", 11.0, 12.0)]}
    host = [(devtrace.WINDOW_SPAN, 0.0, 10.0),
            ("client.put", 0.0, 5.5), ("engine.call", 0.5, 3.2),
            ("storage.put_shard", 4.0, 5.0), ("client.put", 5.6, 9.0)]
    r = devtrace.reduce(device, host)
    assert r["busy_s"] == pytest.approx(3.0)
    assert r["window_s"] == pytest.approx(10.0)
    assert r["idle_share"] == pytest.approx(0.7)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(2.0)]
    assert r["device_ops"][1] == ["pallas_call", pytest.approx(1.5)]
    gaps = dict(r["idle_gaps"])
    # 0-1 lies in engine.call (innermost at 0.5s), 3-6 in
    # storage.put_shard (midpoint 4.5), 7-10 in the second client.put
    assert gaps["engine.call"] == pytest.approx(1.0)
    assert gaps["storage.put_shard"] == pytest.approx(3.0)
    assert gaps["client.put"] == pytest.approx(3.0)
    assert devtrace.gap_label(host, 9.5) == "no_benchmark_span"


def test_host_spans_move_onto_the_trace_clock_by_the_marker():
    annotated = [(devtrace.WINDOW_SPAN, 100.0, 104.0),
                 ("engine.call", 101.0, 102.0)]
    spans = [("worker.run_once", 48.0, 53.0), ("engine.call", 51.0, 52.0)]
    host = devtrace.on_trace_clock(spans, (50.0, 54.0), annotated)
    assert host[0] == (devtrace.WINDOW_SPAN, 100.0, 104.0)
    # the span that was open when the profiler started is there
    assert ("worker.run_once", 98.0, 103.0) in host
    assert devtrace.gap_label(host, 100.5) == "worker.run_once"
    assert devtrace.gap_label(host, 101.5) == "engine.call"


def test_trace_reduction_averages_chips_and_survives_no_events():
    device = {"/device:TPU:0": [("a", 0.0, 2.0)],
              "/device:TPU:1": [("a", 0.0, 1.0)]}
    r = devtrace.reduce(device, [(devtrace.WINDOW_SPAN, 0.0, 4.0)])
    assert r["chips"] == 2 and r["busy_s"] == pytest.approx(1.5)
    empty = devtrace.reduce({}, [])
    assert empty["busy_s"] == 0.0 and empty["idle_share"] is None


def test_roofline_counts_and_unknown_device():
    assert roofline.gf_apply_bytes(8, 12, 4, 699051) == 8 * 16 * 699051
    assert roofline.gf_apply_ops(1, 12, 4, 10) == 2 * 64 * 48 * 10
    peak = roofline.peaks("TPU v5 lite")
    sec, bound = roofline.least_seconds(
        [{"b": 8, "c": 12, "r": 4, "s": 699051}], peak)
    assert bound == "hbm"
    assert sec == pytest.approx(8 * 16 * 699051 / 819e9)
    with pytest.raises(KeyError, match="peaks.json"):
        roofline.peaks("TPU v9 imaginary")


# ------------------------------------------------ the reference

@pytest.mark.parametrize("n,m", [(12, 4), (6, 6), (3, 3)])
def test_reference_is_independent_and_agrees(n, m):
    from cubefs_tpu.codec.engine import get_engine
    from cubefs_tpu.ops import gf256

    src = open(reference.__file__).read()
    assert "cubefs_tpu" not in src.split('"""', 2)[2]
    assert np.array_equal(reference.encode_matrix(n, n + m),
                          gf256.encode_matrix(n, n + m))
    blob = np.random.default_rng([n, m]).bytes(50_001)
    stripe = reference.stripe(blob, n, m, 2048)
    assert stripe.shape == (n + m, max(-(-50_001 // n), 2048))
    assert np.array_equal(
        stripe[n:], get_engine("numpy").encode_parity(stripe[:n], m))
    assert stripe[:n].tobytes()[:50_001] == blob


# ------------------------------------------------ BENCHMARK.json lint

def test_benchmark_json_meets_the_contracts_static_rules():
    bench = spec.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    # a full check with 24 cells must fit the driver's budget
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    paths = bench["paths"]
    assert all(PATH.match(p) and os.path.isdir(os.path.join(spec.ROOT, p))
               for p in paths)
    for word in bench["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/")
    cfg_names = [c["name"] for c in bench["configs"]]
    cells = {w["name"]: w for w in bench["workloads"]}
    assert len(set(cfg_names)) == len(cfg_names)
    assert len(cells) == len(bench["workloads"]) >= 2
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in paths)
        body = spec.load_json(c["file"])
        assert body["source"] == c["source"]
        assert set(c["reduced"]) == set(body["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert body["guarantees"] and body["deployment"]["engine"] == "tpu"
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfg_names and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        traffic = spec.load_json(spec.traffic_file(w["traffic"]))
        assert os.path.exists(os.path.join(
            spec.HERE, "generators", traffic["generator"] + ".py"))
        assert os.path.exists(tiny(w["name"]))
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(cells) // 2)
    names = set()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for group, keys in (("end_to_end", {"name", "unit", "better", "bound",
                                        "source"}),
                        ("per_layer", {"name", "unit", "better", "source",
                                       "layer", "moves"})):
        for m in bench[group]:
            assert set(m) - {"workloads"} == keys, m["name"]
            assert NAME.match(m["name"]) and m["name"] not in names
            names.add(m["name"])
            assert UNIT.match(m["unit"])
            assert m["better"] in ("lower", "higher")
            assert set(m.get("workloads", cells)) <= set(cells)
            sp = spec.metric_spec(group, m["name"])
            assert hasattr(spec.reader(sp["reader"]), "read")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert e2e["setup_s"].get("workloads") is None
    for m in bench["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200
        moved_in = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", cells)) <= moved_in, m["name"]
        if m["name"].split("-")[0].endswith("_roofline"):
            assert m["unit"] == "%"
    # one entry a metric and a moved end-to-end metric: a cell joins its
    # group's lists, it brings no tagged copy of an entry
    assert set(e2e) - {"setup_s"} == set(GROUPS)
    taken = set()
    assert len(bench["per_layer"]) <= 90
    for m in bench["per_layer"]:
        if m["name"] in PINNED_OUTSIDE:
            assert len(m["workloads"]) == 1
            continue
        base, group = m["name"].split("-")[0], GROUPS[m["moves"]]
        assert m["name"][len(base):] in TAGS[group], m["name"]
        assert (base, group) not in taken, m["name"]
        taken.add((base, group))
    # every data file of a per-layer metric is read by an entry
    layers = os.path.join(spec.HERE, "layers")

    def file_of(name):  # spec.metric_spec: its own file, else its base's
        own = os.path.exists(os.path.join(layers, name + ".json"))
        return name if own else name.split("-")[0]

    assert {f[:-len(".json")] for f in os.listdir(layers)} == {
        file_of(m["name"]) for m in bench["per_layer"]}
    for name, w in cells.items():
        mine = [m for m in bench["end_to_end"]
                if name in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert spec.metric_entries(bench, name, "per_layer")
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) \
        <= 64 << 10
    # every file under the benchmark's paths is named from a name's letters
    for p in paths:
        for d, dirs, files in os.walk(os.path.join(spec.ROOT, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), spec.ROOT)
                assert PATH.match(rel), rel
