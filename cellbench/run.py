"""Run one cell once.

    python3 -m cellbench.run --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Set-up (counted as ``setup_s``, from process start to the start of the
window): native runtime (built only when its source hash changed),
deployment, payloads and fill from the seed, every shape of the cell's
traffic warmed. Then the generator offers its load for ``--seconds``;
nothing compiles in the window (``dispatch.compiles_in_window`` counts).
After the window the generator checks the outputs against
``cellbench/reference.py``. The last stdout line is the result object;
the line before it (``{"detail": ...}``) carries sample counts and
diagnostics nobody parses.

``--trace 0`` reports the cell's end-to-end metrics with none of the
benchmark's spans installed and no profiler. ``--trace 1`` installs the
spans, profiles a few seconds in the middle of the window and reports
the per-layer metrics, the device's busy time and the breakdown.

The CLI needs a TPU (``ops.require_tpu()``) and never falls back. The
tests call ``run_cell(..., device_checks=False)`` at tiny traffic files.

In both modes a run is ``correct`` only if the device served it: every
codec step of set-up and window stamped with the configuration's engine
(``codec/engine._dispatch`` quarantines a failing engine and serves from
the next one for the life of the process, which can be *faster* in a
cell the host bounds), at least one step in the window, and nothing
compiled inside it. ``device_faults`` on the detail line says which.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

from . import spec  # noqa: E402

TRACE_START_SHARE = 0.35  # of the window; or the traffic's "trace_start_s"
TRACE_SECONDS = 4.0  # a traffic file may say "trace_seconds"
STEPS = "cubefs_codec_batch_steps_total"  # {op, engine that served}


class Cell:
    """What a generator and a reader get to see of one run."""

    def __init__(self, config, traffic, seed, seconds, trace):
        self.config = config  # the configuration file
        self.traffic = traffic  # the traffic file
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.dep = None
        self.spans = None  # hostspans.SpanLog in a traced run
        self.state = None  # the generator's own
        self.ops: list[tuple] = []  # (kind, t0, t1, bytes, ok) in the window
        self.t0 = self.t1 = 0.0  # the window on time.perf_counter()
        self.t_end = 0.0  # t1, or when the generator ran out of work
        self.setup_s = 0.0
        self.registry = {}  # registry.delta over the window
        self.registry_setup = {}  # ... and over set-up
        self.compiles_window = {}
        self.compiles_setup = {}
        self.devtrace = None  # devtrace.reduce(...) in a traced run
        self.trace_span = None  # (t0, t1) of the profiled part, host clock
        self.device_kind = ""
        self.notes: dict = {}

    def window_ops(self, *kinds) -> list[tuple]:
        """Operations that completed inside the window."""
        return [o for o in self.ops
                if o[0] in kinds and self.t0 <= o[2] <= self.t1]


class _Tracer(threading.Thread):
    """Profiles [start, start + length) of the window into ``out_dir``."""

    def __init__(self, cell: Cell, out_dir: str):
        super().__init__(name="cellbench-tracer", daemon=True)
        self.cell, self.out_dir = cell, out_dir
        length = float(cell.traffic.get("trace_seconds", TRACE_SECONDS))
        self.length = min(length, 0.5 * cell.seconds)
        self.offset = float(cell.traffic.get(
            "trace_start_s", TRACE_START_SHARE * cell.seconds))
        self.error: BaseException | None = None

    def run(self) -> None:
        import jax.profiler as prof

        try:
            time.sleep(max(0.0, self.cell.t0 + self.offset
                           - time.perf_counter()))
            opts = prof.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            opts.enable_hlo_proto = False
            prof.start_trace(self.out_dir, profiler_options=opts)
            try:
                t0 = time.perf_counter()
                with prof.TraceAnnotation("cellbench:trace_window"):
                    time.sleep(self.length)
                self.cell.trace_span = (t0, time.perf_counter())
            finally:
                prof.stop_trace()
        except BaseException as e:  # read by run_cell after join()
            self.error = e


def _device(devs, cell: Cell) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    if cell.devtrace is not None:
        out["busy_s"] = cell.devtrace["busy_s"]
        out["window_s"] = cell.devtrace["window_s"]
    return out


def steps_by_engine(series: dict) -> dict[str, int]:
    """Codec steps of a registry delta, by the engine that served them."""
    out: dict[str, int] = {}
    for (name, labels), v in series.items():
        if name == STEPS and v > 0:
            engine = dict(labels).get("engine", "")
            out[engine] = out.get(engine, 0) + int(v)
    return out


def device_step_share(cell: Cell) -> float | None:
    """% of the window's codec steps the configuration's engine served."""
    by = steps_by_engine(cell.registry)
    if not by:
        return None
    return 100.0 * by.get(cell.config["deployment"]["engine"], 0) \
        / sum(by.values())


def device_faults(cell: Cell) -> list[str]:
    """Why this run's numbers are not the device path's, if they are not."""
    want = cell.config["deployment"]["engine"]
    faults = []
    for part, series in (("set-up", cell.registry_setup),
                         ("the window", cell.registry)):
        off = {e: n for e, n in steps_by_engine(series).items() if e != want}
        if off:
            faults.append(f"codec steps of {part} were served off the "
                          f"configuration's engine {want!r}: {off}")
    if not steps_by_engine(cell.registry).get(want):
        faults.append(f"no codec step of the window was served by {want!r}")
    n = cell.compiles_window.get("compiles", 0)
    if n:
        faults.append(f"{n} programs compiled inside the window")
    return faults


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             device_checks: bool = True,
             traffic_path: str | None = None) -> dict:
    """One run of one cell; returns the result object plus ``detail``."""
    t_start = T_PROCESS if device_checks else time.perf_counter()
    bench = spec.load_benchmark()
    entry, cfg_entry = spec.find_cell(bench, workload)
    config = spec.load_json(cfg_entry["file"])
    traffic = spec.load_json(traffic_path
                             or spec.traffic_file(entry["traffic"]))
    gen = spec.generator(traffic["generator"])

    import jax

    from cubefs_tpu import ops
    from cubefs_tpu.runtime import build as rt_build

    from . import devtrace, hostspans, registry
    from .deployment import CompileClock, Deployment

    devs = ops.require_tpu() if device_checks else jax.devices()
    if len(devs) < int(entry["chips"]):
        raise RuntimeError(f"cell {workload!r} needs {entry['chips']} "
                           f"chips, JAX has {len(devs)}")
    t_build = time.perf_counter()
    rt_build.load()
    t_built = time.perf_counter()

    cell = Cell(config, traffic, seed, seconds, trace)
    cell.device_kind = devs[0].device_kind
    clock = CompileClock()
    workdir = tempfile.mkdtemp(prefix="cellbench-")
    restore = None
    try:
        before_setup = registry.snapshot()
        cell.dep = Deployment(workdir, config["deployment"],
                              config["codemodes"])
        if trace:
            cell.spans = hostspans.SpanLog()
            restore = hostspans.instrument(cell.dep, cell.spans)
        gen.setup(cell)
        cell.compiles_setup = clock.mark()
        before = registry.snapshot()
        cell.registry_setup = registry.delta(before_setup, before)
        tracer = None
        if trace:
            tracer = _Tracer(cell, tempfile.mkdtemp(dir=workdir))
        cell.t0 = time.perf_counter()
        cell.t1 = cell.t0 + cell.seconds
        cell.setup_s = cell.t0 - t_start
        if tracer is not None:
            tracer.start()
        gen.run(cell)
        t_drained = time.perf_counter()
        cell.t_end = min(cell.t1, t_drained)
        if tracer is not None:
            tracer.join()
            if tracer.error is not None:
                raise tracer.error
        cell.registry = registry.delta(before, registry.snapshot())
        after = clock.mark()
        cell.compiles_window = {k: after[k] - cell.compiles_setup[k]
                                for k in after}
        if tracer is not None:
            loaded = devtrace.load(tracer.out_dir)
            cell.devtrace = devtrace.reduce(
                loaded["device"], devtrace.on_trace_clock(
                    cell.spans.all(), cell.trace_span, loaded["host"]))
            cell.notes["trace_lines"] = loaded["lines"]
        device = _device(devs, cell)
        correct, checks = gen.verify(cell)
    finally:
        if restore is not None:
            restore()
        clock.close()
        if cell.dep is not None:
            cell.dep.stop()
        shutil.rmtree(workdir, ignore_errors=True)

    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.metric_entries(bench, workload, group):
        sp = spec.metric_spec(group, m["name"])
        value = spec.reader(sp["reader"]).read(cell, **sp.get("params", {}))
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    in_window = [o for o in cell.ops if cell.t0 <= o[2] <= cell.t1]
    failed = sum(1 for o in cell.ops if not o[4])
    off_device = device_faults(cell)
    # a failure while the last requests drain is still a failure
    result = {"correct": bool(correct and not failed and not off_device),
              "attempted": sum(1 for o in cell.ops
                               if o[2] <= cell.t1 or not o[4]),
              "failed": failed, "metrics": metrics, "device": device}
    if cell.devtrace is not None:
        result["breakdown"] = {
            "device_ops": cell.devtrace["device_ops"],
            "idle_gaps": cell.devtrace["idle_gaps"]}
    counts: dict[str, int] = {}
    per_second = [0] * (int(cell.seconds) + 1)
    for o in in_window:
        counts[o[0]] = counts.get(o[0], 0) + 1
        per_second[int(o[2] - cell.t0)] += 1
    result["detail"] = {
        "workload": workload, "seed": cell.seed, "seconds": cell.seconds,
        "trace": int(cell.trace), "ops_in_window": counts,
        "ops_per_second": per_second,
        "drain_after_window_s": t_drained - cell.t1,
        "native_build_s": t_built - t_build,
        "setup_s": cell.setup_s, "compiles_setup": cell.compiles_setup,
        "compiles_window": cell.compiles_window,
        "steps_by_engine": {"setup": steps_by_engine(cell.registry_setup),
                            "window": steps_by_engine(cell.registry)},
        "device_step_share": device_step_share(cell),
        "device_faults": off_device, "checks": checks,
        "notes": cell.notes,
        "device_trace": None if cell.devtrace is None else {
            k: cell.devtrace[k] for k in
            ("busy_s", "window_s", "idle_share", "chips",
             "inside_window_share")}}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="cellbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    result = run_cell(a.workload, a.seed, a.seconds, bool(a.trace))
    print(json.dumps({"detail": result.pop("detail")}), flush=True)
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
