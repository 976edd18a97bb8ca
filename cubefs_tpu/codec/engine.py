"""Codec engines: the legs that do the GF(2^8) shard math, and the one
order a step degrades through when a leg is lost.

The reference hard-wires one SIMD CPU engine (klauspost/reedsolomon behind
blobstore/common/ec/encoder.go); here the engine is named per caller
(``AccessConfig.engine``; ``CUBEFS_TPU_EC_ENGINE`` where none is).
Engines expose the raw shard-math primitives; cubefs_tpu/codec/encoder.py
layers the reference's Encoder semantics (Split/Verify/Reconstruct/...)
on top.

Four legs, in the order of ``_FALLBACK_CHAIN``:
  * ``tpu`` — the device engine: JAX programs over the GF(2) bit
    expansion, on whatever backend jax selects (TPU on hardware, CPU in
    tests). ``ops/rs_kernel.plan`` picks the fused Pallas program or the
    jnp bit-matmul per shape, from what it can observe; every cell of
    the benchmark pins this leg.
  * ``cpp`` — native SIMD GF engine (cubefs_tpu/runtime), there when the
    shared library has been built.
  * ``numpy-xor`` — a coefficient matrix lowered once into a CSE'd,
    cache-blocked XOR schedule (ops/xorprog.py) and replayed word-wide;
    bit-identical to ``numpy`` and several times as fast. The host leg
    of a machine without the native library.
  * ``numpy`` — table-driven GF(2^8); the golden of every bit-identity
    test and the leg that is always there. Named, it is served as named.

``auto`` routes each call by size through a measured table
(``engine_for``); unmeasured on the chip, see ROADMAP D5. A leg that
raises a device-loss error is quarantined and the step is served by the
next one down; ``CUBEFS_CODEC_DEAD`` declares legs lost for a drill.
"""

from __future__ import annotations

import contextvars
import logging
import math
import os
import time
from typing import Protocol

import numpy as np

from ..ops import gf256, progcache, rs_kernel, xorprog
from ..utils import hostmem, metrics
from ..utils import trace as tracelib

_log = logging.getLogger("cubefs.codec")


class Engine(Protocol):
    """Shard-level GF(2^8) math over (..., B, S) uint8 arrays."""

    name: str

    def matrix_apply(self, coeff: np.ndarray, shards: np.ndarray) -> np.ndarray:
        """(R, C) GF matrix x (..., C, S) shards -> (..., R, S)."""

    def encode_parity(self, data: np.ndarray, n_parity: int) -> np.ndarray:
        """(..., N, S) data -> (..., M, S) parity."""


class NumpyEngine:
    name = "numpy"

    def matrix_apply(self, coeff: np.ndarray, shards: np.ndarray) -> np.ndarray:
        coeff = np.asarray(coeff, dtype=np.uint8)
        shards = np.asarray(shards, dtype=np.uint8)
        if shards.ndim == 2:
            return gf256.gf_matmul(coeff, shards)
        # one table-gather pass for the whole batch: fold the batch axis
        # into the byte axis ((.., C, S) -> (C, B*S)) so gf_matmul's
        # per-column gather runs once per coefficient column instead of
        # once per stripe — the dominant cost of the table path
        lead, (c, s) = shards.shape[:-2], shards.shape[-2:]
        flat = np.ascontiguousarray(
            np.moveaxis(shards.reshape(-1, c, s), 1, 0)).reshape(c, -1)
        out = np.moveaxis(
            gf256.gf_matmul(coeff, flat).reshape(coeff.shape[0], -1, s), 0, 1)
        return np.ascontiguousarray(out).reshape(*lead, coeff.shape[0], s)

    def encode_parity(self, data: np.ndarray, n_parity: int) -> np.ndarray:
        return self.matrix_apply(gf256.parity_matrix(data.shape[-2], n_parity), data)


_PHASE_SPANS = {p: f"{tracelib.PROFILE_PREFIX}codec.{p}"
                for p in ("matrix", "h2d", "launch", "wait", "d2h")}
# A phased call waits twice where the bare call does not, and every
# wait hands the GIL to another thread: with every step phased a step of
# small PUTs took 9.1 ms instead of 6.6, 240 times a second (PERF.md
# section 6, PR 26). So an engine phases at most one call in this many
# seconds — nearly every step of large PUTs and repairs, one in ~60 of
# small PUTs.
PHASE_EVERY_S = 0.25

# Column blocks of a large result in flight to the host ahead of the
# one being copied into its kept buffer (PERF.md section 6: the micro-runs
# that chose column blocks through host memory).
D2H_AHEAD = 4

# Payload columns of the step an engine call serves, where its caller
# knows them (the batcher sets it round a step, codec/batcher.py): a
# result's columns past it are pad, and a large result's blocks that lie
# wholly in the pad are never brought back — those columns of the host
# array are left as they were. The engine methods keep their signature.
STEP_WIDTH: contextvars.ContextVar[int | None] = contextvars.ContextVar(
    "cubefs_codec_step_width", default=None)


def _splitter(shape: tuple, order: tuple):
    """(one jitted program that cuts a device result of `shape` into
    blocks of its last (column) axis, their column bounds). `order` is
    the result's layout, major to minor: each block comes out with its
    axes in that order, so the cut is a strided copy in the layout the
    device already keeps and the block a plain one (the TPU keeps a
    (B, R, S) uint8 result rows-major: a cut along its stripes would be
    a relayout). Blocks are a power of two of columns, at most an eighth
    of the width, so a step whose payload stops short of its width rung
    leaves whole blocks behind (the ladder's rungs lie 9/7, 11/9 and
    14/11 apart), and at most half the mmap threshold where the width
    allows, so the blocks in flight come back into pages malloc already
    has."""
    lead, s = math.prod(shape[:-1]), shape[-1]
    cols = 1 << max(0, (s // 8).bit_length() - 1)
    half = hostmem.MALLOC_MMAP_MAX // 2
    while cols > max(128, s // 16) and lead * cols > half:
        cols //= 2
    bounds = [(a, min(a + cols, s)) for a in range(0, s, cols)]

    def build():
        import jax

        def split(y):
            y = y.transpose(order)
            return tuple(y[..., a:z] for a, z in bounds)

        return jax.jit(split), bounds

    return progcache.SHARED.get_or_build("result_split", (shape, order, cols),
                                         build)


def _to_host(y, width: int | None = None) -> np.ndarray:
    """Device result `y` as a host array. Over the mmap threshold it
    lands in an array the process keeps (`hostmem.KEPT`): its column
    blocks move to host memory D2H_AHEAD ahead of the copy into the
    array, and a block that starts at or past `width` (the payload's
    columns, where the caller knows them) stays on the device — the
    array keeps whatever those columns held."""
    if y.nbytes <= hostmem.MALLOC_MMAP_MAX:
        return np.asarray(y)
    import jax

    shape = tuple(y.shape)
    order = tuple(y.format.layout.major_to_minor)
    back = tuple(int(k) for k in np.argsort(order))
    buf, came = hostmem.KEPT.take(shape)
    split, bounds = _splitter(shape, order)
    pieces = split(y)
    live = [k for k, (a, _) in enumerate(bounds)
            if width is None or a < width]
    host = jax.sharding.SingleDeviceSharding(
        next(iter(y.devices())), memory_kind="unpinned_host")
    moving = [jax.device_put(pieces[k], host) for k in live[:D2H_AHEAD]]
    for i, k in enumerate(live):
        if i + D2H_AHEAD < len(live):
            moving.append(jax.device_put(pieces[live[i + D2H_AHEAD]], host))
        a, z = bounds[k]
        buf[..., a:z] = np.asarray(moving[i]).transpose(back)
        moving[i] = None  # its host copy goes back now
    if tracelib.enabled():
        metrics.codec_result_buffers.inc(result=came)
        span = tracelib.current()
        if span is not None:
            span.set_tag("result_buffer", came)
            span.set_tag("result_blocks", f"{len(live)}/{len(bounds)}")
    return buf


def device_call(eng, op: str, matrix, program, host_in: np.ndarray,
                width: int | None = None) -> np.ndarray:
    """One call of device engine `eng`: ``program(matrix(), host_in)``,
    host array in, host array out (past `width` columns, what
    `_to_host` leaves). ``matrix()`` hands over the step's
    bit matrix, device-resident (rs_kernel.device_bits: a cache lookup,
    or one bit expansion + one upload for a matrix not seen before).
    At most once in PHASE_EVERY_S the call is taken as its five steps:
    `matrix`, `h2d` (device_put until the input is on the device),
    `launch` (`program(w, x)`: the Python dispatch, until it returns its
    not-yet-ready result), `wait` (until the result is ready), `d2h`
    (`_to_host`) — each one sample of
    cubefs_codec_engine_phase_seconds{engine,op,phase} and one
    `cubefs:codec.<phase>` profiler annotation. Every other call, and
    every call with CUBEFS_TRACE=0, is the bare call."""
    now = time.perf_counter()
    if not tracelib.enabled() or now < getattr(eng, "_phase_due", 0.0):
        return _to_host(program(matrix(), host_in), width)
    eng._phase_due = now + PHASE_EVERY_S
    import jax

    def phase(name, fn, *args):
        with tracelib.annotation(_PHASE_SPANS[name]):
            t0 = time.perf_counter()
            try:
                return fn(*args)
            finally:
                metrics.codec_engine_phase.observe(
                    time.perf_counter() - t0, engine=eng.name, op=op,
                    phase=name)

    w = phase("matrix", matrix)
    x = phase("h2d", lambda: jax.block_until_ready(jax.device_put(host_in)))
    y = phase("launch", program, w, x)
    phase("wait", jax.block_until_ready, y)
    return phase("d2h", _to_host, y, width)


def ready_decode(n: int, s: int) -> None:
    """Called by the device engine after an encode of geometry (n data
    shards at the width rung s): the first time, one zero stripe goes
    through the rung's one-stripe decode shape — n rows solved from n
    survivors, (1, n, s), what codec/encoder.py's reconstruct asks for
    a blob — so its program is compiled (and its Pallas gate paid) with
    the encode's, and a hedged or degraded GET that meets no other GET
    in its step never compiles inside a request. The steps of 2, 4 or 8
    stripes that concurrent degraded GETs meet in are built by the
    deployment's door (`Encoder.ready`), not here. Where n == m that is
    the encode's own program and nothing is built."""
    def build() -> bool:
        coeff = np.eye(n, dtype=np.uint8)
        planes, program = rs_kernel.plan(coeff, (1, n, s))
        _to_host(program(rs_kernel.device_bits(coeff, planes),
                         np.zeros((1, n, s), dtype=np.uint8)))
        return True

    progcache.SHARED.get_or_build("decode_ready", (n, s), build)


class JaxEngine:
    name = "tpu"

    def matrix_apply(self, coeff: np.ndarray, shards: np.ndarray) -> np.ndarray:
        return self._apply("apply", coeff, shards)

    def encode_parity(self, data: np.ndarray, n_parity: int) -> np.ndarray:
        data = np.asarray(data)
        n, s = int(data.shape[-2]), int(data.shape[-1])
        out = self._apply("encode", gf256.parity_matrix(n, n_parity), data)
        ready_decode(n, rs_kernel.rung_width(s))
        return out

    def _apply(self, op: str, coeff: np.ndarray, shards: np.ndarray
               ) -> np.ndarray:
        """One device call at the step's rung (rs_kernel.step_shape):
        programs exist for rung shapes alone. The batcher hands over
        rung-shaped steps and they go up as they are, their payload
        width in STEP_WIDTH; any other caller's shards are copied into a
        zeroed rung-shaped array here and its rows sliced back."""
        coeff = np.ascontiguousarray(coeff, dtype=np.uint8)
        shards = np.asarray(shards)
        lead, (c, s) = shards.shape[:-2], shards.shape[-2:]
        b = math.prod(lead)
        rung = rs_kernel.step_shape(c, b, s)
        step, width = shards, STEP_WIDTH.get()
        if shards.shape != (rung[0], c, rung[1]):
            step, width = np.zeros((rung[0], c, rung[1]), dtype=np.uint8), s
            step[:b, :, :s] = shards.reshape(b, c, s)
        planes, program = rs_kernel.plan(coeff, step.shape)
        out = device_call(
            self, op, lambda: rs_kernel.device_bits(coeff, planes, op),
            program, step, width)
        if step is not shards:
            out = out[:b, :, :s].reshape(*lead, coeff.shape[0], s)
        return out


class CppEngine:
    """Native SIMD GF engine (runtime/src/gfcpu.cc — the klauspost-AVX2
    fallback role). ~50x the numpy table path on one core, which makes
    the CPU-vs-device size-class crossover a real policy instead of a
    foregone conclusion."""

    name = "cpp"

    def __init__(self):
        from ..runtime import build as rt_build

        self._lib = rt_build.load()

    def matrix_apply(self, coeff: np.ndarray, shards: np.ndarray) -> np.ndarray:
        coeff = np.ascontiguousarray(coeff, dtype=np.uint8)
        shards = np.ascontiguousarray(shards, dtype=np.uint8)
        lead = shards.shape[:-2]
        c, s = shards.shape[-2:]
        m = coeff.shape[0]
        if coeff.shape[1] != c:
            raise ValueError(f"matrix is {coeff.shape}, shards have {c} rows")
        batch = int(np.prod(lead)) if lead else 1
        out = np.empty((batch, m, s), dtype=np.uint8)
        # zero-copy: both arrays are contiguous; pass their buffers
        self._lib.gf_apply(coeff.ctypes.data, m, c, shards.ctypes.data,
                           out.ctypes.data, s, batch)
        return out.reshape(*lead, m, s)

    def encode_parity(self, data: np.ndarray, n_parity: int) -> np.ndarray:
        return self.matrix_apply(
            gf256.parity_matrix(data.shape[-2], n_parity), data)


class XorNumpyEngine:
    """Scheduled-XOR host engine: each coefficient matrix compiles once
    (ops/xorprog.py, cached in the shared program cache) into a CSE'd,
    cache-blocked straight-line XOR program replayed with word-wide
    ``np.bitwise_xor`` on uint64 views. Bit-identical to NumpyEngine;
    ~4-6x its throughput — the difference between a degraded (TPU-lost)
    cluster repairing at a crawl and repairing at production speed."""

    name = "numpy-xor"

    def matrix_apply(self, coeff: np.ndarray, shards: np.ndarray) -> np.ndarray:
        return xorprog.apply(coeff, shards)

    def encode_parity(self, data: np.ndarray, n_parity: int) -> np.ndarray:
        return xorprog.apply(
            gf256.parity_matrix(data.shape[-2], n_parity), data)


_REGISTRY: dict[str, type] = {
    "numpy": NumpyEngine,
    "tpu": JaxEngine,
    "cpp": CppEngine,
    "numpy-xor": XorNumpyEngine,
}  # and "auto", below its router

_instances: dict[str, Engine] = {}


def get_engine(name: str | None = None) -> Engine:
    """Resolve an engine by name; default from CUBEFS_TPU_EC_ENGINE
    (the --ec-engine flag analog), falling back to the TPU path."""
    name = name or os.environ.get("CUBEFS_TPU_EC_ENGINE", "tpu")
    if name not in _REGISTRY:
        raise KeyError(f"unknown ec engine {name!r}; have {sorted(_REGISTRY)}")
    if name not in _instances:
        _instances[name] = _REGISTRY[name]()
    return _instances[name]


# ---------------- measured size-class crossover (policy.go role) --------
# The reference picks codemodes by object size class
# (blobstore/common/codemode/policy.go); the analogous decision here is
# CPU-vs-device per stripe size: one small stripe cannot amortize device
# dispatch, a large batch leaves the CPU far behind. The table is
# MEASURED on this host+device pair, not assumed.

_POLICY_SIZES = (64 << 10, 256 << 10, 1 << 20, 4 << 20, 16 << 20)
_policy: list | None = None


def _platform() -> str:
    """Device class this process can actually dispatch to. Stamped into
    the persisted crossover table: a table measured on a CPU-only dev
    box routes every size class to the native engine, which is exactly
    wrong on a TPU-attached server. A backend that fails to initialise
    raises (pallas_gf.on_tpu) — it is never read as "cpu"."""
    from ..ops import pallas_gf

    return "tpu" if pallas_gf.on_tpu() else "cpu"


def _policy_path() -> str:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, "artifacts", "CROSSOVER.json")


def measure_crossover(sizes=_POLICY_SIZES, repeats: int = 3,
                      save: bool = True) -> list:
    """Times the host legs (cpp, numpy-xor) against the device engine
    on RS(6+3)-shaped single stripes per total-size class; returns
    [[max_total_bytes, engine], ...] sorted ascending. Persisted (with per-engine timings and the
    host-vs-device crossover point) so later processes inherit the
    policy without re-measuring."""
    import json
    import time

    table = []
    timings: dict[str, dict[str, float]] = {}
    candidates = []
    for name in ("cpp", "numpy-xor"):
        try:
            get_engine(name)
            candidates.append(name)
        except Exception:
            pass
    candidates.append("tpu")
    rng = np.random.default_rng(11)
    for total in sizes:
        s = max(1, total // 6)
        stripe = rng.integers(0, 256, (6, s), dtype=np.uint8)
        best, best_dt = candidates[0], float("inf")
        per = {}
        for name in candidates:
            eng = get_engine(name)
            eng.encode_parity(stripe, 3)  # warm (compile/dispatch)
            t0 = time.perf_counter()
            for _ in range(repeats):
                eng.encode_parity(stripe, 3)
            dt = (time.perf_counter() - t0) / repeats
            per[name] = round(dt, 6)
            if dt < best_dt:
                best, best_dt = name, dt
        timings[str(total)] = per
        table.append([total, best])
    # the size class where the device leg first beats the best host
    # leg; None = the host wins the whole sweep (the faster the host
    # legs, the higher this moves)
    crossover = None
    for total in sizes:
        per = timings[str(total)]
        host = min((v for k, v in per.items() if k != "tpu"),
                   default=None)
        if host is not None and per.get("tpu", float("inf")) < host:
            crossover = total
            break
    if save:
        try:
            os.makedirs(os.path.dirname(_policy_path()), exist_ok=True)
            with open(_policy_path(), "w") as f:
                json.dump({"table": table, "platform": _platform(),
                           "timings_s": timings,
                           "device_crossover_bytes": crossover}, f,
                          indent=1)
        except OSError:
            pass
    global _policy
    _policy = table
    return table


def _static_policy() -> list:
    """Unmeasured host: conservative static split — native CPU for
    sub-MiB stripes, device beyond."""
    have_cpp = True
    try:
        get_engine("cpp")
    except Exception:
        have_cpp = False
    small = "cpp" if have_cpp else "numpy-xor"
    return [[1 << 20, small], [1 << 62, "tpu"]]


def _load_policy() -> list:
    global _policy
    if _policy is None:
        import json

        try:
            with open(_policy_path()) as f:
                data = json.load(f)
        except FileNotFoundError:
            _policy = _static_policy()
            return _policy
        except Exception as e:
            _log.warning("unreadable crossover policy %s (%s); falling "
                         "back to the static size split — re-run "
                         "measure_crossover() to refresh it",
                         _policy_path(), e)
            _policy = _static_policy()
            return _policy
        # a table measured on a different device class is refused, not
        # silently applied: a cpu-measured table in a tpu-attached
        # process pins every size class to the host engine on the one
        # machine where the device path wins, and a tpu-measured table
        # on a cpu host routes small stripes to a device that is not
        # there. Log it and re-measure for this process only: a serving
        # process never writes into the checkout (the file is refreshed
        # by an explicit measure_crossover()). An unstamped (legacy)
        # table is assumed cpu-measured.
        stamped = data.get("platform", "cpu")
        here = _platform()
        if stamped != here:
            _log.warning("stale crossover policy %s: measured on %r but "
                         "this process dispatches to %r; re-measuring "
                         "in memory", _policy_path(), stamped, here)
            return measure_crossover(save=False)
        try:
            table = data["table"]
            if not (isinstance(table, list) and table
                    and all(len(row) == 2 for row in table)):
                raise ValueError(f"malformed table {table!r}")
            _policy = table
        except (KeyError, TypeError, ValueError) as e:
            _log.warning("stale crossover policy %s (%s); falling back "
                         "to the static size split", _policy_path(), e)
            _policy = _static_policy()
    return _policy


# Engines that raised a device-loss error this process; consulted by
# engine_for so a lost accelerator degrades once, not on every call.
_dead_engines: set[str] = set()

# Degradation order on device loss: the device, native SIMD, the host
# XOR programs, table-driven host math (always there).
_FALLBACK_CHAIN = ("tpu", "cpp", "numpy-xor", "numpy")


def _drilled_dead() -> set[str]:
    """CUBEFS_CODEC_DEAD: comma-separated engine names a chaos drill
    declares lost. Routed dispatch treats them exactly like a dead
    device, but transiently — clearing the env var revives them
    (unlike _dead_engines, which quarantines for the process life)."""
    v = os.environ.get("CUBEFS_CODEC_DEAD", "")
    return {x.strip() for x in v.split(",") if x.strip()}


def _fallback_for(name: str, drilled: set[str]) -> str | None:
    """Next live engine after `name` in the degradation chain."""
    try:
        i = _FALLBACK_CHAIN.index(name)
    except ValueError:
        return None
    for nxt in _FALLBACK_CHAIN[i + 1:]:
        if nxt in _dead_engines or nxt in drilled:
            continue
        try:
            get_engine(nxt)
        except Exception:
            continue
        return nxt
    return None


def _dispatch(name: str, method: str, *args) -> tuple[object, str]:
    """Run an engine method, degrading down the chain on device loss;
    returns (result, name of the engine that served it).
    Only RuntimeError/OSError trigger fallback (XLA device loss
    surfaces as a RuntimeError subclass) — semantic errors like shape
    mismatches would fail identically on every engine and must not
    quarantine one. A Mosaic compile error or an HBM RESOURCE_EXHAUSTED
    is a RuntimeError too, so every quarantine is logged with the
    exception that caused it: the degraded chain is the device-loss
    guarantee, not a way to leave the device unseen. Drilled-dead
    engines (CUBEFS_CODEC_DEAD) are skipped before dispatch without
    being quarantined."""
    drilled = _drilled_dead()
    if name in drilled:
        nxt = _fallback_for(name, drilled)
        if nxt is None:
            raise RuntimeError(
                f"engine {name!r} drilled dead and no fallback left")
        name = nxt
    while True:
        eng = get_engine(name)
        try:
            return getattr(eng, method)(*args), name
        except (RuntimeError, OSError):
            nxt = _fallback_for(name, drilled)
            if nxt is None:
                raise
            _log.exception(
                "codec engine %r failed in %s; quarantined for the life "
                "of this process, serving from %r", name, method, nxt)
            _dead_engines.add(name)
            name = nxt


def _call_with_fallback(name: str, method: str, *args):
    return _dispatch(name, method, *args)[0]


def engine_for(nbytes: int) -> Engine:
    """The measured-best engine for a stripe of `nbytes` total."""
    drilled = _drilled_dead()
    for limit, name in _load_policy():
        if nbytes <= limit:
            if name in _dead_engines or name in drilled:
                name = _fallback_for(name, drilled) or name
            try:
                return get_engine(name)
            except Exception:
                break
    return get_engine()


class AutoEngine:
    """Per-call policy dispatch: route each stripe batch to the
    measured-best engine for its size (`engine='auto'`), degrading
    down the fallback chain if the chosen engine's device is lost."""

    name = "auto"

    def matrix_apply(self, coeff: np.ndarray, shards: np.ndarray) -> np.ndarray:
        eng = engine_for(int(np.asarray(shards).nbytes))
        return _call_with_fallback(eng.name, "matrix_apply", coeff, shards)

    def encode_parity(self, data: np.ndarray, n_parity: int) -> np.ndarray:
        eng = engine_for(int(np.asarray(data).nbytes))
        return _call_with_fallback(eng.name, "encode_parity", data, n_parity)


_REGISTRY["auto"] = AutoEngine
