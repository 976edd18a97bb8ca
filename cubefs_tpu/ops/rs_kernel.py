"""Reed-Solomon encode/reconstruct as TPU matmuls (JAX).

The hot path of the reference's erasure-coding plane — GF(2^8)
matrix-times-shards in blobstore/common/ec/encoder.go:114 (encode) and
blobnode/worker_slice_recover.go:865 (reconstruct) — expressed as a single
int8 MXU matmul over the GF(2) bit expansion (see cubefs_tpu/ops/bitlin.py
for why this is exact and gather-free).

Shapes: shards are (..., B, S) uint8 — leading batch dims (stripes), B
shards of S bytes. The GF coefficient matrix is tiny ((M, N) with
M, N <= 36) and goes to the device as an OPERAND: its (8M, 8N) bit form
is expanded and uploaded once per matrix behind a bounded
device-resident cache (``device_bits``), and a program is keyed by its
shapes alone — one executable serves the encode rows and every
survivor set's decode rows of a geometry, so a matrix nobody warmed
costs no compile.

Bit-identical guarantee: every step (bit unpack, 0/1 int matmul, mod-2,
bit pack) is exact integer arithmetic; combined with the same encode
matrix as the reference engine (gf256.encode_matrix), outputs match the
reference byte-for-byte.
"""

from __future__ import annotations

import collections
import functools
import itertools
import logging
import threading

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import metrics
from ..utils import trace as tracelib
from . import bitlin, gf256, msr, pallas_gf, progcache

_BITS = (1 << np.arange(8)).astype(np.int32)
_log = logging.getLogger("cubefs.codec")


def _use_pallas() -> bool:
    """On real TPU the fused plane-major Pallas kernel avoids the 8x bit
    tensor in HBM."""
    from . import pallas_gf

    return pallas_gf.on_tpu()


def _pallas_profitable(s: int) -> bool:
    """The fused program serves shards of four tiles and more. Below,
    a step is microseconds of device work behind milliseconds of host
    dispatch, and the jnp program's dispatch is the cheaper one (one
    jitted call; the fused program reshapes and vmaps in Python on
    every call): `put-small` lost 17% of its `op_rate` with its 64 KiB
    class on the fused program (PERF.md section 6, PR 34)."""
    return s >= 4 * pallas_gf.DEFAULT_TILE


# Programs the gate refused this process: (rows, cols, tile) -> cause.
# A refused program's shapes are served by the exact jnp path for the
# life of the process; chip_smoke.py fails the run when this is
# non-empty.
pallas_refusals: dict[tuple[int, int, int], str] = {}

# (rows, cols, tile) -> blessed? One entry per program the process has
# asked about: bounded by the shapes there are (rows, cols <= 36), not
# by the matrices.
_gate: dict[tuple[int, int, int], bool] = {}
_gate_lock = threading.Lock()
GATE_MATRICES = 3  # seeded random coefficient matrices per program


def _pallas_verified(rows: int, cols: int, tile: int,
                     coeff: np.ndarray | None = None) -> bool:
    """Once-per-process bit-identity gate for the production dispatch,
    per PROGRAM: with the bit matrix an operand, what Mosaic compiles
    depends on (rows, cols, tile) and not on the coefficients, so the
    fused kernel must match the jnp path on-device for GATE_MATRICES
    seeded random coefficient matrices and for the first real one that
    asks (``coeff``) before the program may serve real data. Mosaic has
    silently miscompiled this kernel at some tile sizes — unlike repair
    (whose extras integrity leg fails loudly), encode has no downstream
    check, so wrong parity would only surface at reconstruct time,
    after the data shards are gone.

    Every refusal — a mismatch, or the gate itself raising (a Mosaic
    compile error lands here) — is logged with its cause and recorded
    in ``pallas_refusals``; the program's shapes then ride the jnp
    path."""
    key = (rows, cols, tile)
    ok = _gate.get(key)
    if ok is None:
        with _gate_lock:
            ok = _gate.get(key)
            if ok is None:
                ok = _gate[key] = _run_gate(key, coeff)
    return ok


def _run_gate(key: tuple[int, int, int], coeff: np.ndarray | None) -> bool:
    from . import pallas_gf

    rows, cols, tile = key
    tries = [np.random.default_rng([rows, cols, tile, i]).integers(
        0, 256, (rows, cols), dtype=np.uint8) for i in range(GATE_MATRICES)]
    if coeff is not None:
        tries.append(coeff)
    cause = None
    try:
        for i, m in enumerate(tries):
            if not pallas_gf.verify_tile(m, tile, seed=i):
                cause = f"mismatch vs jnp path at tile={tile}"
                _log.error("pallas kernel MISCOMPILES for program %s "
                           "(matrix %d of the gate's %d); serving its "
                           "shapes from the jnp path", key, i, len(tries))
                break
    except Exception as e:
        _log.exception("pallas gate raised for program %s; serving its "
                       "shapes from the jnp path", key)
        cause = f"gate raised {type(e).__name__}: {e}"
    if cause is not None:
        pallas_refusals[key] = cause
    metrics.codec_pallas_gate.inc(
        result="blessed" if cause is None else "refused")
    return cause is None


def serves_fused(coeff: np.ndarray, s: int) -> bool:
    """The one dispatch decision: does the fused Pallas kernel serve a
    matrix of ``coeff``'s shape at shard size ``s``? (TPU backend, pad
    waste bounded, program blessed by the gate.)"""
    if not (_use_pallas() and _pallas_profitable(s)):
        return False
    from . import pallas_gf

    return _pallas_verified(coeff.shape[0], coeff.shape[1],
                            pallas_gf.DEFAULT_TILE, coeff)


# ---------------- the matrix as an operand ------------------------------
MATRIX_CACHE_CAP = 1024  # <= 83 KB each (288 x 288 int8), mostly ~1.5 KB


class MatrixCache:
    """Bounded LRU of device-resident bit matrices, keyed by layout and
    coefficients. A hit costs a dict lookup and no transfer; a miss is
    one bit expansion (bitlin) + one upload, counted per ``op`` in
    cubefs_codec_matrix_cache_total. EC6P6 has 923 survivor sets and a
    host that lives for weeks meets them all: past ``capacity`` the
    least recently used matrix is dropped."""

    def __init__(self, capacity: int = MATRIX_CACHE_CAP):
        self.capacity = capacity
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self._lock = threading.Lock()

    def get(self, coeff: np.ndarray, planes: bool, op: str) -> jax.Array:
        key = (planes, coeff.shape, coeff.tobytes())
        with self._lock:
            w = self._entries.get(key)
            if w is not None:
                self._entries.move_to_end(key)
        hit = w is not None
        if not hit:
            bits = bitlin.gf_matrix_to_bits(coeff)
            if planes:
                bits = bitlin.w_to_bitmajor(bits, *coeff.shape)
            # a first lookup may happen inside an outer jit trace: keep
            # the upload concrete, or the cache would hold a tracer
            with jax.ensure_compile_time_eval():
                w = jax.device_put(bits)
            with self._lock:
                self._entries[key] = w
                while len(self._entries) > self.capacity:
                    self._entries.popitem(last=False)
        if tracelib.enabled():
            metrics.codec_matrix_cache.inc(
                op=op, result="hit" if hit else "miss")
        return w

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


matrices = MatrixCache()


def device_bits(coeff: np.ndarray, planes: bool, op: str = "apply"
                ) -> jax.Array:
    """The (8R, 8C) int8 bit matrix of ``coeff`` on the device:
    plane-major for the fused kernel (``planes``), byte-major for the
    jnp bit-matmul."""
    return matrices.get(coeff, planes, op)


def unpack_bits(x: jax.Array) -> jax.Array:
    """(..., B, S) uint8 -> (..., 8B, S) int8, LSB-first per byte."""
    *lead, b, s = x.shape
    planes = (x[..., :, None, :].astype(jnp.int32) >> jnp.arange(8)[None, :, None]) & 1
    return planes.reshape(*lead, 8 * b, s).astype(jnp.int8)


def pack_bits(bits: jax.Array) -> jax.Array:
    """(..., 8B, S) int -> (..., B, S) uint8."""
    *lead, b8, s = bits.shape
    planes = bits.reshape(*lead, b8 // 8, 8, s).astype(jnp.int32)
    return (planes << jnp.arange(8)[None, :, None]).sum(-2).astype(jnp.uint8)


def gf_apply_bits(
    w_bits: jax.Array, shards: jax.Array, psum_axis: str | None = None
) -> jax.Array:
    """Apply a GF(2)-expanded coefficient matrix to shard bytes.

    w_bits: (8M, 8N) int8 0/1; shards: (..., N, S) uint8 -> (..., M, S).
    The contraction K = 8N <= 288 keeps the accumulator far below int32
    limits; XLA lowers the int8 x int8 -> int32 dot onto the MXU.

    psum_axis: inside shard_map with the shard axis N split across mesh
    axis `psum_axis`, pass its name — partial int32 products are summed
    across devices BEFORE the mod-2, which is exact (parity of a sum ==
    XOR of parities).
    """
    with jax.named_scope("gf256.bits.unpack"):
        x = unpack_bits(shards)
    with jax.named_scope("gf256.bits.dot"):
        y = jax.lax.dot_general(
            w_bits,
            x,
            ((( 1,), (x.ndim - 2,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # (8M, ..., S)
        if x.ndim > 2:
            y = jnp.moveaxis(y, 0, -2)
        if psum_axis is not None:
            y = jax.lax.psum(y, psum_axis)
    with jax.named_scope("gf256.bits.pack"):
        return pack_bits(y & 1)


# ---------------- the ladder of step shapes -----------------------------
# A program is keyed by its step's shape, and an object store's clients
# PUT whatever sizes they have: so every step runs at the smallest RUNG
# (B_rung, S_rung) that holds it. GF apply is independent per byte
# column and per stripe, so pad columns and pad stripes change nothing
# of what is sliced back. The rungs are few enough to build before the
# first request (codec/encoder.py: Encoder.ready), so a size nobody
# warmed costs no compile, and PUTs of different sizes meet in one
# queue of the batcher. What the rungs cost is in PERF.md section 6.
#
# Width rungs are whole tiles of the fused kernel, so the fused program
# (four tiles and up) never pads on the device and the jnp program below
# it is 128-lane aligned: 1..7 tiles, then {9, 11, 14} x 2^k — a step
# of at most 2/9 between neighbours, chosen so that 22 tiles is a rung:
# the shard of a full 8 MiB blob over 12 (and of 4 MiB over 6) is
# 699,051 B, 21.33 tiles.
_TILE_MANTISSAS = (9, 11, 14)
STEP_BATCH = 8  # stripes a coalesced step may hold (BatchCodec.max_batch)


def rung_width(s: int) -> int:
    """The smallest width rung >= ``s`` bytes of shard: a whole number
    of tiles (128-lane aligned whatever program serves it). A row's pad
    is under one tile up to 4 tiles, then under a quarter of the row,
    from 7 tiles on under 2/9. A rung's rung is itself."""
    tile = pallas_gf.DEFAULT_TILE
    tiles = -(-s // tile)
    if tiles <= 7:
        return tile if tiles < 1 else tiles * tile
    k = 0
    while True:
        for m in _TILE_MANTISSAS:
            if m << k >= tiles:
                return (m << k) * tile
        k += 1


def rung_batch(b: int) -> int:
    """The smallest stripe-count rung >= ``b``: 1, 2, 4, 8 up to
    STEP_BATCH (a coalesced step of small stripes may double), then
    {4, 5, 6, 7} x 2^k (only a single submission of many stripes gets
    there, a repair task's 64: it grows by at most a quarter)."""
    b = max(1, int(b))
    if b <= STEP_BATCH:
        return 1 << (b - 1).bit_length()
    k = max(0, b.bit_length() - 3)
    return -(-b >> k) << k


def step_shape(cols: int, b: int, s: int) -> tuple[int, int]:
    """(B_rung, S_rung): the step shape that ``b`` stripes of ``cols``
    rows of ``s`` bytes run at. The one function every step shape goes
    through: the batcher keys its queues and gathers its steps by it,
    the front door sizes a PUT's data rows by it, the device engine
    pads what reaches it in any other shape."""
    del cols  # no rung depends on the row count today; the key has it
    return rung_batch(b), rung_width(s)


def batch_cap(cols: int, s_rung: int, max_step_bytes: int,
              max_batch: int = STEP_BATCH) -> int:
    """The most stripes a COALESCED step of this width may hold: the
    largest stripe-count rung within ``max_batch`` stripes and
    ``max_step_bytes`` input bytes, both reckoned on the rung (one
    stripe always fits: a submission is never split)."""
    cap = max(1, min(int(max_batch),
                     int(max_step_bytes) // max(1, cols * s_rung)))
    b = 1
    while (nxt := rung_batch(b + 1)) <= cap:
        b = nxt
    return b


def ladder(cols: int, s_lo: int, s_hi: int, max_step_bytes: int,
           max_batch: int = STEP_BATCH, stripes: int = 1
           ) -> list[tuple[int, int]]:
    """Every (B_rung, S_rung) a step of ``cols`` rows can run at when
    its submissions hold shards of ``s_lo``..``s_hi`` bytes and up to
    ``stripes`` stripes each: sorted, finite — the rungs of
    ``step_shape`` up to the coalescing bounds, plus the shape of one
    submission alone where that is past them."""
    out = []
    s = rung_width(s_lo)
    top = rung_width(s_hi)
    while s <= top:
        cap = max(batch_cap(cols, s, max_step_bytes, max_batch),
                  rung_batch(stripes))
        b = 1
        while b <= cap:
            out.append((b, s))
            b = rung_batch(b + 1)
        s = rung_width(s + 1)
    return out


# A repair step is one submission of up to a task's worth of stripes,
# so it could ask for any of rung_batch's sixteen stripe rungs up to 64
# at every width rung: hundreds of programs a codemode, each built by
# the first task that meets it. The repair worker asks for few instead:
# always REPAIR_ROWS rows (the lost unit's and the check's: an extra
# survivor rebuilt, or in a local stripe that leaves none the lost unit
# derived again through the global code, `lrc_checked_rows`; the lost
# one twice where neither can be had) and a stripe rung of
# ``repair_batches`` — zero stripes up to it — so the set is small
# enough to build before the first task (blob/worker.py:
# RepairWorker.ready). Each is a rung of rung_batch, so the batcher
# passes the worker's array as it is.
REPAIR_ROWS = 2


def repair_batches(max_stripes: int) -> list[int]:
    """The stripe rungs of a repair step of up to ``max_stripes``
    stripes (the worker's batch_stripes): STEP_BATCH doubling, the rung
    at three quarters of the top one, and the rung that holds
    ``max_stripes`` — 8, 16, 32, 48, 64. Every zero stripe of a wide
    rung is megabytes of fresh pages on the host and of transfer, and
    past half the top rung doubling would add up to as many again as
    the step holds (PERF.md section 6, PR 36)."""
    top = rung_batch(max_stripes)
    out, b = [], STEP_BATCH
    while b < top:
        out.append(b)
        b <<= 1
    mid = rung_batch(3 * top // 4)
    if out and out[-1] < mid < top:
        out.append(mid)
    return out + [top]


def repair_step_shape(b: int, s: int, max_stripes: int) -> tuple[int, int]:
    """(B_rung, S_rung) of the repair step that holds ``b`` <=
    ``max_stripes`` stripes whose widest shard is ``s`` bytes."""
    return (next(r for r in repair_batches(max_stripes) if r >= b),
            rung_width(s))


def repair_steps(s_lo: int, s_hi: int, max_stripes: int
                 ) -> list[tuple[int, int]]:
    """Every (B_rung, S_rung) a repair step can run at when a volume's
    shards are ``s_lo``..``s_hi`` bytes: sorted, finite. With the row
    count fixed at REPAIR_ROWS and the columns by the codemode's n, a
    program a shape."""
    out = []
    s, top = rung_width(s_lo), rung_width(s_hi)
    while s <= top:
        out += [(b, s) for b in repair_batches(max_stripes)]
        s = rung_width(s + 1)
    return out


@progcache.cached("rs_jit")
def _bits_fn(rows: int, cols: int, shape: tuple):
    """The jnp bit-matmul program for this shape: ``apply(w, shards)``,
    the byte-major (8R, 8C) bit matrix an operand."""
    del rows, cols, shape  # the key: one cached program per shape

    @jax.jit
    def apply(w: jax.Array, shards: jax.Array) -> jax.Array:
        return gf_apply_bits(w, shards)

    metrics.codec_programs.inc(kernel="bits")
    return apply


def plan(coeff: np.ndarray, shape: tuple) -> tuple[bool, object]:
    """(plane-major matrix?, program) that serve a matrix of ``coeff``'s
    shape on shards of ``shape``: the fused kernel where ``serves_fused``
    says so, else the jnp bit-matmul. ``program(w, shards)`` takes the
    matrix from ``device_bits(coeff, planes)``."""
    rows, cols = coeff.shape
    shape = tuple(shape)
    if serves_fused(coeff, shape[-1]):
        from . import pallas_gf

        return True, pallas_gf._apply_fn(rows, cols, shape,
                                         pallas_gf.DEFAULT_TILE,
                                         not pallas_gf.on_tpu())
    return False, _bits_fn(rows, cols, shape)


def gf_matrix_apply(coeff: np.ndarray, shards: jax.Array,
                    op: str = "apply") -> jax.Array:
    """shards: (..., C, S) uint8, coeff: (R, C) GF(256) -> (..., R, S).

    The one building block of encode (parity rows), reconstruct
    (decode-matrix rows), LRC and MSR rows. The program is keyed by the
    shapes; the matrix is its operand."""
    coeff = np.ascontiguousarray(coeff, dtype=np.uint8)
    planes, program = plan(coeff, shards.shape)
    return program(device_bits(coeff, planes, op), shards)


def encode_parity(data: jax.Array, n_parity: int) -> jax.Array:
    """data: (..., N, S) uint8 -> parity (..., M, S) uint8."""
    return gf_matrix_apply(
        gf256.parity_matrix(int(data.shape[-2]), n_parity), data, "encode")


def reconstruct_rows(
    n_data: int, n_total: int, present: list[int], wanted: list[int]
) -> np.ndarray:
    """GF matrix mapping the first n_data present shards to the wanted
    shard indices (data rows come from the inverted submatrix, parity rows
    from re-encoding — same algebra as the reference engine's
    Reconstruct, vendor reedsolomon.go reconstruct())."""
    present = sorted(present)[:n_data]
    dec = gf256.decode_matrix(n_data, n_total, present)
    enc = gf256.encode_matrix(n_data, n_total)
    return gf256.gf_matmul(enc[np.asarray(wanted)], dec)


def lrc_reconstruct_rows(
    n_data: int, n_total: int, stripes: list[list[int]], ln: int,
    present: list[int], wanted: list[int],
) -> np.ndarray:
    """reconstruct_rows over the FULL two-level LRC shard space.

    `present` must index the global stripe (< n_total: data + global
    parity), but `wanted` may include local-parity indices (>= n_total).
    A local parity is the local code's re-encode of its stripe's first
    `ln` members — all global-space indices — so its row is the local
    encode row composed with the global solve: one matrix, same batched
    apply as every other repair. This is what lets a repair rebuild a
    local parity when its entire stripe's AZ is dark."""
    present = sorted(present)[:n_data]
    dec = gf256.decode_matrix(n_data, n_total, present)
    enc = gf256.encode_matrix(n_data, n_total)
    rows = np.zeros((len(wanted), n_data), dtype=np.uint8)
    for r, w in enumerate(wanted):
        if w < n_total:
            rows[r] = enc[w]
            continue
        stripe = next(s for s in stripes if w in s)
        local = gf256.encode_matrix(ln, len(stripe))
        members = enc[np.asarray(stripe[:ln])]
        rows[r] = gf256.gf_matmul(local[[stripe.index(w)]], members)[0]
    return gf256.gf_matmul(rows, dec)


def lrc_encode_rows(n_data: int, n_total: int, stripes: list[list[int]],
                    ln: int) -> np.ndarray:
    """The (m + l, n_data) rows that make ALL of a two-level LRC's
    parity from its data shards in one apply: the global rows of the
    systematic RS(n_data, n_total), then every local parity composed
    through them — lrc_reconstruct_rows with every data shard present.
    Local parity is linear in the data, so one step of these rows is
    bit-identical to the global step followed by each AZ's own."""
    total = n_total + sum(len(s) - ln for s in stripes)
    return lrc_reconstruct_rows(n_data, n_total, stripes, ln,
                                list(range(n_data)),
                                list(range(n_data, total)))


@functools.lru_cache(maxsize=1024)
def lrc_checked_rows(n_data: int, n_total: int,
                     stripes: tuple[tuple[int, ...], ...], ln: int,
                     stripe: tuple[int, ...], present: tuple[int, ...],
                     lost: int) -> np.ndarray | None:
    """(2, ln) rows that rebuild position ``lost`` of a local stripe
    twice from the ``ln`` positions ``present`` as read (positions in
    the local code, ``stripe`` their unit indices): row 0 through the
    local code, row 1 through the global code, from ``n_data`` of the
    global units among them (composed with the local encode row where
    the lost unit is a local parity). A local stripe of one local parity
    leaves no survivor to check with; the second derivation takes its
    place without a read across AZs. The solving set is the first whose
    row 1 differs from row 0 at every column: one wrong survivor then
    always makes the two rows disagree (the difference of the rows is
    its coefficient times the error, and GF(2^8) has no zero divisors),
    which a fixed choice does not give for every lost position. None
    where no solving set does that, as where the AZ holds fewer than
    ``n_data`` global units besides the lost one."""
    row0 = reconstruct_rows(ln, len(stripe), list(present), [lost])[0]
    cols = [c for c, p in enumerate(present) if stripe[p] < n_total]
    for solve in itertools.combinations(cols, n_data):
        row1 = np.zeros_like(row0)
        row1[list(solve)] = lrc_reconstruct_rows(
            n_data, n_total, [list(s) for s in stripes], ln,
            [stripe[present[c]] for c in solve], [stripe[lost]])[0]
        if np.all(row0 ^ row1):
            rows = np.stack([row0, row1])
            rows.setflags(write=False)
            return rows
    return None


def reconstruct_stripes(
    surviving: jax.Array,
    present: list[int],
    wanted: list[int],
    n_data: int,
    n_total: int,
) -> jax.Array:
    """surviving: (..., n_data, S) uint8 = the first n_data present shards
    stacked in ascending shard-index order; returns (..., len(wanted), S)."""
    rows = reconstruct_rows(n_data, n_total, present, wanted)
    return gf_matrix_apply(rows, surviving)


# ---------------- product-matrix MSR (regenerating-code) kernels --------
# Row construction lives in ops/msr.py (tiny exact host math, lru-cached
# per geometry/failed-slot/helper-set); these wrappers are the kernel
# surface the codec engines and the blob plane consume. Like RS, the
# byte work is ONE gf_matrix_apply — the same bit-matmul (jax/pallas)
# or table (numpy/cpp) engines serve both families, and admitted
# callers coalesce MSR sub-shard steps with RS stripes for free.

msr_encode_rows = msr.encode_rows
msr_helper_rows = msr.helper_rows
msr_repair_rows = msr.repair_rows
msr_verify_rows = msr.verify_rows
msr_reconstruct_rows = msr.reconstruct_rows


def msr_subshards(shards: jax.Array, alpha: int) -> jax.Array:
    """(..., B, S) -> (..., B*alpha, S/alpha): expose each shard's alpha
    sub-shards as rows so MSR coefficient matrices can apply. S must be
    alpha-divisible (MsrEncoder.shard_size guarantees it on write)."""
    *lead, b, s = shards.shape
    if s % alpha:
        raise ValueError(f"shard size {s} not divisible by alpha={alpha}")
    return shards.reshape(*lead, b * alpha, s // alpha)


def msr_join_subshards(sub: jax.Array, alpha: int) -> jax.Array:
    """Inverse of msr_subshards: (..., B*alpha, beta) -> (..., B, S)."""
    *lead, rows, beta = sub.shape
    return sub.reshape(*lead, rows // alpha, alpha * beta)


def msr_encode_parity(data: jax.Array, k: int, total: int, d: int) -> jax.Array:
    """data: (..., k, S) uint8 -> parity (..., total-k, S) uint8 via the
    product-matrix generator (jax path; engines route the same rows
    through their own matrix_apply)."""
    alpha = d - k + 1
    rows = msr.encode_rows(k, total, d)
    sub = msr_subshards(np.asarray(data), alpha)
    return msr_join_subshards(gf_matrix_apply(rows, sub), alpha)


def msr_repair_shard(payloads: jax.Array, k: int, total: int, d: int,
                     failed: int, helpers: tuple[int, ...]) -> jax.Array:
    """payloads: (..., d, beta) helper symbols (in `helpers` order) ->
    the failed shard (..., S=alpha*beta) — repair traffic d*beta bytes
    instead of the conventional k*alpha*beta."""
    rows = msr.repair_rows(k, total, d, failed, helpers)
    out = gf_matrix_apply(rows, payloads)  # (..., alpha, beta)
    *lead, alpha, beta = out.shape
    return out.reshape(*lead, alpha * beta)
