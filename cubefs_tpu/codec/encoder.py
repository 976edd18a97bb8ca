"""Encoder: the reference codec interface over TPU-batched stripes.

Semantics mirror blobstore/common/ec/encoder.go:41-62 (Encoder interface:
Encode/Verify/Reconstruct/ReconstructData/Split/Join/GetDataShards/
GetParityShards/GetLocalShards/GetShardsInIdc) and lrcencoder.go (two-level
LRC: global N+M stripe plus per-AZ local parity). The data model is
TPU-first: a stripe is ONE (total, S) uint8 ndarray (and batched
(B, total, S) stacks for the repair/migrate fleet), not a []][]byte —
device kernels see large contiguous batches, never per-shard slices.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import numpy as np

from ..ops import rs_kernel
from ..utils import metrics
from ..utils import trace as tracelib
from . import codemode as cm
from .batcher import admit
from .engine import Engine


class PendingEncode:
    """An encode admitted to the codec batcher while its caller still
    has other work in hand (bid allocation, header parsing, streaming
    the rest of the body). wait() returns what the entry point that
    made it promised — encode_rows_async: the parity rows (B, m[+l], S),
    the engine's own result, copied into nothing; encode_async: the
    caller's stripe array with the parity rows landed in place, the
    array encode() would return — raising any per-submission error at
    the collect point. `resolved` says whether the device step already
    completed without blocking."""

    __slots__ = ("_value", "_finish", "_fut")

    def __init__(self, value=None, finish=None, fut=None):
        self._value = value
        # timeout -> value; runs at most once; None = already complete
        self._finish = finish
        self._fut = fut

    @property
    def resolved(self) -> bool:
        return self._finish is None or (self._fut is not None
                                        and self._fut.done)

    def wait(self, timeout: float = 120.0) -> np.ndarray:
        if self._finish is not None:
            finish, self._finish = self._finish, None
            self._value = finish(timeout)
        return self._value


class ECError(Exception):
    pass


class ShortDataError(ECError):
    pass


class VerifyError(ECError):
    pass


@dataclass
class CodecConfig:
    """ec.Config analog (blobstore/common/ec/encoder.go:64-69)."""

    mode: cm.CodeMode
    enable_verify: bool = False
    engine: str | None = None  # --ec-engine; None -> env default


def new_encoder(cfg: CodecConfig) -> "Encoder":
    t = cm.tactic(cfg.mode)
    # every encoder reaches device math through the batched admission
    # surface (codec/batcher.py): concurrent PUT/repair/verify callers
    # sharing a geometry coalesce into one device step, bit-identically
    eng = admit(cfg.engine)
    if t.is_msr():
        return MsrEncoder(cfg, t, eng)
    if t.l != 0:
        return LrcEncoder(cfg, t, eng)
    return Encoder(cfg, t, eng)


class Encoder:
    """Plain N+M Reed-Solomon codec over stripe arrays."""

    def __init__(self, cfg: CodecConfig, t: cm.Tactic, engine: Engine):
        self.cfg = cfg
        self.t = t
        self.engine = engine

    @property
    def codec(self) -> Engine:
        """The encoder's admission-surface handle, for callers that
        need raw shard math (batched verify sweeps, culprit isolation)
        without bypassing coalescing (lint family CFC)."""
        return self.engine

    # -- shape helpers ---------------------------------------------------
    def _check(self, shards: np.ndarray, total: int | None = None) -> np.ndarray:
        total = total if total is not None else self.t.total
        shards = np.asarray(shards)
        if shards.dtype != np.uint8:
            # a silent asarray copy would break the in-place mutation
            # contract of encode/reconstruct — reject instead
            raise ECError(f"stripe dtype must be uint8, got {shards.dtype}")
        if shards.shape[-2] != total:
            raise ECError(
                f"stripe has {shards.shape[-2]} shards, want {total} for {self.t}"
            )
        return shards

    def shard_size(self, data_len: int) -> int:
        """Per-shard size for a payload: max(ceil(len/N), min_shard_size)
        (Tactic.MinShardSize semantics, codemode.go MinShardSize doc)."""
        per = -(-data_len // self.t.n)
        return max(per, self.t.min_shard_size)

    def row_width(self, shard_size: int) -> int:
        """The width to build data rows of `shard_size` bytes at, zeros
        past the shard: the rung their step runs at
        (rs_kernel.rung_width), so encode_rows_async(rows, shard_size)
        takes the array as it is. Stored shards stay `shard_size`."""
        return rs_kernel.rung_width(shard_size)

    def ready(self, lo: int, hi: int, stripes: int = 1) -> int:
        """Build every program that encodes of blobs of `lo`..`hi`
        payload bytes, up to `stripes` blobs a call, and degraded reads
        of such blobs can ask the device for: one zero step through the
        encoder's own door at every rung of the ladder that its
        batcher's bounds can reach, then every decode step of
        `_decode_shapes`. What a deployment does once, before its first
        request; returns the number of encode steps (every program it
        builds, decodes too, counts in `cubefs_codec_programs_total`)."""
        batcher = self._batcher()
        if batcher is None:
            return 0
        cols, lo_w, hi_w = self._step_geometry(lo, hi)
        bounds = (batcher.max_step_bytes, batcher.max_batch)
        shapes = rs_kernel.ladder(cols, lo_w, hi_w, *bounds, stripes)
        for b, width in shapes:
            self._ready_step(b, width)
        decodes = self._decode_shapes(lo_w, hi_w, *bounds)
        n = self.t.n
        for b, width in decodes:
            self.engine.matrix_apply(np.eye(n, dtype=np.uint8),
                                     np.zeros((b, n, width), dtype=np.uint8))
        return len(shapes)

    def _step_geometry(self, lo: int, hi: int) -> tuple[int, int, int]:
        """(rows of a step, its least width, its largest)."""
        return self.t.n, self.shard_size(lo), self.shard_size(hi)

    def _ready_step(self, b: int, width: int) -> None:
        self.encode_rows_async(
            np.zeros((b, self.t.n, width), dtype=np.uint8)).wait()

    def _decode_shapes(self, lo_w: int, hi_w: int, max_step_bytes: int,
                       max_batch: int) -> list[tuple[int, int]]:
        """(B_rung, S_rung) of every decode a degraded GET of shards of
        `lo_w`..`hi_w` bytes can ask for. A GET decodes a blob at a time
        by one (n, n) matrix (`_reconstruct` pads it to n rows), and the
        batcher joins concurrent GETs that lost the same units into one
        step: every stripe rung up to its bounds at every width rung.
        Where m == n these are the encode's own programs, built above.
        The split with the device engine: `engine.ready_decode` builds
        the one-stripe rung with a geometry's first encode, for a
        process that never calls `ready`; the door builds the rest, and
        its one-stripe steps find that program built."""
        if self.t.m == self.t.n:
            return []
        return rs_kernel.ladder(self.t.n, lo_w, hi_w, max_step_bytes,
                                max_batch)

    # -- reference Encoder interface ------------------------------------
    def encode(self, shards: np.ndarray) -> np.ndarray:
        """Fill parity rows from data rows; returns the same array."""
        shards = self._check(shards)
        n = self.t.n
        shards[..., n:, :] = self._finish_rows(shards[..., :n, :], None, 0.0)
        return shards

    def encode_rows_async(self, data: np.ndarray,
                          shard_size: int | None = None) -> PendingEncode:
        """Admit the encode of C-contiguous data rows (B, n, S) as they
        are and return immediately; wait() returns the parity rows
        (B, total - n, S). With a batcher-admitted engine the device
        step runs (coalesced with concurrent submissions) while the
        caller overlaps allocation or IO; engines without an admission
        surface degrade to an inline encode. The caller keeps `data`
        unchanged until wait() has returned.

        `shard_size`: the rows were built row_width(shard_size) wide,
        zeros past the shard; the parity rows come back `shard_size`
        wide."""
        data = self._check(data, total=self.t.n)
        if data.ndim != 3:
            raise ECError(f"data rows must be (B, n, S), got {data.shape}")
        if shard_size is None:
            shard_size = int(data.shape[2])
        elif data.shape[2] < shard_size:
            raise ECError(f"rows of {shard_size} B shards are "
                          f"{data.shape[2]} wide")
        fut = self._submit_rows(data, shard_size)
        if fut is None:
            return PendingEncode(
                self._finish_rows(data, None, 0.0)[..., :shard_size])
        return PendingEncode(
            None, lambda timeout: self._finish_rows(data, fut, timeout), fut)

    def encode_async(self, shards: np.ndarray) -> PendingEncode:
        """encode_rows_async for a caller that holds whole stripes:
        wait() fills the parity rows in place and returns `shards`."""
        shards = self._check(shards)
        n = self.t.n
        flat = shards.reshape(-1, self.t.total, shards.shape[-1])
        pending = self.encode_rows_async(np.ascontiguousarray(flat[:, :n, :]))

        def fill(timeout: float) -> np.ndarray:
            flat[:, n:, :] = pending.wait(timeout)
            return shards

        if pending._fut is None:  # nothing admitted: complete already
            return PendingEncode(fill(0.0))
        return PendingEncode(None, fill, pending._fut)

    def _batcher(self):
        """The engine's admission surface; None for a raw engine."""
        return getattr(self.engine, "batcher", None)

    def _submit_rows(self, data: np.ndarray, shard_size: int):
        """The one way from an encoder to the batcher: the future of
        the step that needs `data`, or None where there is no step to
        wait for (no parity, or no admission surface)."""
        batcher = self._batcher()
        if batcher is None or not self.t.m:
            return None
        return batcher.submit_encode_async(self.engine.label, data, self.t.m,
                                           width=shard_size)

    def _finish_rows(self, data: np.ndarray, fut, timeout: float
                     ) -> np.ndarray:
        """Parity rows of `data` from its step's result (computed
        inline where nothing was admitted)."""
        if not self.t.m:
            return data[..., :0, :]
        parity = (fut.result(timeout) if fut is not None
                  else self.engine.encode_parity(data, self.t.m))
        return self._verified(data, parity)

    def _verified(self, data: np.ndarray, parity: np.ndarray) -> np.ndarray:
        if self.cfg.enable_verify and not self.verify(np.concatenate(
                [data[..., :parity.shape[-1]], parity], axis=-2)):
            raise VerifyError("parity verify failed after encode")
        return parity

    def verify(self, shards: np.ndarray) -> bool:
        shards = self._check(shards)
        n, m = self.t.n, self.t.m
        if not m:
            return True
        parity = self.engine.encode_parity(shards[..., :n, :], m)
        return bool(np.array_equal(parity, shards[..., n : n + m, :]))

    def reconstruct(self, shards: np.ndarray, bad_idx: list[int]) -> np.ndarray:
        return self._reconstruct(shards, bad_idx, wanted=sorted(set(bad_idx)))

    def reconstruct_data(self, shards: np.ndarray, bad_idx: list[int]) -> np.ndarray:
        wanted = sorted({i for i in bad_idx if i < self.t.n})
        return self._reconstruct(shards, bad_idx, wanted=wanted)

    def _reconstruct(
        self, shards: np.ndarray, bad_idx: list[int], wanted: list[int]
    ) -> np.ndarray:
        shards = self._check(shards, total=self.t.n + self.t.m)
        if not wanted:
            return shards
        n, total = self.t.n, self.t.n + self.t.m
        bad = set(bad_idx)
        present = [i for i in range(total) if i not in bad]
        if len(present) < n:
            raise ECError(f"unrecoverable: only {len(present)} of {n} shards")
        rows = rs_kernel.reconstruct_rows(n, total, present, wanted)
        if len(wanted) < n:
            # one decode matrix shape per geometry, (n, n), whatever is
            # missing: the device engines compile its one-stripe program
            # with the geometry's encode (engine.ready_decode) and
            # `ready` its coalesced steps, so a survivor set nobody has
            # seen costs a matrix upload, not a compile. The zero rows'
            # outputs are dropped.
            rows = np.concatenate(
                [rows, np.zeros((n - len(wanted), n), dtype=np.uint8)])
        rec = self.engine.matrix_apply(rows, shards[..., present[:n], :])
        shards[..., wanted, :] = rec[..., :len(wanted), :]
        return shards

    def split(self, data: bytes | np.ndarray) -> np.ndarray:
        """Lay a payload into a zero-padded (total, S) stripe (data rows
        filled, parity rows zero until encode)."""
        buf = np.frombuffer(data, dtype=np.uint8) if isinstance(data, (bytes, bytearray)) else np.asarray(data, dtype=np.uint8).ravel()
        if buf.size == 0:
            raise ShortDataError("empty payload")
        s = self.shard_size(buf.size)
        stripe = np.zeros((self.t.total, s), dtype=np.uint8)
        flat = stripe.reshape(-1)
        flat[: buf.size] = buf
        return stripe.reshape(self.t.total, s)

    def join(self, shards: np.ndarray, out_size: int) -> bytes:
        shards = self._check(shards)
        if shards.ndim != 2:
            raise ECError("join takes a single (total, S) stripe, not a batch")
        flat = np.ascontiguousarray(shards[: self.t.n]).reshape(-1)
        if out_size > flat.size:
            raise ECError(f"out_size {out_size} exceeds data capacity {flat.size}")
        return flat[:out_size].tobytes()

    def get_data_shards(self, shards: np.ndarray) -> np.ndarray:
        return shards[..., : self.t.n, :]

    def get_parity_shards(self, shards: np.ndarray) -> np.ndarray:
        return shards[..., self.t.n : self.t.n + self.t.m, :]

    def get_local_shards(self, shards: np.ndarray) -> np.ndarray:
        return shards[..., self.t.total : self.t.total, :]  # empty

    def get_shards_in_idc(self, shards: np.ndarray, az: int) -> np.ndarray:
        n, m, azc = self.t.n, self.t.m, self.t.az_count
        ln, lm = n // azc, m // azc
        idx = list(range(az * ln, (az + 1) * ln)) + list(
            range(n + lm * az, n + lm * (az + 1))
        )
        return shards[..., idx, :]


class MsrEncoder(Encoder):
    """Product-matrix MSR codec: same Encoder interface, but parity and
    reconstruction run over the sub-shard space (each shard is alpha
    rows of beta bytes) so a single-shard repair can pull beta-sized
    helper symbols instead of full shards (ops/msr.py). Shard sizes are
    alpha-aligned at split/encode time so every stored shard divides
    cleanly into sub-shards."""

    @property
    def alpha(self) -> int:
        return self.t.alpha

    def shard_size(self, data_len: int) -> int:
        per = super().shard_size(data_len)
        return -(-per // self.alpha) * self.alpha  # round up to alpha

    def _parity_rows(self):
        t = self.t
        return rs_kernel.msr_encode_rows(t.n, t.n + t.m, t.d)

    def row_width(self, shard_size: int) -> int:
        """Rows are cut into alpha sub-shards before the step, so they
        stay `shard_size` wide; the batcher pads the sub-shards."""
        return shard_size

    def _step_geometry(self, lo: int, hi: int) -> tuple[int, int, int]:
        return (self.t.n * self.alpha, self.shard_size(lo) // self.alpha,
                self.shard_size(hi) // self.alpha)

    def _ready_step(self, b: int, width: int) -> None:
        self.encode_rows_async(np.zeros(
            (b, self.t.n, width * self.alpha), dtype=np.uint8)).wait()

    def _decode_shapes(self, lo_w: int, hi_w: int, max_step_bytes: int,
                       max_batch: int) -> list[tuple[int, int]]:
        """None: a repair's rows over the sub-shards are its own, a
        shape per lost set (`_reconstruct`)."""
        return []

    def _submit_rows(self, data: np.ndarray, shard_size: int):
        batcher = self._batcher()
        if batcher is None:
            return None
        return batcher.submit_apply_async(
            self.engine.label, self._parity_rows(),
            rs_kernel.msr_subshards(data, self.alpha))

    def _finish_rows(self, data: np.ndarray, fut, timeout: float
                     ) -> np.ndarray:
        sub = (fut.result(timeout) if fut is not None
               else self.engine.matrix_apply(
                   self._parity_rows(),
                   rs_kernel.msr_subshards(data, self.alpha)))
        return self._verified(
            data, rs_kernel.msr_join_subshards(sub, self.alpha))

    def verify(self, shards: np.ndarray) -> bool:
        shards = self._check(shards)
        t, alpha = self.t, self.alpha
        sub = rs_kernel.msr_subshards(shards[..., : t.n, :], alpha)
        parity = rs_kernel.msr_join_subshards(
            self.engine.matrix_apply(self._parity_rows(), sub), alpha)
        return bool(np.array_equal(parity, shards[..., t.n:, :]))

    def _reconstruct(
        self, shards: np.ndarray, bad_idx: list[int], wanted: list[int]
    ) -> np.ndarray:
        shards = self._check(shards, total=self.t.total)
        if not wanted:
            return shards
        t, alpha = self.t, self.alpha
        n, total = t.n, t.total
        bad = set(bad_idx)
        present = [i for i in range(total) if i not in bad]
        if len(present) < n:
            raise ECError(f"unrecoverable: only {len(present)} of {n} shards")
        rows = rs_kernel.msr_reconstruct_rows(
            n, total, t.d, tuple(present[:n]), tuple(wanted))
        sub = rs_kernel.msr_subshards(shards[..., present[:n], :], alpha)
        rec = self.engine.matrix_apply(rows, sub)
        shards[..., wanted, :] = rs_kernel.msr_join_subshards(rec, alpha)
        return shards


@functools.cache
def _lrc_rows(t: cm.Tactic) -> np.ndarray:
    """The codemode's composed (m + l, n) parity rows, built once."""
    stripes, ln, _ = t.all_local_stripes()
    rows = rs_kernel.lrc_encode_rows(t.n, t.n + t.m, stripes, ln)
    rows.flags.writeable = False
    return rows


class LrcEncoder(Encoder):
    """Two-level LRC codec: global RS(N+M) plus per-AZ local parity
    RS((N+M)/az, L/az). Local stripes allow intra-AZ reconstruction
    without crossing the DCN (lrcencoder.go:133-186 semantics). Both
    levels are made by one apply of the composed (m + l, n) rows
    (`rows`): one admitted step a PUT, and the one way encode, verify
    and reconstruct make LRC parity."""

    @property
    def _local_nm(self) -> tuple[int, int]:
        t = self.t
        return (t.n + t.m) // t.az_count, t.l // t.az_count

    @property
    def rows(self) -> np.ndarray:
        return _lrc_rows(self.t)

    def _decode_shapes(self, lo_w: int, hi_w: int, max_step_bytes: int,
                       max_batch: int) -> list[tuple[int, int]]:
        """One (n, n) decode a width rung, of one stripe: a degraded GET
        mends a lost data unit inside its AZ's local stripe first
        (blob/access.py `_local_reconstruct`, decodes of its own rows
        that no door builds), so the global decode, and GETs that meet
        in one, are the rare case. An RS encode gets the same program
        from the device engine (engine.ready_decode); a step of
        composed rows does not."""
        return [(1, w) for b, w in rs_kernel.ladder(
            self.t.n, lo_w, hi_w, max_step_bytes, max_batch) if b == 1]

    def _parity(self, data: np.ndarray) -> np.ndarray:
        t = self.t
        if self._batcher() is None:  # a raw engine
            return self.engine.matrix_apply(self.rows, data)
        return self.engine.encode_parity(data, t.m + t.l, rows=self.rows,
                                         local_rows=t.l)

    def _submit_rows(self, data: np.ndarray, shard_size: int):
        batcher = self._batcher()
        if batcher is None:
            return None
        t = self.t
        return batcher.submit_encode_async(
            self.engine.label, data, t.m + t.l, width=shard_size,
            rows=self.rows, local_rows=t.l)

    def _finish_rows(self, data: np.ndarray, fut, timeout: float
                     ) -> np.ndarray:
        parity = fut.result(timeout) if fut is not None else self._parity(data)
        if tracelib.enabled():
            metrics.codec_lrc_local.inc(math.prod(data.shape[:-2]),
                                        how="in_step")
        return self._verified(data, parity)

    def verify(self, shards: np.ndarray) -> bool:
        shards = np.asarray(shards, dtype=np.uint8)
        t = self.t
        ln, lm = self._local_nm
        if shards.shape[-2] == ln + lm:  # a bare local stripe
            parity = self.engine.encode_parity(shards[..., :ln, :], lm)
            return bool(np.array_equal(parity, shards[..., ln:, :]))
        shards = self._check(shards)
        return bool(np.array_equal(self._parity(shards[..., : t.n, :]),
                                   shards[..., t.n:, :]))

    def reconstruct(self, shards: np.ndarray, bad_idx: list[int]) -> np.ndarray:
        shards = np.asarray(shards, dtype=np.uint8)
        t = self.t
        ln, lm = self._local_nm
        if shards.shape[-2] == ln + lm:
            # intra-AZ repair on a bare local stripe (saves DCN bandwidth)
            bad = sorted(set(bad_idx))
            if not bad:
                return shards
            present = [i for i in range(ln + lm) if i not in bad]
            if len(present) < ln:
                raise ECError(
                    f"unrecoverable local stripe: only {len(present)} of {ln} shards"
                )
            rows = rs_kernel.reconstruct_rows(ln, ln + lm, present, bad)
            shards[..., bad, :] = self.engine.matrix_apply(
                rows, shards[..., present[:ln], :]
            )
            return shards
        shards = self._check(shards)
        global_bad = sorted({i for i in bad_idx if i < t.n + t.m})
        if global_bad:
            self._reconstruct(
                shards[..., : t.n + t.m, :], global_bad, wanted=global_bad
            )
        # lost local parities: their rows of `rows` over the (now
        # complete) data shards
        local_bad = sorted({i for i in bad_idx if i >= t.n + t.m})
        if local_bad:
            shards[..., local_bad, :] = self.engine.matrix_apply(
                self.rows[[i - t.n for i in local_bad]], shards[..., : t.n, :])
        return shards

    def reconstruct_data(self, shards: np.ndarray, bad_idx: list[int]) -> np.ndarray:
        t = self.t
        # data recovery only needs the global stripe; accept either the
        # full (N+M+L) layout or just the (N+M) rows (degraded GET path)
        if np.asarray(shards).shape[-2] != t.n + t.m:
            shards = self._check(shards)
        global_bad = [i for i in bad_idx if i < t.n + t.m]
        wanted = sorted({i for i in global_bad if i < t.n})
        if wanted:
            self._reconstruct(shards[..., : t.n + t.m, :], global_bad, wanted=wanted)
        return shards

    def get_local_shards(self, shards: np.ndarray) -> np.ndarray:
        return shards[..., self.t.n + self.t.m :, :]

    def get_shards_in_idc(self, shards: np.ndarray, az: int) -> np.ndarray:
        stripe_idx, _, _ = self.t.local_stripe_in_az(az)
        return shards[..., stripe_idx, :]
