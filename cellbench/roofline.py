"""What a GF(2^8) matrix apply needs, from its shapes alone, and the
least time the chip could take for it. The kernels multiply an (8R x 8C)
bit matrix into (8C x S) bit planes per stripe: B stripes read B*C*S
bytes, write B*R*S bytes and make 2 * 64 * R * C * S * B int8
operations (a multiply and an add per bit pair)."""

from __future__ import annotations

import os

from . import spec


def peaks(device_kind: str) -> dict:
    table = spec.load_json(os.path.join(spec.HERE, "peaks.json"))["peaks"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"cellbench/peaks.json; have {sorted(table)}")
    return table[device_kind]


def gf_apply_bytes(b: int, c: int, r: int, s: int) -> int:
    return b * (c + r) * s


def gf_apply_ops(b: int, c: int, r: int, s: int) -> int:
    return 2 * 64 * r * c * s * b


def least_seconds(calls, peak: dict) -> tuple[float, str]:
    """(least time for these calls, which peak bounds it); ``calls`` are
    dicts with b, c, r, s."""
    by_bytes = sum(gf_apply_bytes(k["b"], k["c"], k["r"], k["s"])
                   for k in calls) / peak["hbm_bytes_per_s"]
    by_ops = sum(gf_apply_ops(k["b"], k["c"], k["r"], k["s"])
                 for k in calls) / peak["int8_ops_per_s"]
    return (by_bytes, "hbm") if by_bytes >= by_ops else (by_ops, "int8")
