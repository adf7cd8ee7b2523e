"""The one table of chip peaks (``bench/peaks.json``), keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an
error, never a default."""

from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parents[1] / "peaks.json"


class UnknownDevice(KeyError):
    """The device kind has no row in the peaks table."""


def load() -> dict:
    return json.loads(TABLE.read_text())


def peaks(device_kind: str, table: dict | None = None) -> dict:
    """``{"flops_per_s": {dtype: rate}, "hbm_bytes_per_s": rate, ...}`` of
    ``device_kind``; raises :class:`UnknownDevice` if it is not listed."""
    table = load() if table is None else table
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {TABLE.name}; "
            f"listed: {sorted(table['devices'])}") from None


def flops_peak(device_kind: str, dtype: str, table: dict | None = None):
    row = peaks(device_kind, table)["flops_per_s"]
    if dtype not in row:
        raise UnknownDevice(f"no {dtype} peak for {device_kind!r}")
    return float(row[dtype])
