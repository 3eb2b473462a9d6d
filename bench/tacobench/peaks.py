"""The chip peaks every roofline reads, from ``bench/peaks.json``, keyed by
``device_kind`` as JAX reports it. A device missing from the table is an
error, never a default."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parents[1] / "peaks.json"


class UnknownDevice(KeyError):
    pass


def peak(device_kind: str, table: Path = TABLE) -> dict:
    """``{"flops_per_s", "hbm_bytes_per_s", "hbm_bytes", ...}`` of one chip."""
    peaks = json.loads(Path(table).read_text())
    if device_kind not in peaks:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {table}; "
            f"known: {sorted(peaks)}")
    return peaks[device_kind]
