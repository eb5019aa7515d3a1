"""What a compaction job has to move through device memory, whatever
program does it, and the chip's peaks. Bytes come from rows and key width,
never from a program's array shapes.

  bytes = rows_in x (key_bytes + 8)   each internal key read once
        + rows_out x 4                one source index written per survivor
Values stay on the host.
"""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def job_bytes(rows_in: int, rows_out: int, key_bytes: int) -> int:
    return rows_in * (key_bytes + 8) + rows_out * 4


def peaks(device_kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(
            f"device kind {device_kind!r} is not in {_PEAKS}: add its peaks "
            "with their source before measuring on it")
    return table["devices"][device_kind]


def least_seconds(n_bytes: int, device_kind: str) -> float:
    """The least time one chip needs for n_bytes: these kernels move bytes,
    so the HBM bound is the roofline."""
    return n_bytes / peaks(device_kind)["hbm_bytes_per_s"]
