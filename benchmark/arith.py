"""Byte arithmetic of an allreduce (the model of
``calls/osu_allreduce.py``) and the table of the chip's peaks, which
every call module's ``floor_s`` is given.

``bus_bytes`` follows the OSU/NCCL bus-bandwidth model: an allreduce
of S bytes per rank over n ranks moves ``2(n-1)/n * S`` through each
rank's link, so a bus rate compares across rank counts.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def bus_factor(n: int) -> float:
    return 2.0 * (n - 1) / n


def bus_bytes(nbytes: int, n: int) -> float:
    return bus_factor(n) * nbytes


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The published peaks of ``device_kind``; a kind not in the table
    is an error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def allreduce_floor_s(nbytes: int, n: int, pk: dict) -> tuple[float, str]:
    """Least time one chip can take for an n-rank allreduce of ``nbytes``
    per rank, and which bound sets it: the bytes each chip sends,
    ``2(n-1)/n * S``, over the chip's interconnect rate, or reading the
    message and writing the result, ``2S``, over HBM bandwidth."""
    ici = bus_bytes(nbytes, n) / pk["ici_bytes_per_s"]
    hbm = 2.0 * nbytes / pk["hbm_bytes_per_s"]
    return (ici, "ici") if ici >= hbm else (hbm, "hbm")
