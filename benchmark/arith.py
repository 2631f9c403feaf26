"""Byte arithmetic of an allreduce (the model of
``calls/osu_allreduce.py``) and the table of the chip's peaks, which
every call module's ``floor_s`` is given.

The interconnect's peak is per link (``ici_link_bytes_per_s``, one way)
in the table; a chip's peak in a slice is that rate times the links that
the slice wires to it (``ici_links``, from the chips' coords), so a cell
on another layout gets its own peak.

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


def peaks(device_kind: str, links: int, path: Path = PEAKS) -> dict:
    """The published peaks of ``device_kind``, with ``ici_bytes_per_s``,
    what one chip can send at once over the ``links`` that its slice
    wires to it; a kind not in the table is an error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    pk = table[device_kind]
    return {**pk, "ici_bytes_per_s": links * pk["ici_link_bytes_per_s"]}


def ici_links(coords) -> int | None:
    """The fewest ICI links any chip of the slice has to the slice's
    other chips: the chips at distance 1 from it in the coords grid
    (``device.coords``), 2 on a 2x2 and 0 on one chip.  None where a
    chip has no coords, as on the CPU."""
    coords = [None if c is None else tuple(c) for c in coords]
    if not coords or None in coords:
        return None
    return min(sum(1 for o in coords
                   if sum(abs(a - b) for a, b in zip(c, o)) == 1)
               for c in coords)


def allreduce_floor_s(nbytes: int, n: int, pk: dict) -> tuple[float, str]:
    """Least time one chip can take for an n-rank allreduce of ``nbytes``
    per rank, and which bound sets it: the bytes each chip sends,
    ``2(n-1)/n * S``, over the chip's interconnect rate, or reading the
    message and writing the result, ``2S``, over HBM bandwidth.  One
    rank sends nothing."""
    ici = bus_bytes(nbytes, n) / pk["ici_bytes_per_s"] if n > 1 else 0.0
    hbm = 2.0 * nbytes / pk["hbm_bytes_per_s"]
    return (ici, "ici") if ici >= hbm else (hbm, "hbm")
