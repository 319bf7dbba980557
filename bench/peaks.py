"""The chip's published peaks, looked up by ``device_kind``. A device that
is not in ``peaks.json`` is an error, never a default."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str, table: dict | None = None) -> dict:
    table = table if table is not None else json.loads(PEAKS_FILE.read_text())
    try:
        return dict(table["devices"][device_kind])
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r} in "
            f"{PEAKS_FILE.name}; known: {sorted(table['devices'])}") from None


def roofline_share(flops: float, nbytes: float, seconds: float,
                   device_kind: str) -> tuple[float, str] | None:
    """Least time the chip could take (the larger of FLOPs over the bf16
    peak and bytes over HBM bandwidth) as a percentage of ``seconds``, and
    which of the two bounds it. ``None`` when nothing was timed."""
    if seconds <= 0:
        return None
    p = peaks(device_kind)
    t_flops = flops / p["bf16_flops"]
    t_bytes = nbytes / p["hbm_bytes_per_s"]
    bound = "hbm" if t_bytes >= t_flops else "compute"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
