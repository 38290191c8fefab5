"""Roofline shares: the least time the chip could take over the time it
took."""
from __future__ import annotations


def hbm_share(rec: dict, backend: str, programs: tuple):
    """100 x needed bytes of ``backend``'s queries / (device seconds of
    ``programs`` x peak HBM bytes/s); None where either is missing."""
    tr, peaks = rec["trace"], rec["peaks"]
    need = rec["needed_bytes"].get(backend, 0)
    if tr is None or peaks is None or not need:
        return None
    secs = sum(s for name, s in tr["program_s"].items() if name in programs)
    if secs <= 0:
        return None
    return 100.0 * need / (secs * float(peaks["hbm_bytes_per_s"]))
