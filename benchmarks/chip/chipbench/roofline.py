"""Bytes a kernel has to move, from the shapes it was called with, and
its share of the chip's roofline.

The least time a call can take is the larger of its operations over the
peak operation rate and its bytes over the peak memory bandwidth.  The
two kernels here (the ``dfa_ref`` scan at ingest and the word query at
query time) do integer gathers, compares and bit operations, for which
the peaks table has no rate, so their bound is bytes over bandwidth: the
memory bound.  Bytes count each input read once, each output written
once, and each table read once per call, at the padded shapes the device
runs.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str, path=PEAKS) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an
    error, never a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def bucket(n: int, block: int) -> int:
    """The padded row count of a call: a power of two, at least
    ``block``, rounded up to a multiple of ``block``."""
    n = max(n, 1)
    if n <= block:
        return block
    p = 1 << (n - 1).bit_length()
    return (p + block - 1) // block * block


def dfa_ref_bytes(*, fields: int, rows: int, width: int, words: int,
                  engines: int, states: int, classes: int) -> int:
    """One fused ``dfa_ref`` call: the (F, N, L) uint8 text in, the
    (N, W) uint32 bitmap and (N,) bool any-match out, and the tables
    (byte-class LUT, transitions, emit rows) of each engine."""
    text = fields * rows * width
    out = rows * words * 4 + rows
    tables = engines * (256 * 4 + states * classes * 4 + states * words * 4)
    return text + out + tables


def word_query_bytes(*, rows: int, preds: int, with_counts: bool,
                     segments: int) -> int:
    """One stacked word query: the (N, P) uint32 word columns and (P,)
    masks in, the (N,) bool match out, and with counts the (N,) int32
    segment slots in and the per-segment counts out."""
    total = rows * preds * 4 + preds * 4 + rows
    if with_counts:
        total += rows * 4 + segments * 4
    return total


def share_pct(nbytes: float, device_s: float, peak: dict):
    """Percent of the memory roofline, or None without device time."""
    if device_s <= 0:
        return None
    return 100.0 * nbytes / (peak["hbm_bytes_per_s"] * device_s)
