"""Enrichment representations for match metadata (paper §3.1, §5.1, §6.1).

The native in-framework format is a **packed rule bitmap** — ``(N, W) uint32``
with bit ``r`` of word ``r // 32`` set iff rule ``r`` matched the record.
Fixed width, shardable, bit-addressable at query time, and maximally
RLE/bit-pack friendly for columnar storage (most records are all-zero under
high selectivity).

The paper's two materializations are provided for fidelity benchmarks:
  * Pinot layout  — one boolean column per rule (``to_bool_columns``);
  * DuckDB layout — a sparse ``matched_rule_ids INT[]`` array
    (``to_sparse_ids``: fixed-capacity, -1 padded — the jit-able analogue).
"""
from __future__ import annotations

import numpy as np

from repro.core.automaton import WORD_BITS, words_for_rules


def rule_mask(rule_ids, num_rules: int) -> np.ndarray:
    """Query-time mask: (W,) uint32 with the given rule bits set."""
    W = words_for_rules(num_rules)
    mask = np.zeros(W, np.uint32)
    for r in rule_ids:
        if not 0 <= r < num_rules:
            raise ValueError(f"rule id {r} out of range [0, {num_rules})")
        mask[r // WORD_BITS] |= np.uint32(1 << (r % WORD_BITS))
    return mask


def bitmap_get(bm: np.ndarray, rule_id: int) -> np.ndarray:
    """(N, W) -> (N,) bool for a single rule."""
    w, b = rule_id // WORD_BITS, rule_id % WORD_BITS
    return (np.asarray(bm)[:, w] >> np.uint32(b)) & np.uint32(1) != 0


def to_bool_columns(bm: np.ndarray, num_rules: int) -> np.ndarray:
    """Pinot layout: (N, W) uint32 -> (N, num_rules) bool."""
    bm = np.ascontiguousarray(bm)     # device results may come back F-order
    N, W = bm.shape
    bits = np.unpackbits(bm.view(np.uint8).reshape(N, W, 4),
                         axis=-1, bitorder="little")       # (N, W, 32)
    return bits.reshape(N, W * WORD_BITS)[:, :num_rules].astype(bool)


def from_bool_columns(cols: np.ndarray) -> np.ndarray:
    """(N, num_rules) bool -> (N, W) uint32 packed bitmap."""
    cols = np.asarray(cols, bool)
    N, R = cols.shape
    W = words_for_rules(R)
    pad = np.zeros((N, W * WORD_BITS), np.uint8)
    pad[:, :R] = cols
    packed = np.packbits(pad.reshape(N, W, WORD_BITS), axis=-1,
                         bitorder="little")                # (N, W, 4) uint8
    return packed.reshape(N, W * 4).view(np.uint32)


def to_sparse_ids(bm: np.ndarray, max_matches: int = 8) -> np.ndarray:
    """DuckDB layout: (N, W) -> (N, max_matches) int32 rule ids, -1 padded.

    Records matching more than ``max_matches`` rules keep the lowest ids
    (benchmarks size the capacity so this never truncates)."""
    bm = np.asarray(bm)
    R = bm.shape[1] * WORD_BITS
    cols = to_bool_columns(bm, R)                          # (N, R)
    ids = np.argsort(~cols, axis=1, kind="stable")[:, :max_matches]
    valid = np.take_along_axis(cols, ids, axis=1)
    return np.where(valid, ids, -1).astype(np.int32)


def from_sparse_ids(ids: np.ndarray, num_rules: int) -> np.ndarray:
    ids = np.asarray(ids)
    N = ids.shape[0]
    W = words_for_rules(num_rules)
    bm = np.zeros((N, W), np.uint32)
    rows, cols = np.nonzero(ids >= 0)
    r = ids[rows, cols]
    np.bitwise_or.at(bm, (rows, r // WORD_BITS),
                     (np.uint32(1) << (r % WORD_BITS).astype(np.uint32)))
    return bm


def popcount(bm: np.ndarray) -> np.ndarray:
    """(N, W) -> (N,) number of matched rules per record."""
    bm = np.ascontiguousarray(bm)
    return np.unpackbits(bm.view(np.uint8), axis=-1).sum(axis=-1)


def any_match(bm: np.ndarray) -> np.ndarray:
    """(N, W) -> (N,) bool: record matched at least one rule."""
    return np.asarray(bm).any(axis=1)


def storage_nbytes(bm: np.ndarray, layout: str, num_rules: int,
                   max_matches: int = 8) -> int:
    """Raw (pre-compression) footprint of each enrichment layout."""
    bm = np.asarray(bm)
    if layout == "bitmap":
        return bm.nbytes
    if layout == "bools":
        return bm.shape[0] * num_rules  # 1 byte per boolean column value
    if layout == "sparse":
        # list<int32> with per-row length prefix
        return int(popcount(bm).clip(max=max_matches).sum()) * 4 + bm.shape[0] * 4
    raise ValueError(layout)
