"""The plain reference: which records contain a term, by byte search over
the generated text, with the standard library and numpy alone.

A rule matches a record when its literal occurs in one of the rule's
fields; a query is the conjunction of its (field, term) predicates.  A
rare term is found by ``bytes.find`` over the whole column, each hit kept
when it lies inside one row; a dense one by a shifted comparison of the
column with the term.  Both are exact.
"""
from __future__ import annotations

import numpy as np

ROWS = 1 << 15              # rows compared per step (bounds temporaries)
SAMPLE = 1 << 20            # bytes sampled to tell a dense term
DENSE = 256                 # hits in the sample above which a term is dense


class Corpus:
    """The content columns of some records, searchable by term."""

    def __init__(self, cols: dict):
        self.cols = {f: np.ascontiguousarray(v) for f, v in cols.items()
                     if f.startswith("content")}
        self.fields = tuple(sorted(self.cols))
        self.n = len(self.cols[self.fields[0]])
        self._bytes = {}
        self._memo = {}

    def contains(self, field: str, term: str) -> np.ndarray:
        """(N,) bool: ``term`` occurs in the row's ``field``."""
        key = (field, term)
        if key not in self._memo:
            self._memo[key] = self._search(self.cols[field], field,
                                           term.encode())
        return self._memo[key]

    def _search(self, text: np.ndarray, field: str, t: bytes) -> np.ndarray:
        n, width = text.shape
        m = len(t)
        out = np.zeros(n, bool)
        if m == 0 or m > width:
            return out
        raw = self._bytes.get(field)
        if raw is None:
            raw = self._bytes[field] = text.tobytes()
        if raw.count(t, 0, SAMPLE) <= DENSE:
            i = raw.find(t)
            while i >= 0:
                if i % width + m <= width:
                    out[i // width] = True
                i = raw.find(t, i + 1)
            return out
        tt = np.frombuffer(t, np.uint8)
        for a in range(0, n, ROWS):
            block = text[a:a + ROWS]
            acc = block[:, :width - m + 1] == tt[0]
            for j in range(1, m):
                acc &= block[:, j:width - m + 1 + j] == tt[j]
            out[a:a + ROWS] = acc.any(axis=1)
        return out

    def rule_bitmaps(self, rules: list, words: int) -> np.ndarray:
        """(N, words) uint32 with bit r set where rule r matches.
        ``rules`` is [(rule_id, term, fields)]; fields ``("*",)`` means
        every content field."""
        out = np.zeros((self.n, words), np.uint32)
        for rid, term, rfields in rules:
            hit = np.zeros(self.n, bool)
            for f in (self.fields if "*" in rfields else rfields):
                hit |= self.contains(f, term)
            out[hit, rid // 32] |= np.uint32(1 << (rid % 32))
        return out

    def answer(self, terms) -> np.ndarray:
        """Sorted indices of the rows that match every (field, term)."""
        hit = np.ones(self.n, bool)
        for f, term in terms:
            hit &= self.contains(f, term)
        return np.flatnonzero(hit)
