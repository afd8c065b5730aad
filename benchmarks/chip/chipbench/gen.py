"""Log records of the paper's synthetic workload (FluxSieve §4.3),
generated in bulk with numpy.

The semantics follow ``repro.data.generator``: each content field holds
60 words drawn from a Zipf-distributed vocabulary of 8192 pseudo-words of
3-10 lowercase letters (the same vocabulary, from the configuration's
``vocab_seed``), joined by single spaces and cut to the field width;
planted terms replace one of the first 30 words of a record at a
controlled rate, decided per record index by a stable hash, so counts do
not depend on batching.

To stay fast at a million records, a record is a run of 60 consecutive
words of one long seeded Zipf word stream, starting at a random word:
each record still holds 60 Zipf-distributed words, and the text is a
single strided copy instead of a join per record.  The bytes differ from
``repro.data.generator``'s, which is why the benchmark keeps its own
generator: the yardstick may not move when the program does.
"""
from __future__ import annotations

import hashlib

import numpy as np

WORDS_PER_FIELD = 60
VOCAB_SIZE = 8192
PLANT_WINDOW = 30           # planted terms land among the first 30 words
STREAM_WORDS = 1 << 22      # words in the stream records are cut from
CHUNK = 1 << 16             # records per seeded draw


def make_vocab(seed: int) -> list:
    """``repro.data.generator._make_vocab(default_rng(seed), 8192)``."""
    rng = np.random.default_rng(seed)
    alphabet = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz", np.uint8)
    lengths = rng.integers(3, 11, size=VOCAB_SIZE)
    return [alphabet[rng.integers(0, 26, size=k)].tobytes()
            for k in lengths]


def planted_terms(config: dict) -> list:
    """[(term, field, rate)]: an ultra and a high selectivity term in
    every content field."""
    out = []
    for i in range(1, config["content_fields"] + 1):
        f = f"content{i}"
        out.append((f"ULTRAneedle{i}x", f, config["ultra_rate"]))
        out.append((f"HIGHneedle{i}x", f, config["high_rate"]))
    return out


def plant_mask(term: str, rate: float, start: int, n: int) -> np.ndarray:
    """(n,) bool: which records of [start, start + n) carry ``term``."""
    th = int.from_bytes(hashlib.sha256(term.encode()).digest()[:4], "little")
    mix = (np.arange(start, start + n, dtype=np.uint64)
           * np.uint64(0x9E3779B97F4A7C15) + np.uint64(th))
    mix ^= mix >> np.uint64(31)
    mix *= np.uint64(0xBF58476D1CE4E5B9)
    mix ^= mix >> np.uint64(29)
    return (mix >> np.uint64(11)).astype(np.float64) / float(1 << 53) < rate


class Generator:
    """``records(start, n)`` is pure in (config, seed, start, n)."""

    def __init__(self, config: dict, seed: int):
        self.config = config
        self.seed = int(seed)
        self.width = int(config["text_width"])
        self.fields = tuple(f"content{i}" for i in
                            range(1, config["content_fields"] + 1))
        self.planted = planted_terms(config)
        self.vocab = make_vocab(config["vocab_seed"])
        p = 1.0 / np.arange(1, VOCAB_SIZE + 1)
        cdf = np.cumsum(p / p.sum())
        rng = np.random.default_rng([self.seed, 0])
        self._ids = np.minimum(np.searchsorted(
            cdf, rng.random(STREAM_WORDS), side="right"), VOCAB_SIZE - 1)
        len1 = np.asarray([len(w) + 1 for w in self.vocab], np.int64)
        off = np.cumsum(len1) - len1
        blob = np.frombuffer(b"".join(w + b" " for w in self.vocab), np.uint8)
        wl = len1[self._ids]
        self._wstart = np.concatenate([[0], np.cumsum(wl)])
        src = (np.repeat(off[self._ids] - self._wstart[:-1], wl)
               + np.arange(self._wstart[-1]))
        stream = np.zeros(self._wstart[-1] + self.width, np.uint8)
        stream[:self._wstart[-1]] = blob[src]
        self._windows = np.lib.stride_tricks.sliding_window_view(
            stream, self.width)

    def records(self, start: int, n: int) -> dict:
        """Columns of records [start, start + n): ``timestamp`` (record
        index times 1000), ``status``, ``event_type`` and each content
        field as (n, width) uint8."""
        cols = {"timestamp": np.arange(start, start + n, dtype=np.int64)
                * 1000,
                "status": np.empty(n, np.int32),
                "event_type": np.empty(n, np.int32)}
        for f in self.fields:
            cols[f] = np.empty((n, self.width), np.uint8)
        for a in range(start - start % CHUNK, start + n, CHUNK):
            lo, hi = max(a, start), min(a + CHUNK, start + n)
            self._chunk(a, slice(lo - a, hi - a), cols,
                        slice(lo - start, hi - start))
        return cols

    def _chunk(self, a: int, k: slice, cols: dict, at: slice) -> None:
        rng = np.random.default_rng([self.seed, 1, a])
        cols["status"][at] = rng.integers(0, 5, size=CHUNK)[k]
        cols["event_type"][at] = rng.integers(0, 32, size=CHUNK)[k]
        cut = np.arange(self.width)
        for f in self.fields:
            first = rng.integers(0, STREAM_WORDS - WORDS_PER_FIELD,
                                 size=CHUNK)
            text = self._windows[self._wstart[first[k]]]
            reclen = (self._wstart[first[k] + WORDS_PER_FIELD]
                      - self._wstart[first[k]] - 1)
            text[cut[None, :] >= reclen[:, None]] = 0
            for term, tf, rate in self.planted:
                if tf != f:
                    continue
                rows = np.flatnonzero(plant_mask(term, rate, a, CHUNK))
                pos = rng.integers(0, PLANT_WINDOW, size=len(rows))
                for r, p in zip(rows, pos):
                    if k.start <= r < k.stop:
                        text[r - k.start] = self._planted_row(
                            first[r], p, term)
            cols[f][at] = text

    def _planted_row(self, first: int, pos: int, term: str) -> np.ndarray:
        words = [self.vocab[i] for i in
                 self._ids[first:first + WORDS_PER_FIELD]]
        words[pos] = term.encode()
        line = b" ".join(words)[:self.width]
        out = np.zeros(self.width, np.uint8)
        out[:len(line)] = np.frombuffer(line, np.uint8)
        return out
