"""Compile + wrap the shift-AND matcher: literal packing, kernel dispatch,
match-word -> rule-bitmap mapping.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.patterns import RuleSet
from repro.kernels.shift_or.ref import shift_or_ref
from repro.kernels.shift_or.shift_or import shift_or_kernel, BLOCK_N

WORD = 32
MAX_LIT = 32


@dataclass(frozen=True)
class ShiftOrTables:
    tbl: np.ndarray          # (256, Wb) uint32
    init_mask: np.ndarray    # (Wb,) uint32
    final_mask: np.ndarray   # (Wb,) uint32
    lit_word: np.ndarray     # (n_lits,) int32 word holding each literal's end bit
    lit_bit: np.ndarray      # (n_lits,) int32 end-bit offset
    lit_rule: np.ndarray     # (n_lits,) int32 rule id
    num_rules: int
    version: str


def compile_shift_or(ruleset: RuleSet, field: str = "*") -> ShiftOrTables:
    rules = ruleset.rules_for_field(field) if field != "*" else list(ruleset.rules)
    lits = []
    for r in rules:
        for lit in r.literals():
            b = lit.encode()
            if len(b) > MAX_LIT:
                raise ValueError(
                    f"shift_or supports literals <= {MAX_LIT} B; "
                    f"rule {r.name!r} has {len(b)} — use dfa_scan")
            lits.append((r.rule_id, b))
    # first-fit pack into 32-bit words
    words: list = []      # remaining free bits per word
    placement = []        # (word, offset) per literal
    for _, b in lits:
        ln = len(b)
        for w, free in enumerate(words):
            if free >= ln:
                placement.append((w, WORD - free))
                words[w] -= ln
                break
        else:
            words.append(WORD - ln)
            placement.append((len(words) - 1, 0))
    Wb = max(1, len(words))
    tbl = np.zeros((256, Wb), np.uint32)
    init = np.zeros(Wb, np.uint32)
    final = np.zeros(Wb, np.uint32)
    lw, lb, lr = [], [], []
    for (rid, b), (w, off) in zip(lits, placement):
        init[w] |= np.uint32(1 << off)
        final[w] |= np.uint32(1 << (off + len(b) - 1))
        for j, ch in enumerate(b):
            tbl[ch, w] |= np.uint32(1 << (off + j))
        lw.append(w)
        lb.append(off + len(b) - 1)
        lr.append(rid)
    return ShiftOrTables(tbl=tbl, init_mask=init, final_mask=final,
                         lit_word=np.array(lw, np.int32),
                         lit_bit=np.array(lb, np.int32),
                         lit_rule=np.array(lr, np.int32),
                         num_rules=ruleset.num_rules,
                         version=ruleset.version_hash())


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("num_rules",))
def _match_words_to_bitmap(M, lit_word, lit_bit, lit_rule, *, num_rules: int):
    """(N, Wb) match words -> (N, W) packed rule bitmaps."""
    W = max(1, (num_rules + WORD - 1) // WORD)
    ew = jnp.take(M, lit_word, axis=1)                          # (N, n_lits)
    hit = (ew >> lit_bit.astype(jnp.uint32)) & jnp.uint32(1)    # per-literal
    # literal -> rule (OR over literals of a rule), then pack; clamp: rules
    # with no literal in this field's engine get int32-min from the empty
    # segment_max, which must read as "no match", not a stray bit
    rule_hit = jax.ops.segment_max(hit.T.astype(jnp.int32), lit_rule,
                                   num_segments=num_rules).T    # (N, num_rules)
    rule_hit = jnp.maximum(rule_hit, 0)
    word_idx = jnp.arange(num_rules) // WORD
    bit = (rule_hit.astype(jnp.uint32) << (jnp.arange(num_rules) % WORD).astype(jnp.uint32))
    bm = jax.ops.segment_sum(bit.T, word_idx, num_segments=W).T  # sum == or (distinct bits)
    return bm.astype(jnp.uint32)


def shift_or_match(data, tables: ShiftOrTables, *, backend: str = "ref",
                   block_n: int = BLOCK_N):
    """data: (N, L) uint8 -> (N, W) uint32 rule bitmaps."""
    N = data.shape[0]
    tbl = jnp.asarray(tables.tbl)
    I = jnp.asarray(tables.init_mask)
    F = jnp.asarray(tables.final_mask)
    if backend == "pallas":
        n_pad = _round_up(max(N, 1), block_n)
        d = jnp.pad(data, ((0, n_pad - N), (0, 0))).astype(jnp.int32)
        M = shift_or_kernel(d, tbl, I[None], F[None], block_n=block_n)[:N]
    else:
        M = shift_or_ref(data, tbl, I, F)
    return _match_words_to_bitmap(
        M, jnp.asarray(tables.lit_word), jnp.asarray(tables.lit_bit),
        jnp.asarray(tables.lit_rule), num_rules=tables.num_rules)
