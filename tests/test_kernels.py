"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle vs the
host numpy reference, swept over shapes/dtypes per the task spec."""
import re

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.automaton import compile_rules, match_oracle
from repro.core.patterns import Rule, RuleSet
from repro.core.records import encode_texts
from repro.kernels.bitmap_filter.ops import (bitmap_count, bitmap_match,
                                             bitmap_query_stacked,
                                             bitmap_query_words,
                                             bitmap_select,
                                             _word_query_dispatch)
from repro.kernels.bitmap_filter.ref import bitmap_filter_ref
from repro.kernels.dfa_scan.ops import dfa_scan
from repro.kernels.shift_or.ops import compile_shift_or, shift_or_match

RULES = RuleSet((
    Rule(0, "err", "ERROR"),
    Rule(1, "alt", "fatal|panic"),
    Rule(2, "cls", "usr[0-9]"),
    Rule(3, "short", "a"),
    Rule(4, "long", "averyveryverylongpattern"),
))
ENGINE = compile_rules(RULES)


def _random_texts(rng, n, width):
    words = ["ERROR", "fatal", "panic", "usr3", "usr9x", "quiet", "a", "zz",
             "averyveryverylongpattern", "averyveryverylongpatter"]
    return encode_texts(
        [" ".join(rng.choice(words, size=rng.integers(1, 8))) for _ in range(n)],
        width)


@pytest.mark.parametrize("n", [1, 3, 8, 37, 256])
@pytest.mark.parametrize("width", [16, 64, 512])
def test_dfa_scan_shapes(n, width):
    rng = np.random.default_rng(n * 1000 + width)
    data = _random_texts(rng, n, width)
    want = match_oracle(ENGINE, data)
    args = (jnp.asarray(data), jnp.asarray(ENGINE.delta),
            jnp.asarray(ENGINE.emit), jnp.asarray(ENGINE.byte_classes))
    got_ref = np.asarray(dfa_scan(*args, backend="ref"))
    got_pl = np.asarray(dfa_scan(*args, backend="pallas", block_n=8))
    np.testing.assert_array_equal(got_ref, want)
    np.testing.assert_array_equal(got_pl, want)


@pytest.mark.parametrize("match_rate", ["none", "some", "all"])
def test_dfa_scan_selective(match_rate):
    """Two-pass confirm path agrees with the oracle at every selectivity."""
    from repro.kernels.dfa_scan.ops import dfa_scan_selective
    rng = np.random.default_rng(7)
    if match_rate == "none":
        texts = ["calm quiet"] * 33
    elif match_rate == "all":
        texts = ["ERROR fatal"] * 33
    else:
        texts = [rng.choice(["an ERROR", "ok", "usr3", "x"]) for _ in range(33)]
    data = encode_texts(texts, 32)
    want = match_oracle(ENGINE, data)
    got = dfa_scan_selective(data, ENGINE.delta, ENGINE.emit,
                             ENGINE.byte_classes)
    np.testing.assert_array_equal(got, want)


def test_dfa_scan_parallel_backend():
    small = RuleSet((Rule(0, "a", "ab"), Rule(1, "b", "ba")))
    eng = compile_rules(small, bucket=256)
    rng = np.random.default_rng(0)
    data = _random_texts(rng, 16, 32)
    want = match_oracle(eng, data)
    got = np.asarray(dfa_scan(jnp.asarray(data), jnp.asarray(eng.delta),
                              jnp.asarray(eng.emit),
                              jnp.asarray(eng.byte_classes),
                              backend="parallel"))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n", [1, 5, 64])
@pytest.mark.parametrize("width", [32, 128])
def test_shift_or_vs_oracle(n, width):
    rules = RuleSet(tuple(Rule(i, f"r{i}", p) for i, p in enumerate(
        ["ERROR", "fatal|panic", "usr[0-3]", "a"])))
    eng = compile_rules(rules)
    tables = compile_shift_or(rules)
    rng = np.random.default_rng(n + width)
    data = _random_texts(rng, n, width)
    want = match_oracle(eng, data)
    got_ref = np.asarray(shift_or_match(jnp.asarray(data), tables))[:, :want.shape[1]]
    got_pl = np.asarray(shift_or_match(jnp.asarray(data), tables,
                                       backend="pallas", block_n=8))[:, :want.shape[1]]
    np.testing.assert_array_equal(got_ref, want)
    np.testing.assert_array_equal(got_pl, want)


def test_shift_or_rejects_long_literals():
    rules = RuleSet((Rule(0, "too", "x" * 33),))
    with pytest.raises(ValueError):
        compile_shift_or(rules)


@pytest.mark.parametrize("n", [1, 7, 1024, 2500])
@pytest.mark.parametrize("w", [1, 4, 32])
def test_bitmap_filter_shapes(n, w):
    rng = np.random.default_rng(n + w)
    bm = rng.integers(0, 2**32, size=(n, w), dtype=np.uint32)
    bm[rng.random(n) < 0.7] = 0                      # sparse, like real data
    query = np.zeros(w, np.uint32)
    query[0] = 0b1010
    want = np.asarray(bitmap_filter_ref(jnp.asarray(bm), jnp.asarray(query)))
    got = np.asarray(bitmap_match(jnp.asarray(bm), jnp.asarray(query),
                                  backend="pallas", block_n=256))
    np.testing.assert_array_equal(got, want)
    cnt = bitmap_count(jnp.asarray(bm), jnp.asarray(query), backend="pallas")
    assert int(cnt) == int(want.sum())


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("p, nseg, min_len, max_len, block_n", [
    pytest.param(1, 4, 1, 40, 8, id="1"),
    pytest.param(2, 4, 1, 40, 8, id="2"),
    pytest.param(3, 4, 1, 40, 8, id="3"),
    # 20 ragged segments bucket to 32 slots, rows pad to a block multiple
    pytest.param(2, 20, 1, 400, 128, id="2-20ragged"),
    # above 2**16 rows, so an exact count cannot hide in a narrow type
    pytest.param(3, 20, 3300, 3700, 1024, id="3-wide"),
    # 100 segments bucket to 128 slots: the split one-hot layout
    pytest.param(2, 100, 1, 80, 128, id="2-100seg"),
])
def test_bitmap_query_stacked_multi_segment(backend, p, nseg, min_len,
                                            max_len, block_n):
    """The multi-segment conjunctive entries (full-width masks AND the
    word-sliced fast path) agree with the numpy AND-of-any semantics across
    ragged segment sizes, counts equal the per-segment numpy sums exactly,
    and padded rows/slots never contribute."""
    rng = np.random.default_rng(p * 10 + (backend == "pallas"))
    lens = [int(rng.integers(min_len, max_len)) for _ in range(nseg)]
    N, W = sum(lens), 3
    bm = rng.integers(0, 2**32, size=(N, W), dtype=np.uint32)
    bm[rng.random(N) < 0.5] = 0
    rids = rng.choice(W * 32, size=p, replace=False)
    masks = np.zeros((p, W), np.uint32)
    for i, r in enumerate(rids):
        masks[i, r // 32] = np.uint32(1) << np.uint32(r % 32)
    row_seg = np.repeat(np.arange(nseg, dtype=np.int32), lens)
    want = (((bm[:, None, :] & masks[None]) != 0).any(-1)).all(-1)
    want_counts = [int(want[row_seg == s].sum()) for s in range(nseg)]
    slots = 1 << (nseg - 1).bit_length()

    m, c = bitmap_query_stacked(jnp.asarray(bm), jnp.asarray(masks),
                                jnp.asarray(row_seg), num_segments=nseg,
                                backend=backend, block_n=block_n)
    np.testing.assert_array_equal(np.asarray(m)[:N], want)
    assert not np.asarray(m)[N:].any()          # padded rows never match
    assert np.asarray(c).shape == (slots,)
    assert np.asarray(c)[:nseg].tolist() == want_counts
    assert not np.asarray(c)[nseg:].any()       # padded slots stay zero

    words = jnp.asarray((rids // 32).astype(np.int32))
    cols = jnp.asarray(np.ascontiguousarray(bm[:, np.asarray(rids) // 32]))
    bits = jnp.asarray(masks[np.arange(p), np.asarray(rids) // 32])
    m2, c2 = bitmap_query_words(cols, bits, jnp.asarray(row_seg),
                                num_segments=nseg, backend=backend,
                                block_n=block_n)
    np.testing.assert_array_equal(np.asarray(m2)[:N], want)
    assert np.asarray(c2)[:nseg].tolist() == want_counts
    assert not np.asarray(c2)[nseg:].any()
    m3, c3 = bitmap_query_words(cols, bits, jnp.asarray(row_seg),
                                num_segments=nseg, backend=backend,
                                block_n=block_n, with_counts=False)
    np.testing.assert_array_equal(np.asarray(m3)[:N], want)
    assert c3 is None


@pytest.mark.parametrize("num_segments", [32, 256])
def test_word_query_counts_compile_without_scatter(num_segments):
    """The per-segment count reduction is a dense one-hot contraction: the
    compiled count path holds no scatter, whose colliding slot updates
    serialise (every row of a segment lands on one slot)."""
    n = 1 << 12
    hlo = _word_query_dispatch.lower(
        jnp.zeros((n, 2), jnp.uint32), jnp.ones((2,), jnp.uint32),
        jnp.zeros((n,), jnp.int32), num_segments=num_segments,
        backend="ref", block_n=1024, with_counts=True).compile().as_text()
    assert not re.search(r"\sscatter\(", hlo)   # the op, not a metadata name


def test_bitmap_select_compaction():
    bm = np.zeros((10, 1), np.uint32)
    bm[[2, 5, 9], 0] = 1
    idx, count = bitmap_select(jnp.asarray(bm), jnp.asarray([1], np.uint32),
                               max_out=5)
    assert int(count) == 3
    assert sorted(np.asarray(idx[:3]).tolist()) == [2, 5, 9]
    assert np.asarray(idx[3:]).tolist() == [-1, -1]


def test_kernels_agree_on_1000_rules():
    """The paper's operating point: 1000 patterns, single pass."""
    rules = tuple(Rule(i, f"r{i}", f"QQpat{i:04d}") for i in range(998))
    rules += (Rule(998, "real", "ERROR"), Rule(999, "alt", "fatal|panic"))
    rs = RuleSet(rules)
    eng = compile_rules(rs)
    data = encode_texts(["an ERROR", "fatal stuff", "QQpat0500!", "calm"], 64)
    want = match_oracle(eng, data)
    got = np.asarray(dfa_scan(jnp.asarray(data), jnp.asarray(eng.delta),
                              jnp.asarray(eng.emit),
                              jnp.asarray(eng.byte_classes),
                              backend="pallas", block_n=8))
    np.testing.assert_array_equal(got, want)
    assert want[0, 998 // 32] >> np.uint32(998 % 32) & 1
    assert want[2, 500 // 32] >> np.uint32(500 % 32) & 1
