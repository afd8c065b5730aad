"""Fused multi-field dispatch: byte-identical equivalence with the per-field
loop across every backend, single-D2H accounting on the enrich path, and
jit-retrace stability across ragged/tail batch sizes."""
import numpy as np
import pytest

from repro.core import matcher as matcher_mod
from repro.core.automaton import compile_rules, match_oracle
from repro.core.matcher import EngineBundle, FusedMatcher, compile_bundle
from repro.core.patterns import Rule, RuleSet
from repro.core.records import RecordBatch, encode_texts
from repro.core.stream_processor import ENRICH_COLUMN, StreamProcessor
from repro.kernels.dfa_scan import ops as dfa_ops

FIELDS = ("content1", "content2", "content3")
WORDS = ["ERROR", "fatal", "panic", "usr3", "quiet", "calm", "zz",
         "needleA", "needleB", "overlapAB", "xyzzy"]


def random_ruleset(rng, num_rules: int) -> RuleSet:
    """Literal-only rules (<= 32 B, so shift_or qualifies), a mix of
    field-scoped and '*' rules, some shared across fields so single records
    can match in multiple fields."""
    rules = []
    for i in range(num_rules):
        term = rng.choice(WORDS)
        fields = ("*",) if rng.random() < 0.4 else \
            (FIELDS[rng.integers(0, len(FIELDS))],)
        rules.append(Rule(i, f"r{i}", str(term), fields=fields))
    return RuleSet(tuple(rules))


def random_batch(rng, n: int, width: int = 64) -> RecordBatch:
    cols = {"timestamp": np.arange(n, dtype=np.int64)}
    for f in FIELDS:
        texts = [" ".join(rng.choice(WORDS, size=rng.integers(1, 6)))
                 for _ in range(n)]
        cols[f] = encode_texts(texts, width)
    return RecordBatch(cols)


def oracle_bitmap(bundle: EngineBundle, batch: RecordBatch) -> np.ndarray:
    """Ground truth: numpy per-field loop over the compiled automata."""
    bm = np.zeros((len(batch), bundle.words), np.uint32)
    for fieldname in bundle.fields:
        eng = bundle.engines[fieldname]
        cols = batch.text_fields if fieldname == "*" else \
            ((fieldname,) if fieldname in batch.text_fields else ())
        for c in cols:
            bm |= match_oracle(eng, batch.columns[c])
    return bm


@pytest.mark.parametrize("backend",
                         ["dfa", "dfa_ref", "dfa_selective", "shift_or"])
@pytest.mark.parametrize("seed", [0, 1])
def test_backend_equivalence_randomized(backend, seed):
    """Every backend — fused (dfa/dfa_ref) or per-field fallback — produces
    byte-identical bitmaps on randomized rulesets, including ragged tail
    batch sizes and the empty batch."""
    rng = np.random.default_rng(seed)
    ruleset = random_ruleset(rng, num_rules=24)
    bundle = compile_bundle(ruleset, FIELDS)
    proc = StreamProcessor(bundle, backend=backend, block_n=8)
    for n in (0, 1, 5, 37):
        batch = random_batch(rng, n)
        got = np.asarray(proc.process(batch).columns[ENRICH_COLUMN])
        want = oracle_bitmap(bundle, batch)
        np.testing.assert_array_equal(got, want, err_msg=f"{backend} n={n}")


@pytest.mark.parametrize("backend", ["dfa", "dfa_ref"])
def test_fused_matches_per_field_loop(backend):
    """The fused dispatcher's OR-of-fields equals the per-field
    MatchEngine.match loop bit for bit."""
    rng = np.random.default_rng(2)
    ruleset = random_ruleset(rng, num_rules=16)
    bundle = compile_bundle(ruleset, FIELDS)
    batch = random_batch(rng, 21)
    fused = FusedMatcher(bundle, backend=backend, block_n=8)
    bm, mask = fused.match_batch(batch.columns, batch.text_fields,
                                 len(batch)).to_host()
    want = oracle_bitmap(bundle, batch)
    np.testing.assert_array_equal(bm, want)
    np.testing.assert_array_equal(mask, want.any(axis=1))


def test_fused_parallel_backend():
    """The associative-scan backend fuses too (small-automaton bundles)."""
    rs = RuleSet((Rule(0, "a", "ab", fields=("content1",)),
                  Rule(1, "b", "ba", fields=("*",))))
    engines = {f: compile_rules(rs, f, bucket=256)
               for f in ("content1", "content2")}
    bundle = EngineBundle(version=rs.version_hash(), num_rules=rs.num_rules,
                          engines=engines, ruleset_json=rs.to_json())
    batch = RecordBatch({
        "content1": encode_texts(["abba", "zz", "xbax"], 16),
        "content2": encode_texts(["zz", "ab", "zz"], 16),
    })
    fused = FusedMatcher(bundle, backend="parallel", block_n=8)
    bm, _ = fused.match_batch(batch.columns, batch.text_fields,
                              len(batch)).to_host()
    np.testing.assert_array_equal(bm, oracle_bitmap(bundle, batch))


@pytest.mark.parametrize("backend", ["dfa", "dfa_ref"])
def test_shared_star_engine_deduped(backend):
    """A '*' engine matched against every text column is stored ONCE in the
    fused plan (eng_idx maps all slots to one table row) and still yields
    oracle-identical bitmaps."""
    rng = np.random.default_rng(5)
    rs = RuleSet((Rule(0, "e", "ERROR", fields=("*",)),
                  Rule(1, "p", "panic", fields=("*",))))
    bundle = compile_bundle(rs, ("*",))
    batch = random_batch(rng, 19)
    fused = FusedMatcher(bundle, backend=backend, block_n=8)
    bm, _ = fused.match_batch(batch.columns, batch.text_fields,
                              len(batch)).to_host()
    plan = fused._plan(batch.text_fields)
    # both lanes keep one table for three slots (the Pallas kernel takes the
    # slot->row map as a scalar-prefetch operand of its BlockSpecs)
    assert plan.eng_idx == (0,) * len(FIELDS)
    assert plan.deltas.shape[0] == 1
    np.testing.assert_array_equal(bm, oracle_bitmap(bundle, batch))


def test_multi_field_matches_merge():
    """A record matching different rules in different fields carries the OR
    of all of them."""
    rs = RuleSet((Rule(0, "e", "ERROR", fields=("content1",)),
                  Rule(1, "u", "usr3", fields=("content2",)),
                  Rule(2, "any", "panic", fields=("*",))))
    bundle = compile_bundle(rs, ("content1", "content2"))
    batch = RecordBatch({
        "content1": encode_texts(["ERROR panic", "calm"], 32),
        "content2": encode_texts(["usr3 here", "panic"], 32),
    })
    proc = StreamProcessor(bundle, backend="dfa_ref")
    bm = np.asarray(proc.process(batch).columns[ENRICH_COLUMN])
    assert bm[0, 0] == 0b111          # rules 0, 1, 2 all set on record 0
    assert bm[1, 0] == 0b100          # panic via content2 '*' on record 1


@pytest.mark.parametrize("backend", ["dfa", "dfa_ref"])
def test_single_d2h_transfer_per_batch(backend):
    """The enrich path performs exactly ONE device-to-host transfer per
    processed batch: the counted MatchResult.to_host hook fires once, and
    jax's transfer guard proves no other (implicit) D2H sneaks in."""
    import jax
    rng = np.random.default_rng(3)
    bundle = compile_bundle(random_ruleset(rng, 8), FIELDS)
    proc = StreamProcessor(bundle, backend=backend, block_n=8)
    proc.process(random_batch(rng, 16))            # warmup/compile
    before = matcher_mod.transfer_count()
    with jax.transfer_guard_device_to_host("disallow"):
        # only the explicit jax.device_get inside to_host is permitted;
        # any np.asarray-style implicit transfer raises here
        for _ in range(4):
            proc.process(random_batch(rng, 16))
    assert matcher_mod.transfer_count() - before == 4


def test_no_retrace_across_batch_sizes():
    """After warming the N shape buckets, varying batch sizes (tail batches
    included) must not trigger new jit traces."""
    rng = np.random.default_rng(4)
    bundle = compile_bundle(random_ruleset(rng, 8), FIELDS)
    proc = StreamProcessor(bundle, backend="dfa_ref", block_n=8)
    for n in (8, 16, 32, 64):                      # warm buckets 8..64
        proc.process(random_batch(rng, n))
    before = dict(dfa_ops.TRACE_COUNTS)
    for n in (3, 7, 12, 33, 64, 20, 5, 48):       # all land in warm buckets
        proc.process(random_batch(rng, n))
    assert dict(dfa_ops.TRACE_COUNTS) == before


def test_bucket_n():
    assert dfa_ops.bucket_n(0, 256) == 256
    assert dfa_ops.bucket_n(1, 256) == 256
    assert dfa_ops.bucket_n(256, 256) == 256
    assert dfa_ops.bucket_n(257, 256) == 512
    assert dfa_ops.bucket_n(4096, 256) == 4096
    assert dfa_ops.bucket_n(4097, 256) == 8192
    assert dfa_ops.bucket_n(100, 8) == 128
    # non-power-of-two block_n still yields block-aligned buckets
    assert dfa_ops.bucket_n(25, 24) % 24 == 0
