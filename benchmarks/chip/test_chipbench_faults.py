"""The comparison that decides ``correct`` fails where it should: the
control (the reference in the program's place with one guarantee
broken), and the timed path broken underneath in each way a one-chip
cell can break.  A cell on one chip has no exchange between chips to
leave out."""
import numpy as np
import pytest

from chipbench import testkit

INGEST, QUERY = "tiny.tiny-ingest", "tiny.tiny-query"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return testkit.make_root(tmp_path_factory.mktemp("chipbench"))


def _failed_checks(res):
    return {n for n, c in res["checks"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("cell,failing", [
    (INGEST, {"rows_missing"}),
    (QUERY, {"wrong_answers"}),
])
def test_control_fails(root, cell, failing):
    res = testkit.run(root, cell, seed=31, control=1)
    assert res["correct"] is False
    assert failing <= _failed_checks(res)


def _store_unchanged(mp):
    from repro.core.query.store import SegmentStore
    mp.setattr(SegmentStore, "append", lambda self, batch: None)


def _half_batch_ingest(mp):
    from repro.core.stream_processor import StreamProcessor
    finalize = StreamProcessor.finalize

    def half(self, pending):
        out = finalize(self, pending)
        return out.slice(0, len(out) // 2)
    mp.setattr(StreamProcessor, "finalize", half)


def _bitmap_altered(mp):
    from repro.core import matcher
    scan = matcher.dfa_scan_fused

    def altered(*a, **k):
        bm, mask = scan(*a, **k)
        return bm.at[0, 0].set(bm[0, 0] ^ 1), mask
    mp.setattr(matcher, "dfa_scan_fused", altered)


def _query_kernel(rows):
    """The stacked query kernel with ``rows(n)`` of its n rows' matches
    cleared: all of them for a step that leaves its output as it was,
    the second half for half of the batch left out."""
    def patch(mp):
        from repro.kernels.bitmap_filter import ops
        words = ops.bitmap_query_words

        def broken(cols, *a, **k):
            n = cols.shape[0]
            keep = np.arange(n) < n - rows(n)
            return words(cols * keep[:, None].astype(cols.dtype), *a, **k)
        mp.setattr(ops, "bitmap_query_words", broken)
    return patch


def _answer_altered(mp):
    from repro.serve import frontend
    payload = frontend.result_payload

    def altered(res, mode):
        out = payload(res, mode)
        out["count"] += 1
        return out
    mp.setattr(frontend, "result_payload", altered)


@pytest.mark.parametrize("cell,fault", [
    (INGEST, _store_unchanged),
    (INGEST, _half_batch_ingest),
    (INGEST, _bitmap_altered),
    (QUERY, _query_kernel(lambda n: n)),
    (QUERY, _query_kernel(lambda n: n // 2)),
    (QUERY, _answer_altered),
], ids=["ingest-state-unchanged", "ingest-half-batch",
        "ingest-bitmap-altered", "query-state-unchanged",
        "query-half-batch", "query-answer-altered"])
def test_broken_path_is_not_correct(root, monkeypatch, cell, fault):
    fault(monkeypatch)
    res = testkit.run(root, cell, seed=43)
    assert res["correct"] is False, res["checks"]
