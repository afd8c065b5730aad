"""Mean duration of the pipeline's ``ingest/wal_truncate`` spans, one per
stored batch (listing the journal and deleting the entries below the
sealed watermark), in milliseconds."""


def read(window):
    durs = [s["dur"] for s in window.spans_named("ingest/wal_truncate")]
    return sum(durs) / len(durs) / 1e3 if durs else None
