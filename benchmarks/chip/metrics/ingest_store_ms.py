"""Mean duration of the pipeline's ``ingest/store`` spans, one per batch
(append, and the seal and spill when a segment fills), in
milliseconds."""


def read(window):
    durs = [s["dur"] for s in window.spans_named("ingest/store")]
    return sum(durs) / len(durs) / 1e3 if durs else None
