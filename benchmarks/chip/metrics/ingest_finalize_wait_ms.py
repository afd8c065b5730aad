"""Mean duration of the pipeline's ``ingest/finalize_wait`` spans, one per
batch (the wait for the device's bitmap and its copy to the host), in
milliseconds."""


def read(window):
    durs = [s["dur"] for s in window.spans_named("ingest/finalize_wait")]
    return sum(durs) / len(durs) / 1e3 if durs else None
