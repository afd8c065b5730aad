"""Mean duration of the ``store/spill`` spans, one per segment written
to disk (one ``.npy`` per column and the segment's metadata), in
milliseconds."""


def read(window):
    durs = [s["dur"] for s in window.spans_named("store/spill")]
    return sum(durs) / len(durs) / 1e3 if durs else None
