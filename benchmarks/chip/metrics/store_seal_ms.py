"""Mean duration of the store's ``store/seal`` spans, one per sealed
segment (concatenating the active batches, the enrichment metadata and
postings, the spill and the manifest commit), in milliseconds."""


def read(window):
    durs = [s["dur"] for s in window.spans_named("store/seal")]
    return sum(durs) / len(durs) / 1e3 if durs else None
