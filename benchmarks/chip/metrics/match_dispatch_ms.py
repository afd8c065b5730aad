"""Mean duration of the matcher's ``match/dispatch`` spans, one per batch
(the host's share of launching the scan), in milliseconds."""


def read(window):
    durs = [s["dur"] for s in window.spans_named("match/dispatch")]
    return sum(durs) / len(durs) / 1e3 if durs else None
