"""Mean duration of ``QueryEngine.execute``'s ``query/execute`` spans, in
milliseconds."""


def read(window):
    durs = [s["dur"] for s in window.spans_named("query/execute")]
    return sum(durs) / len(durs) / 1e3 if durs else None
