"""Mean duration of ``QueryEngine.execute``'s ``query/plan`` spans (the
planner's segment classification), in milliseconds."""


def read(window):
    durs = [s["dur"] for s in window.spans_named("query/plan")]
    return sum(durs) / len(durs) / 1e3 if durs else None
