"""Mean duration of the engine's ``query/materialize`` spans (reading the
matched rows of an ids or copy query), in milliseconds, over the
requests that have one."""


def read(window):
    durs = [s["dur"] for s in window.spans_named("query/materialize")]
    return sum(durs) / len(durs) / 1e3 if durs else None
