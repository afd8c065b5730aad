"""Mean duration of the front end's ``serve/request`` spans (the engine
call and the framing of an admitted request), in milliseconds."""


def read(window):
    durs = [s["dur"] for s in window.spans_named("serve/request")]
    return sum(durs) / len(durs) / 1e3 if durs else None
