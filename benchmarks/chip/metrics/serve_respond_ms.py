"""Mean duration of the front end's ``serve/respond`` spans (encoding a
response and sending its frame), in milliseconds."""


def read(window):
    durs = [s["dur"] for s in window.spans_named("serve/respond")]
    return sum(durs) / len(durs) / 1e3 if durs else None
