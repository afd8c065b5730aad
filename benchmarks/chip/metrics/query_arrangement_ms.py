"""Mean duration of the executor's ``query/arrangement`` spans (leasing
or building the device arrangement, with any upload, and the device word
masks), in milliseconds."""


def read(window):
    durs = [s["dur"] for s in window.spans_named("query/arrangement")]
    return sum(durs) / len(durs) / 1e3 if durs else None
