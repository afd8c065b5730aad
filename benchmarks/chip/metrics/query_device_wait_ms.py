"""Mean duration of the executor's ``query/device_wait`` spans (the host
blocking on the stacked dispatch's result and its copy to the host), in
milliseconds."""


def read(window):
    durs = [s["dur"] for s in window.spans_named("query/device_wait")]
    return sum(durs) / len(durs) / 1e3 if durs else None
