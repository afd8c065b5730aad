"""Mean duration of the front end's ``serve/queue_wait`` spans of the
admitted requests (from the queue-bound check to an inflight slot), in
milliseconds.  Requests shed from the queue are left out."""


def read(window):
    durs = [s["dur"] for s in window.spans_named("serve/queue_wait")
            if s["args"].get("outcome") == "admitted"]
    return sum(durs) / len(durs) / 1e3 if durs else None
