"""Share of the memory roofline that the served path's stacked word
query (``jit__word_query_dispatch``) reaches, in percent: the bytes its
calls in the window must move, from their shapes, over the HBM
bandwidth, divided by the device time of those calls.

Each ``query/stacked_dispatch`` span gives its segment count; its parent
``query/execute`` span names the query, whose predicate count and mode
the mix defines.  Rows are the segments' records padded as the executor
pads them."""
from chipbench import roofline

MODULE = "jit__word_query_dispatch"


def read(window):
    device_s = window.device.module_s(MODULE) if window.device else 0.0
    if device_s <= 0:
        return None
    sh = window.shapes
    execs = {s["args"]["id"]: s for s in window.spans
             if s["name"] == "query/execute"}
    nbytes = 0
    for s in window.spans_named("query/stacked_dispatch"):
        parent = execs.get(s["args"].get("parent"))
        if parent is None or sh["segment_records"] is None:
            return None
        segs = s["args"]["segments"]
        nbytes += roofline.word_query_bytes(
            rows=roofline.bucket(segs * sh["segment_records"], sh["block"]),
            preds=sh["preds"][parent["args"]["query"]],
            with_counts=parent["args"]["mode"] == "count",
            segments=1 << (segs - 1).bit_length())
    peak = roofline.peaks(window.run.device_kind)
    return roofline.share_pct(nbytes, device_s, peak)
