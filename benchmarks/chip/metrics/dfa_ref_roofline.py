"""Share of the memory roofline that the ingest match scan
(``jit__dispatch_fused`` on the ``dfa_ref`` lane) reaches, in percent:
the bytes its calls in the window must move (text in, bitmaps out, the
DFA tables once per call), from their shapes, over the HBM bandwidth,
divided by the device time of those calls.  The scan does integer
gathers, which have no peak rate in the table, so the memory bound is
the one that holds."""
from chipbench import roofline

MODULE = "jit__dispatch_fused"


def read(window):
    device_s = window.device.module_s(MODULE) if window.device else 0.0
    if device_s <= 0:
        return None
    sh = window.shapes
    nbytes = sum(
        roofline.dfa_ref_bytes(
            fields=s["args"]["fields"],
            rows=roofline.bucket(s["args"]["n"], sh["block"]),
            width=sh["width"], words=sh["words"], engines=sh["engines"],
            states=sh["states"], classes=sh["classes"])
        for s in window.spans_named("match/dispatch"))
    peak = roofline.peaks(window.run.device_kind)
    return roofline.share_pct(nbytes, device_s, peak)
