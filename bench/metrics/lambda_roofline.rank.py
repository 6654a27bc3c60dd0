"""lambda_roofline.rank: the least time of the lambda passes in the window
(``rank_work.lambda_pass`` of each ``ranking/lambda`` span's ``rows`` and
``pairs``) over the device time inside those spans, in percent. Under the
span tracer the pass waits for the device inside its span, and nothing
else runs on the chip then, so the device time inside the spans is the
pass's. Nothing when no chip was traced or the program has no span."""
import devtrace
import rank_work
import work


def read(r):
    spans = r.spans_named("ranking/lambda")
    if not r.ops or not spans:
        return None
    w, device_ns = work.NOTHING, 0.0
    for name, t0, t1, args in spans:
        w = w + rank_work.lambda_pass(args["rows"], args["pairs"])
        a, b = t0 * 1e9 + r.offset_ns, t1 * 1e9 + r.offset_ns
        device_ns += sum(devtrace.busy_ns(ev, a, b)
                         for ev in r.ops.values()) / len(r.ops)
    return work.share_pct(w, device_ns / 1e9, r.peaks)
