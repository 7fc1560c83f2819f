"""`decode.fetch_tail_ms.capacity`: the logits' way to the host, ms: the
end of the `model.fetch` host span less the end of the decode program
(``jit_step``) that ends inside it on the device plane, averaged over
the traced decode dispatches.  Both ends come from one xplane, whose
device plane `host_spans.device_view` first shifts by the least that
restores causality; the earlier lines say by how much, whether every
device program then starts between its host dispatch span's start and
the next one's and ends inside the fetch the host waits in, and the
lag from a prefill dispatch's host span to its program's start
(lag + tail is measured to a fraction of a millisecond; their split
only to the planes' clock skew, about one) (device_trace)."""
from benchmarks import host_spans as hs

DECODE, PREFILL = "jit_step", "jit_prefill"


def read(ctx, metric):
    view = hs.device_view(ctx)
    if view is None:
        return None
    spans, _, modules, _ = view
    decodes = modules.get(DECODE, [])
    tails = hs.fetch_tails(spans, decodes)
    inside = [tail for tail, ok in tails if ok]
    d_n, d_late = hs.starts_in_order(spans, "sched.decode.dispatch", decodes)
    p_n, p_late = hs.starts_in_order(spans, "sched.prefill.dispatch",
                                     modules.get(PREFILL, []))
    ctx.out(f"shared clock: {len(inside)} of {len(tails)} {DECODE} programs "
            f"end inside a model.fetch span; {d_late} of {d_n} {DECODE} and "
            f"{p_late} of {p_n} {PREFILL} programs start outside the time "
            "from their host dispatch span's start to the next one's")
    lags = hs.nearest_lags(spans, "sched.prefill.dispatch",
                           modules.get(PREFILL, []))
    if lags and inside:
        ctx.out(f"a {PREFILL} program starts {1e3 * min(lags):.3f} to "
                f"{1e3 * max(lags):.3f} ms after its host span; a {DECODE} "
                f"program ends {1e3 * min(inside):.3f} to "
                f"{1e3 * max(inside):.3f} ms before its model.fetch span; "
                f"mean lag + mean tail "
                f"{1e3 * (sum(lags) / len(lags) + sum(inside) / len(inside)):.3f}"
                " ms")
    if not inside:
        return None
    return 1e3 * sum(inside) / len(inside)
