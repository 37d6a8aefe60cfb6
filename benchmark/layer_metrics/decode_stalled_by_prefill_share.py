"""Of the slot-time requests spent decoding inside the capture, the share in
which the device ran another request's prompt: S / (S + D), where S sums each
paired prefill's module seconds x the ``decoding`` slots its dispatch span
counted (their next chunk ran behind it) and D sums each fetched chunk's
``decode_chunk`` module seconds x the ``active`` slots its ``engine.fetch_chunk``
span counted. A fetch returns once its chunk has run, and the chunk after it
is still running then, so a span's module is the last decode module that ended
by the span's end; the first spans of a capture, whose modules ran before it,
pair with none. None where a span lacks its argument (a program before them)
and, by ``_spans.py``'s contract, where no prefill lies whole inside the
capture: one of 3 s holds a prefill or none where prefills are seconds long and
seconds apart, and a share of that is a coin, so BENCHMARK.json lists the cells
whose captures hold several."""

from benchmark.layer_metrics import _common, _spans

CLOCKS_S = 0.0005       # a module may end this long after the fetch it fed


def read(ctx):
    red = _spans.reduction(ctx)
    if red is None or "window" not in red:
        return None
    try:
        if not red["prefills"]:
            return None
        stalled = sum(p["module_s"] * p["decoding"] for p in red["prefills"])
        lo = red["window"][0]
        ends = sorted((lo + s + d, d) for s, d, _program
                      in _common.modules(ctx, "decode_chunk")["events"])
        decoding, j = 0.0, 0
        for start, dur, st in sorted(red["events"]["engine.fetch_chunk"],
                                     key=lambda e: e[0]):
            active, mine = st["active"], None
            while j < len(ends) and ends[j][0] <= start + dur + CLOCKS_S:
                mine = ends[j][1]
                j += 1
            if mine is not None:
                decoding += mine * active
    except (KeyError, TypeError, IndexError, ValueError):
        return None
    if stalled + decoding <= 0:
        return None
    return 100.0 * stalled / (stalled + decoding)
