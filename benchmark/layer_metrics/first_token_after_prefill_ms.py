"""Mean, over the prefills wholly inside the capture, of the time from the end
of the prefill's module event on device 0 to the start of its request's
``engine.first_token`` span: what the first token waits for after its prefill
has run (ROADMAP S1). Span and module event are paired by span_reduce.py."""

from benchmark.layer_metrics import _spans


def read(ctx):
    red = _spans.reduction(ctx)
    if red is None:
        return None
    waits = [p["first_token_start"] - p["module_start"] - p["module_s"]
             for p in red.get("prefills", [])
             if p.get("first_token_start") is not None]
    waits = [w for w in waits if w >= 0.0]
    return sum(waits) / len(waits) * 1e3 if waits else None
