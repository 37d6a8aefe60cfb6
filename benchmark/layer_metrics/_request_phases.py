"""What the readers of a request's time by phase share. The engine observes
each wait of a request's trace chain (kukeon_tpu/obs/trace.py) into a
histogram at the instant the wait ends: ``kukeon_engine_queue_wait_seconds``
when it leaves the queue, ``kukeon_engine_ttft_seconds`` when its first token
is emitted, ``kukeon_engine_inter_token_seconds`` at every later token. A
histogram's ``_sum`` over its ``_count`` between the window's two scrapes is
the exact mean of what it observed there, where a quantile is interpolated
inside a power-of-two bucket; an answer still running at the close scrape has
its tokens so far counted, so long answers are not censored. ``_spans.py``'s
contract holds: None, never an exception, over scrapes without the family or a
window in which it observed nothing."""

from __future__ import annotations

from benchmark.layer_metrics import _spans

QUEUE_WAIT = "kukeon_engine_queue_wait_seconds"
TTFT = "kukeon_engine_ttft_seconds"
TOKEN_GAP = "kukeon_engine_inter_token_seconds"
E2E = "kukeon_engine_e2e_seconds"


def mean_ms(ctx: dict, family: str) -> float | None:
    """Mean milliseconds of what the histogram observed in the window."""
    count = _spans.window_delta(ctx, family + "_count")
    seconds = _spans.window_delta(ctx, family + "_sum")
    if count <= 0 or seconds <= 0:
        return None
    return seconds * 1e3 / count


def show_sum(ctx: dict) -> None:
    """One line: the three phase means, with the token gaps of an answer the
    client asked for in the window, beside the engine's own mean submit ->
    ended and the client's mean latency. What the sum lacks of the client's is
    the cell's HTTP side and the load generator's lateness, and the difference
    between the requests each histogram observed in the window and those the
    client sent in it."""
    queued, first = mean_ms(ctx, QUEUE_WAIT), mean_ms(ctx, TTFT)
    gap, e2e = mean_ms(ctx, TOKEN_GAP), mean_ms(ctx, E2E)
    if None in (queued, first, gap, e2e):
        return
    answers = [len(r["token_times"]) - 1 for r in ctx.get("records", ())
               if r.get("in_window") and r.get("ok") and r.get("token_times")]
    if answers:
        gaps = sum(answers) / len(answers)
    else:       # no client beside the scrapes: what the engine emitted
        gaps = (_spans.window_delta(ctx, TOKEN_GAP + "_count")
                / _spans.window_delta(ctx, TTFT + "_count"))
    total = first + gap * gaps
    line = (f"request phases: queued {queued:.1f} + prefill "
            f"{first - queued:.1f} + decode {gap:.3f} x {gaps:.2f} token gaps"
            f" an answer = {total:.1f} ms; the engine's submit -> ended "
            f"{e2e:.1f} ms")
    client = ctx.get("client", {}).get("latency_mean_ms")
    if client:
        line += (f"; the client's latency_mean_ms {client:.1f} "
                 f"({total - client:+.1f} ms, {100 * (total / client - 1):+.2f}%)")
    print(line, flush=True)
