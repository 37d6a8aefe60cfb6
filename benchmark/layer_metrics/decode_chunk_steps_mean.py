"""Mean length in steps of the decode chunks whose token block the capture saw
fetched: the ``k`` argument over the ``engine.fetch_chunk`` events that
span_reduce.py keeps. The engine takes chunks of 4 while a slot is free and of
``decode_chunk`` once every slot is seated (``serving/engine.py``
``_chunk_size``), so this says how often the short chunk engaged; a program
that always runs whole chunks reads its ``decode_chunk``."""

from benchmark.layer_metrics import _spans


def read(ctx):
    red = _spans.reduction(ctx)
    if red is None:
        return None
    try:
        ks = [float(st["k"]) for _s, _d, st
              in red["events"]["engine.fetch_chunk"]]
    except (KeyError, TypeError, ValueError):
        return None
    return sum(ks) / len(ks) if ks else None
