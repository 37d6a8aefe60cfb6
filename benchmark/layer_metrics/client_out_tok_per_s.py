"""`out_tok_per_s` at the client over the traced run's window, in a cell where
it is not an end-to-end metric: there its runs spread by more than half of the
largest bound the contract allows (PERF.md section 2), so it stands here, without
a bound, under a name of its own."""


def read(ctx):
    return ctx["client"]["out_tok_per_s"]
