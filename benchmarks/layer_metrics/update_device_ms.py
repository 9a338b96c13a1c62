"""Device time a step outside the model and not the exchange's: the step's own
scopes (`optimizer`, guard, statistics) and whatever else carries a name
stack without autodiff in it. It reads the same whether or not the compiled
text names the `optimizer` scope. Traced epoch, mean over the chips."""

import scope_spans


def read(run: dict):
    return scope_spans.sum_ms(run, "update")
