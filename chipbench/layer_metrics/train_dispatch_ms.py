"""Host time from calling ``engine.train_batch()`` to its return (the
enqueue, not the step): the benchmark's own span, median over the window."""
import statistics

SPECS = [{"name": "train_dispatch_ms", "unit": "ms", "better": "lower",
          "source": "program_span", "layer": "engine",
          "moves": "train_tok_s"}]


def read(ctx):
    calls = ctx["spans"].within("cb.train_batch", *ctx["window"])
    return statistics.median(calls) * 1e3 if calls else None
