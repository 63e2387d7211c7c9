"""Seconds of a start inside the ``compile`` events of the engine's
registered programs: the persistent cache's retrieval and the executable's
deserialisation when the cache is warm, XLA's and Mosaic's own work when it
is cold (``_setup_spans.py``).  Also prints what the cache answered for
them."""
from chipbench.layer_metrics import _setup_spans as ss

SPECS = [{"name": "setup_compile_s", "unit": "s", "better": "lower",
          "source": "program_span", "layer": "model step",
          "moves": "setup_s"}]


def read(ctx):
    got = ss.account(ctx)
    if got is None:
        return None
    cache = got["cache"]
    print("chipbench: compile cache over the registered programs: "
          f"{cache['hits']} hits, {cache['misses']} misses, {cache['off']} "
          f"uncached; retrieval_s {cache['retrieval_s']:.3f}"
          + (f"; missed: {', '.join(cache['missed'])}"
             if cache["missed"] else ""), flush=True)
    return ss.row(ctx, "compile_s", got)
