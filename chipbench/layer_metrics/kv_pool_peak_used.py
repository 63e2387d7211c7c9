"""Largest share of the paged KV pool in use after any step of the
window (``blocks_in_use / num_blocks``)."""

SPECS = [{"name": "kv_pool_peak_used", "unit": "%", "better": "higher",
          "source": "program_counter", "layer": "KV manager",
          "moves": "serve_tok_s"}]


def read(ctx):
    used = ctx["samples"].get("blocks_in_use")
    if not used:
        return None
    return 100.0 * max(used) / ctx["counters"]["num_blocks"]
