"""Share of the prompt tokens admitted in the window that the prefix trie
SERVED (blocks a request was handed and did not prefill): the window's
``prefix_hit_tokens`` over its ``prompt_tokens`` (the driver's counters, the
engine's own).  A self-drafting engine's hit ends one block early (the
module's entry at a shared prefix's last position is made from the request's
own next token), so a prefix of 32 blocks reads (32 - 1) x 256 tokens a
request.  ``None`` where the driver has no such counters or nothing was
admitted."""

SPECS = [{"name": "prefix_hit_share", "unit": "%", "better": "higher",
          "source": "program_counter", "layer": "KV manager",
          "moves": "serve_tok_s"}]


def read(ctx):
    counters = ctx.get("counters") or {}
    asked = counters.get("prompt_tokens")
    if not asked or "prefix_hit_tokens" not in counters:
        return None
    return 100.0 * counters["prefix_hit_tokens"] / asked
