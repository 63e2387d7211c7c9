"""How full the blocks a decode step's rows hold are: ``kv_valid`` over
``kv_blocks x block tokens x layers``, both summed over the ``decode``
spans of the program's ring that start inside the window (the scheduler of
a model with latent attention puts them there from its rows' lengths,
``ServingEngine._kv_reach``; the block size is the engine's, in the
driver's counters).  What larger blocks cost in held-but-empty tokens: a
row holds on average half a block it has not filled, 256 of ~8,200 tokens
at 512 a block.  The other side of the choice of block size, whose gain is
``latent_attn_ms``.  A ring without the counters gives ``None``."""
from chipbench import costs
from chipbench.layer_metrics import latent_attn_ms

SPECS = [{"name": "kv_block_fill", "unit": "%", "better": "higher",
          "source": "program_span", "layer": "KV manager",
          "moves": "serve_tok_s"}]


def read(ctx):
    # a ratio of sums over the same spans is the ratio of their means
    means = latent_attn_ms.span_means(
        ctx, "decode", ("kv_valid", "kv_blocks", "latent_bytes"))
    block = ctx["counters"].get("block_size")
    if not means or not means["kv_blocks"] or not block \
            or "family" not in ctx["config"]:
        return None
    return 100.0 * means["kv_valid"] \
        / (means["kv_blocks"] * block * costs.arch(ctx["config"])["layers"])
