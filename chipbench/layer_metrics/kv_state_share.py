"""What share of the cache a serving engine holds IN USE is recurrent
state: the state kind's bytes (every slot's float32 matrices and
convolution tails, all KDA layers: ``stats()["kv_state"]["bytes"]``, whatever
the rows' lengths) over those plus the paged pool's blocks in use at the
pool's peak in the window (``max(blocks_in_use)`` x a block's bytes over
every layer that has blocks).  A model whose cache is all blocks (every
other family: the driver's counters carry no ``state_bytes``) gives
``None``."""

SPECS = [{"name": "kv_state_share", "unit": "%", "better": "higher",
          "source": "program_counter", "layer": "KV manager",
          "moves": "serve_tok_s"}]


def read(ctx):
    state = ctx["counters"].get("state_bytes")
    block = ctx["counters"].get("block_bytes_all_layers")
    used = ctx["samples"].get("blocks_in_use")
    if not state or not block or not used:
        return None
    return 100.0 * state / (state + max(used) * block)
