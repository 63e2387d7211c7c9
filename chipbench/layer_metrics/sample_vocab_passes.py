"""How many times a decode step walks the logits: the bytes ONE call of the
decode-side programs (``decode``, or ``verify`` + ``draft`` of a
self-drafting engine) moves under the sampler's scopes (``sample`` and
``sample/*``: HBM, arrays the compiler keeps on the chip, and the operands of
the ``nucleus_search`` / ``kth_search`` kernels, which ARE the logits), over
one float32 ``[slots, vocab]`` array.  A loop's passes count as often as it
runs.  The note says how many of them go through HBM: where few do, the
sampler's milliseconds are not the HBM's to give back.  Counters of the
compiled program (``_scope_tables.py``): no profiler."""
from chipbench.layer_metrics import _scope_tables as st

SPECS = [{"name": "sample_vocab_passes", "unit": "passes", "better": "lower",
          "source": "program_counter", "layer": "model step",
          "moves": "serve_tok_s"}]


def read(ctx):
    slots = ctx.get("counters", {}).get("slots")
    vocab = ctx.get("config", {}).get("vocab_size")
    found = st.tables(ctx, st.DECODE_SIDE) if slots and vocab else None
    if found is None:
        return None
    mine = [r for r in st.rows(found)
            if r["scope"] == "sample" or r["scope"].startswith("sample/")]
    one = 4.0 * slots * vocab
    hbm = sum(r["bytes"] for r in mine)
    moved = hbm + sum(r["onchip_bytes"] + r["kernel_bytes"] for r in mine)
    if not moved:
        return None
    by = {}
    for r in mine:
        by[r["scope"]] = by.get(r["scope"], 0.0) + (
            r["bytes"] + r["onchip_bytes"] + r["kernel_bytes"]) / one
    print(f"chipbench: the sampler walks a [{slots}, {vocab}] float32 "
          f"array ({one / 1e6:.1f} MB) {moved / one:.1f} times a decode "
          f"call, {hbm / one:.1f} of them through HBM; by scope: "
          + ", ".join(f"{k} {v:.1f}" for k, v in sorted(by.items())),
          flush=True)
    return moved / one
