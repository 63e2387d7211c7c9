"""``paged_decode_attn`` alone, on the chip: us a row in calls of 24 rows.

    python3 benchmarks/paged_walk_alone.py <checkout> <tag>

``<checkout>``: the tree whose ``deepspeed_tpu`` is timed (``.`` or a
``git archive`` of the parent unpacked beside it: run both in ONE chiprun
call, one process after the other); ``<tag>`` is printed on every line.

Shapes: OPT-1.3B (32 KV heads x 64), OLMoE (16 x 128), Command A+ (128 query
/ 8 KV heads x 128), bf16 pools of 32-token blocks, K and V the same array (HBM
has no cache to share them through).  Rows of 1 / 6 / 24 / 56 blocks, a chat-
like mix of 2-12, live rows of 6 between idle ones, and a call of idle
rows alone (what a call costs before any block: subtract it).  A program is 24
calls of the kernel (a layer each, as a decode step makes them); its time is
the least of 30 runs, divided by calls and rows.  PERF.md section 6 (PR 45,
PR 56) has the tables this printed.
"""

import os
import statistics
import sys
import time

ROWS, BLOCK, LAYERS, NBPER, CALLS, RUNS = 24, 32, 4, 64, 24, 30
#: (name, query heads, KV heads, head dim)
FAMILIES = [("opt", 32, 32, 64), ("olmoe", 16, 16, 128),
            ("commanda", 128, 8, 128)]
#: (label, blocks a row; 0: an idle row — 24 of them are the call's own
#: cost: the launch, 24 empty grid steps, the program's add)
ROWSETS = [("idle", [0] * ROWS), ("1", [1] * ROWS), ("6", [6] * ROWS),
           ("24", [24] * ROWS), ("56", [56] * ROWS),
           ("chat-mix", [2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 6] * 2),
           ("6+idle", [6, 0] * 12)]
HBM_BYTES_S = 819e9                                  # one v5e chip


def bench(tag, name, h, hkv, hd):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops import decode_attention as da, paged_kv

    g = paged_kv.lane_pack(BLOCK, hd)
    pool = jnp.full((LAYERS, 1 + ROWS * NBPER, hkv, BLOCK // g, g * hd),
                    0.01, jnp.bfloat16)
    q = jnp.ones((ROWS, h, 1, hd), jnp.bfloat16)
    bt = jnp.asarray(1 + np.arange(ROWS * NBPER).reshape(ROWS, NBPER),
                     jnp.int32)

    @jax.jit
    def program(q, pool, bt, pos):
        def call(layer, acc):
            return acc + da.paged_decode_attention_pallas(
                q, pool, pool, bt, pos, layer=layer % LAYERS,
                interpret=False)
        return jax.lax.fori_loop(0, CALLS, call, jnp.zeros_like(q))

    for label, blocks in ROWSETS:
        pos = jnp.asarray(np.asarray(blocks) * BLOCK - 1, jnp.int32)
        program(q, pool, bt, pos).block_until_ready()
        seconds = []
        for _ in range(RUNS):
            t0 = time.perf_counter()
            program(q, pool, bt, pos).block_until_ready()
            seconds.append(time.perf_counter() - t0)
        a_row = 1e6 / CALLS / ROWS
        floor = sum(blocks) * 2 * hkv * BLOCK * hd * 2 / HBM_BYTES_S
        print(f"ALONE {tag} {name} rows={label}: min "
              f"{min(seconds) * a_row:.3f} median "
              f"{statistics.median(seconds) * a_row:.3f} us a row of {ROWS} "
              f"({sum(1 for n in blocks if n)} live); a call "
              f"{min(seconds) / CALLS * 1e6:.1f} us, its bytes' floor "
              f"{floor * 1e6:.1f} us", flush=True)


def main(argv):
    if len(argv) != 3:
        print(__doc__, file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.abspath(argv[1]))
    import jax

    if jax.devices()[0].platform != "tpu":
        print(f"no TPU here ({jax.devices()}): a CPU's time is no device "
              "number", file=sys.stderr)
        return 2
    for family in FAMILIES:
        bench(argv[2], *family)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
