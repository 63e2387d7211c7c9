"""``paged_latent_attn`` / ``paged_latent_prefill`` alone, on the chip: us a
row and us a block visit, decode calls of 64 rows and ``[1, 512]`` chunks.

    python3 benchmarks/latent_walk_alone.py <checkout> <tag> [nt]

``<checkout>``: the tree whose ``deepspeed_tpu`` is timed (``.`` or a
``git archive`` of the parent unpacked beside it: run both in ONE chiprun
call, one process after the other); ``<tag>`` is printed on every line.
``nt`` (a tree that has ``latent_tile_blocks`` only): time the walk at THAT
tile instead of the one the rule gives — what the rule's budget was chosen
from, never a setting of the program.

Shapes: Mistral Small 4 (512-token blocks of 384 lanes, rank 256) and Kimi
Linear (256-token blocks of 640 lanes, rank 512), 32 heads, bf16 pools whose
tables hold 32 blocks a row.  Decode: rows of 1 / 4 / 16 / 24 blocks, live
rows of 16 between idle ones, and a call of idle rows alone (what a call
costs before any block: subtract it).  Chunk: 512 queries of one row whose
last sees 2 / 4 / 16 / 24 blocks' keys (32 grid steps of 16 positions, each
walking the blocks up to its own last query).  A program is 6 calls of the
kernel (a layer each, as the long-decode cell's step makes them); its time
is the least of 30 runs, divided by calls and rows.  PERF.md section 6
(PR 58) has the tables this printed.
"""

import os
import statistics
import sys
import time

ROWS, HEADS, LAYERS, NBPER, CALLS, RUNS, CHUNK = 64, 32, 2, 32, 6, 30, 512
#: (name, tokens a block, lanes a token, rank)
FAMILIES = [("mistral4", 512, 384, 256), ("kimi", 256, 640, 512)]
#: (label, blocks a decode row; 0: an idle row)
ROWSETS = [("idle", [0] * ROWS), ("1", [1] * ROWS), ("4", [4] * ROWS),
           ("16", [16] * ROWS), ("24", [24] * ROWS),
           ("16+idle", [16, 0] * (ROWS // 2))]
CHUNK_BLOCKS = [2, 4, 16, 24]
HBM_BYTES_S = 819e9                                  # one v5e chip


def timed(program, *args):
    program(*args).block_until_ready()
    seconds = []
    for _ in range(RUNS):
        t0 = time.perf_counter()
        program(*args).block_until_ready()
        seconds.append(time.perf_counter() - t0)
    return min(seconds), statistics.median(seconds)


def bench(tag, name, bs, width, rank, interpret=False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops import decode_attention as da

    pool = jnp.full((LAYERS, 1 + ROWS * NBPER, 1, bs, width), 0.01,
                    jnp.bfloat16)
    bt = jnp.asarray(1 + np.arange(ROWS * NBPER).reshape(ROWS, NBPER),
                     jnp.int32)

    @jax.jit
    def program(q, pool, bt, pos):
        def call(layer, acc):
            return acc + da.paged_latent_attention_pallas(
                q, pool, bt, pos, rank=rank, layer=layer % LAYERS,
                interpret=interpret)
        return jax.lax.fori_loop(
            0, CALLS, call, jnp.zeros(q.shape[:3] + (rank,), q.dtype))

    def tile(t):
        """Blocks a loop iteration of the walk over ``t`` positions (a tree
        that walks one block a visit has no rule to ask)."""
        shape = getattr(da, "latent_walk_shape", None)
        return shape(HEADS, t, bs, width, 2, NBPER)[1] if shape else 1

    a_block = bs * width * 2
    q = jnp.ones((ROWS, HEADS, 1, width), jnp.bfloat16)
    nt = tile(1)
    idle = None
    for label, blocks in ROWSETS:
        pos = jnp.asarray(np.asarray(blocks) * bs - 1, jnp.int32)
        least, median = timed(program, q, pool, bt, pos)
        a_call = least / CALLS * 1e6
        idle = a_call if idle is None else idle
        visits = sum(blocks)
        print(f"ALONE {tag} {name} decode nt={nt} rows={label}: min "
              f"{a_call / ROWS:.3f} median "
              f"{median / CALLS * 1e6 / ROWS:.3f} us a row of {ROWS} "
              f"({sum(1 for n in blocks if n)} live); a call {a_call:.1f} "
              f"us, less the idle call "
              f"{(a_call - idle) / max(visits, 1):.3f} us a block, its "
              f"bytes' floor {a_block / HBM_BYTES_S * 1e6:.3f}", flush=True)
    q = jnp.ones((1, HEADS, CHUNK, width), jnp.bfloat16)
    nt = tile(CHUNK)
    for blocks in CHUNK_BLOCKS:
        pos = jnp.asarray([blocks * bs - CHUNK], jnp.int32)
        try:
            least, median = timed(program, q, pool, bt[:1], pos)
        except Exception as e:     # a forced tile the compiler refuses
            print(f"ALONE {tag} {name} chunk[1,{CHUNK}] nt={nt}: refused "
                  f"({str(e).splitlines()[0][:120]})", flush=True)
            break
        # a grid step of 16 positions walks the blocks up to its own last
        visits = sum(-(-(blocks * bs - CHUNK + at + 16) // bs)
                     for at in range(0, CHUNK, 16))
        print(f"ALONE {tag} {name} chunk[1,{CHUNK}] nt={nt} "
              f"blocks={blocks}: min {least / CALLS * 1e6:.1f} median "
              f"{median / CALLS * 1e6:.1f} us a call; {visits} visits, "
              f"{least / CALLS * 1e6 / visits:.3f} us a block", flush=True)


def main(argv):
    if len(argv) not in (3, 4):
        print(__doc__, file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.abspath(argv[1]))
    import jax

    if jax.devices()[0].platform != "tpu":
        print(f"no TPU here ({jax.devices()}): a CPU's time is no device "
              "number", file=sys.stderr)
        return 2
    if len(argv) == 4:
        from deepspeed_tpu.ops import decode_attention as da

        nt = int(argv[3])
        assert hasattr(da, "latent_tile_blocks"), \
            f"{argv[1]} walks one block a visit: no tile to set"
        da.latent_tile_blocks = lambda *shapes: min(nt, shapes[-1])
    for family in FAMILIES:
        bench(argv[2], *family)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
