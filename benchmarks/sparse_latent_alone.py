"""The kernels ``dots3-longnote-closed`` adds, alone, on the chip, at the
cell's shapes: each against its XLA form (results) and against its roofline
(time).

    python3 benchmarks/sparse_latent_alone.py [context]

Shapes: 24 decode rows and a ``[1, 512]`` chunk at ``context`` keys a row
(default 20,480, the cell's mean prompt), bf16.  Full layers: 128 heads over
``[256, 640]`` latent blocks (rank 512) beside a 128-wide index key, 64 index
heads, 2,048 keys chosen; sliding layers: 64 heads over ``[128, 1152]`` ring
blocks (rank 1,024) under a 513-key window.  Pools hold seeded N(0, 1)
values, queries N(0, 0.1): a random indexer's choices lie in every block.

Printed, a line a kernel: the largest difference from the XLA form on the
same operands (``ops/sparse_index_attention.py`` / ``decode_attention.py``
references; bf16 operands, so ~1e-2), us a call (the least of ``RUNS``
runs of a program of ``CALLS`` calls), and the share of the roofline its
NEEDED work gives (``chipbench/families/dots3.py``'s byte and FLOP
functions: chosen keys' latents, valid index keys, visible keys) — and, for
the selected read of a decode step, the TOKEN-granular alternative beside
the block-granular kernel: an XLA gather of the 2,048 chosen rows of 1,280 B
a row followed by dense absorbed attention over them.  Exit 2 without a TPU:
every kernel is asked for compiled.
"""

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

ROWS, CHUNK, CALLS, RUNS = 24, 512, 4, 10
HBM_BYTES_S, BF16_FLOPS = 819e9, 197e12
TOPK, WINDOW = 2048, 513
FULL = dict(heads=128, bs=256, width=640, rank=512, keys=576)
INDEX = dict(heads=64, width=128)
SLIDING = dict(heads=64, bs=128, width=1152, rank=1024, keys=1088)


def timed(program, *args):
    import jax

    jax.block_until_ready(program(*args))
    seconds = []
    for _ in range(RUNS):
        t0 = time.perf_counter()
        jax.block_until_ready(program(*args))
        seconds.append(time.perf_counter() - t0)
    return min(seconds) / CALLS, statistics.median(seconds) / CALLS


def report(name, seconds, nbytes, flops, err=None):
    floor = max(nbytes / HBM_BYTES_S, flops / BF16_FLOPS)
    bound = "bytes" if nbytes / HBM_BYTES_S >= flops / BF16_FLOPS else "FLOPs"
    print(f"ALONE {name}: min {seconds[0] * 1e6:.1f} median "
          f"{seconds[1] * 1e6:.1f} us a call; needs {nbytes / 1e6:.2f} MB, "
          f"{flops / 1e9:.2f} GFLOP: {100 * floor / seconds[0]:.1f} % of the "
          f"roofline ({bound})"
          + ("" if err is None else f"; largest difference from the XLA "
             f"form {err:.4f}"), flush=True)


def repeat(fn):
    """A program of ``CALLS`` calls of ``fn(layer)`` whose results are
    summed (so that none is dropped)."""
    import jax

    def program(*args):
        def call(i, acc):
            return jax.tree_util.tree_map(lambda a, b: a + b.astype(a.dtype),
                                          acc, fn(i % 2, *args))
        out = fn(0, *args)
        return jax.lax.fori_loop(1, CALLS, call, out)
    return jax.jit(program)


def run(ctx: int, max_seq_len: int = 32768, interpret: bool = False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops import decode_attention as da
    from deepspeed_tpu.ops import sparse_index_attention as sia

    key = jax.random.PRNGKey(61)
    f, ix, w = FULL, INDEX, SLIDING
    nbper = max_seq_len // f["bs"]
    nb = 1 + ROWS * nbper

    def normal(i, shape, scale=1.0, dtype=jnp.bfloat16):
        return (jax.random.normal(jax.random.fold_in(key, i), shape)
                * scale).astype(dtype)

    pool = normal(1, (2, nb, 1, f["bs"], f["width"]))
    pool = pool.at[..., f["keys"]:].set(0)
    idx = normal(2, (2, nb, 1, f["bs"], ix["width"]))
    bt = jnp.asarray(1 + np.arange(ROWS * nbper).reshape(ROWS, nbper),
                     jnp.int32)
    # what each kernel NEEDS a key: the benchmark's own functions, at the
    # published configuration
    from chipbench.families import dots3 as family

    with open(os.path.join(ROOT, "chipbench", "configs",
                           "dots3-note-prev.json")) as fh:
        config = json.load(fh)
    per_key = {"latent": family.latent_bytes_per_key(config),
               "index": family.index_bytes_per_key(config),
               "window": family.latent_bytes_per_key(config, "sliding")}
    flops_key = {"latent": family.latent_flops_per_key(config),
                 "index": family.index_flops_per_key(config),
                 "window": family.latent_flops_per_key(config, "sliding")}

    for label, rows, t in (("decode", ROWS, 1), ("chunk[1,512]", 1, CHUNK)):
        pos = jnp.full((rows,), ctx - t, jnp.int32)
        last = sia.last_visible(pos, t, rows)
        q = normal(3, (rows, f["heads"], t, f["width"]), 0.1)
        q = q.at[..., f["keys"]:].set(0)
        qi = normal(4, (rows, ix["heads"], t, ix["width"]))
        wi = normal(5, (rows, t, ix["heads"]), 1.0, jnp.float32)
        table = bt[:rows]
        pairs = float(jnp.sum(last + 1))
        # scoring
        score = repeat(lambda layer, qi, wi, idx, table, last:
                       da.paged_index_scores_pallas(
                           qi, wi, idx, table, last, layer=layer,
                           interpret=interpret))
        scores = da.paged_index_scores_pallas(qi, wi, idx, table, last,
                                              layer=0, interpret=interpret)
        # (the XLA form holds [heads, queries, keys] products: 64 queries)
        want = sia.index_scores_reference(qi[:, :, :64], wi[:, :64], idx,
                                          table, last[:, :64], 0)
        both = jnp.isfinite(want)
        err = float(jnp.max(jnp.abs(jnp.where(both, scores[:, :64] - want,
                                              0.0)))
                    / jnp.max(jnp.abs(jnp.where(both, want, 0.0))))
        report(f"paged_index_scores {label}",
               timed(score, qi, wi, idx, table, last),
               rows * ctx * per_key["index"], pairs * flops_key["index"], err)
        # selection
        select = repeat(lambda layer, s: da.paged_sparse_select_pallas(
            s, TOPK, interpret=interpret))
        theta, s_last = da.paged_sparse_select_pallas(scores, TOPK,
                                                      interpret=interpret)
        t_ref, s_ref = sia.select_threshold_reference(scores, TOPK)
        exact = bool(jnp.all(theta == t_ref) & jnp.all(s_last == s_ref))
        report(f"paged_sparse_select {label} (exact: {exact})",
               timed(select, scores), scores.size * 4, 0.0)
        # the read under the selection
        keep = sia.chosen(scores, theta, s_last, last)
        chosen = float(jnp.sum(keep))
        read = repeat(lambda layer, q, pool, table, scores, theta, s_last,
                      last: da.paged_sparse_latent_attention_pallas(
                          q, pool, table, scores, theta, s_last, last,
                          rank=f["rank"], layer=layer, interpret=interpret)[0])
        got, landed = da.paged_sparse_latent_attention_pallas(
            q, pool, table, scores, theta, s_last, last, rank=f["rank"],
            layer=0, interpret=interpret)
        want = sia._masked_latent_walk(q, pool, table, keep, last, 0,
                                       f["rank"])
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        print(f"ALONE {label}: {chosen:.0f} keys chosen of {pairs:.0f} "
              f"scored; the kernel landed {int(landed)} blocks = "
              f"{int(landed) * f['bs']} latent rows "
              f"({int(landed) * f['bs'] * f['width'] * 2 / 1e6:.1f} MB)",
              flush=True)
        report(f"paged_sparse_latent_attn {label} (blocks + mask)",
               timed(read, q, pool, table, scores, theta, s_last, last),
               chosen * per_key["latent"], chosen * flops_key["latent"], err)
        if t == 1:
            def gathered(layer, q, pool, table, keep):
                s_max = keep.shape[-1]
                mine = keep[:, 0]
                order = jnp.cumsum(mine, axis=1) - 1
                at = jnp.zeros((rows, TOPK), jnp.int32).at[
                    jnp.arange(rows)[:, None],
                    jnp.where(mine, order, TOPK)].set(
                        jnp.arange(s_max, dtype=jnp.int32)[None, :],
                        mode="drop")
                blocks = jnp.take_along_axis(table, at // f["bs"], axis=1)
                tok = pool[layer, blocks, 0, at % f["bs"]]   # [B, topk, W]
                s = jnp.einsum("bhw,bkw->bhk", q[:, :, 0], tok,
                               preferred_element_type=jnp.float32)
                p = jax.nn.softmax(s, axis=-1).astype(tok.dtype)
                return jnp.einsum("bhk,bkc->bhc", p, tok[..., :f["rank"]])

            alt = gathered(0, q, pool, table, keep)
            err = float(jnp.max(jnp.abs(alt.astype(jnp.float32)
                                        - want[:, :, 0].astype(jnp.float32))))
            report(f"XLA gather of the chosen rows + dense {label} (tokens)",
                   timed(repeat(gathered), q, pool, table, keep),
                   chosen * per_key["latent"], chosen * flops_key["latent"],
                   err)
        # the dense walk beside it (what no selection would read)
        dense = repeat(lambda layer, q, pool, table, pos:
                       da.paged_latent_attention_pallas(
                           q, pool, table, pos, rank=f["rank"], layer=layer,
                           interpret=interpret))
        report(f"paged_latent_* {label} (every key: no selection)",
               timed(dense, q, pool, table, pos),
               rows * ctx * per_key["latent"], pairs * flops_key["latent"])

    # the windowed read over ring blocks
    from deepspeed_tpu.inference.paged import WindowRing

    for label, rows, t in (("decode", ROWS, 1), ("chunk[1,512]", 1, CHUNK)):
        ring = WindowRing(rows, WINDOW, CHUNK, w["bs"])
        for row in range(rows):
            ring.advance(row, ctx - t, ctx)
        wpool = normal(6, (2, ring.alloc.num_blocks, 1, w["bs"], w["width"]))
        wpool = wpool.at[..., w["keys"]:].set(0)
        table = jnp.asarray(ring.tables, jnp.int32)
        pos = jnp.full((rows,), ctx - t, jnp.int32)
        q = normal(7, (rows, w["heads"], t, w["width"]), 0.1)
        q = q.at[..., w["keys"]:].set(0)
        got = da.paged_latent_attention_pallas(
            q, wpool, table, pos, rank=w["rank"], layer=0, window=WINDOW,
            interpret=interpret)
        want = da.paged_latent_attention_reference(
            q, wpool, table, pos, rank=w["rank"], layer=0, window=WINDOW)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        walk = repeat(lambda layer, q, wpool, table, pos:
                      da.paged_latent_attention_pallas(
                          q, wpool, table, pos, rank=w["rank"], layer=layer,
                          window=WINDOW, interpret=interpret))
        visible = rows * t * WINDOW
        report(f"{da.latent_kernel_name(t, WINDOW)} {label}",
               timed(walk, q, wpool, table, pos),
               rows * (WINDOW + t - 1) * per_key["window"],
               visible * flops_key["window"], err)


def main(argv):
    import jax

    if jax.devices()[0].platform != "tpu":
        print(f"no TPU here ({jax.devices()}): a CPU's time is no device "
              "number", file=sys.stderr)
        return 2
    run(int(argv[1]) if len(argv) > 1 else 20480)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
