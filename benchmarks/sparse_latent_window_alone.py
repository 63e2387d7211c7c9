"""The selected latent read, the scoring and the selection at a VERIFY
WINDOW, alone, on the chip, at ``glm5-agentloop-closed``'s shapes: 24 rows,
each with ``WINDOW`` (2) query positions at a base of ~9.8k keys, 64 heads
over ``[256, 640]`` latent blocks (rank 512) beside a 128-wide index key, 32
index heads, 2,048 keys chosen a position, bf16.

    python3 benchmarks/sparse_latent_window_alone.py [context [window]]

Three bodies for the read, one timing each (``sparse_latent_alone.py``'s
manner: the least of ``RUNS`` runs of a program of ``CALLS`` calls, the
largest difference from the XLA form on the same operands):

* **the decode kernel at two query rows** — ``paged_sparse_latent_attn`` with
  ONE grid step a row, the window's positions' query rows side by side (``tq
  = window``: 128 rows at 64 heads), a block landed once for both;
* **the prefill kernel at a short tile** — the same kernel at its prefill
  tile of 8 positions, the window padded with 6 positions that see no key;
* **the XLA walk** (``_masked_latent_walk``: gather + mask, 512 keys a step).

Exit 2 without a TPU.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import sparse_latent_alone as alone  # noqa: E402

ROWS, TOPK = 24, 2048
FULL = dict(heads=64, bs=256, width=640, rank=512, keys=576)
INDEX = dict(heads=32, width=128)


def run(ctx: int, window: int, max_seq_len: int = 12288,
        interpret: bool = False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops import decode_attention as da
    from deepspeed_tpu.ops import sparse_index_attention as sia

    key = jax.random.PRNGKey(64)
    f, ix, t = FULL, INDEX, window
    nbper = max_seq_len // f["bs"]
    nb = 1 + ROWS * nbper

    def normal(i, shape, scale=1.0, dtype=jnp.bfloat16):
        return (jax.random.normal(jax.random.fold_in(key, i), shape)
                * scale).astype(dtype)

    pool = normal(1, (2, nb, 1, f["bs"], f["width"]))
    pool = pool.at[..., f["keys"]:].set(0)
    idx = normal(2, (2, nb, 1, f["bs"], ix["width"]))
    table = jnp.asarray(1 + np.arange(ROWS * nbper).reshape(ROWS, nbper),
                        jnp.int32)
    # rows at bases of their own, ~ctx keys each
    pos = jnp.asarray(ctx - t - 37 * np.arange(ROWS), jnp.int32)
    last = sia.last_visible(pos, t, ROWS)
    q = normal(3, (ROWS, f["heads"], t, f["width"]), 0.1)
    q = q.at[..., f["keys"]:].set(0)
    qi = normal(4, (ROWS, ix["heads"], t, ix["width"]))
    wi = normal(5, (ROWS, t, ix["heads"]), 1.0, jnp.float32)
    pairs = float(jnp.sum(last + 1))
    latent_b, index_b = f["keys"] * 2, ix["width"] * 2
    latent_f = f["heads"] * 2 * (f["keys"] + f["rank"])
    index_f = ix["heads"] * 2 * ix["width"] + 2 * ix["heads"]

    score = alone.repeat(lambda layer, qi, wi, idx, table, last:
                         da.paged_index_scores_pallas(
                             qi, wi, idx, table, last, layer=layer,
                             interpret=interpret))
    scores = da.paged_index_scores_pallas(qi, wi, idx, table, last, layer=0,
                                          interpret=interpret)
    want = sia.index_scores_reference(qi, wi, idx, table, last, 0)
    both = jnp.isfinite(want)
    err = float(jnp.max(jnp.abs(jnp.where(both, scores - want, 0.0)))
                / jnp.max(jnp.abs(jnp.where(both, want, 0.0))))
    alone.report(f"paged_index_scores window[{ROWS},{t}]",
                 alone.timed(score, qi, wi, idx, table, last),
                 float(jnp.sum(jnp.max(last, axis=1) + 1)) * index_b,
                 pairs * index_f, err)
    select = alone.repeat(lambda layer, s: da.paged_sparse_select_pallas(
        s, TOPK, interpret=interpret))
    theta, s_last = da.paged_sparse_select_pallas(scores, TOPK,
                                                  interpret=interpret)
    t_ref, s_ref = sia.select_threshold_reference(scores, TOPK)
    exact = bool(jnp.all(theta == t_ref) & jnp.all(s_last == s_ref))
    alone.report(f"paged_sparse_select window[{ROWS},{t}] (exact: {exact})",
                 alone.timed(select, scores), scores.size * 4, 0.0)
    keep = sia.chosen(scores, theta, s_last, last)
    chosen = float(jnp.sum(keep))
    want = sia._masked_latent_walk(q, pool, table, keep, last, 0, f["rank"])

    def differ(got):
        return float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                     - want.astype(jnp.float32))))

    def read(layer, q, pool, table, scores, theta, s_last, last):
        return da.paged_sparse_latent_attention_pallas(
            q, pool, table, scores, theta, s_last, last, rank=f["rank"],
            layer=layer, interpret=interpret)[0]

    got, landed = da.paged_sparse_latent_attention_pallas(
        q, pool, table, scores, theta, s_last, last, rank=f["rank"], layer=0,
        interpret=interpret)
    print(f"ALONE window: {chosen:.0f} keys chosen of {pairs:.0f} scored; one "
          f"step a row landed {int(landed)} blocks "
          f"({int(landed) * f['bs'] * f['width'] * 2 / 1e6:.1f} MB)",
          flush=True)
    need = (chosen * latent_b, chosen * latent_f)
    alone.report(f"read A: paged_sparse_latent_attn, one step a row (tq={t})",
                 alone.timed(alone.repeat(read), q, pool, table, scores,
                             theta, s_last, last), *need, differ(got))
    # the prefill tile: the window padded to 8 positions that see no key
    pad = 8 - t
    wide = dict(
        q=jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0))),
        scores=jnp.pad(scores, ((0, 0), (0, pad), (0, 0)),
                       constant_values=-jnp.inf),
        theta=jnp.pad(theta, ((0, 0), (0, pad))),
        s_last=jnp.pad(s_last, ((0, 0), (0, pad))),
        last=jnp.pad(last, ((0, 0), (0, pad)), constant_values=-1))

    def read8(layer, q, pool, table, scores, theta, s_last, last):
        return read(layer, q, pool, table, scores, theta, s_last,
                    last)[:, :, :t]

    args8 = (wide["q"], pool, table, wide["scores"], wide["theta"],
             wide["s_last"], wide["last"])
    alone.report("read B: paged_sparse_latent_attn, the prefill tile (tq=8, "
                 f"{pad} pad positions)",
                 alone.timed(alone.repeat(read8), *args8), *need,
                 differ(read8(0, *args8)))
    walk = alone.repeat(lambda layer, q, pool, table, keep, last:
                        sia._masked_latent_walk(q, pool, table, keep, last,
                                                layer, f["rank"]))
    alone.report("read C: the XLA walk (_masked_latent_walk)",
                 alone.timed(walk, q, pool, table, keep, last), *need, 0.0)


def main(argv):
    import jax

    if jax.devices()[0].platform != "tpu":
        print(f"no TPU here ({jax.devices()}): a CPU's time is no device "
              "number", file=sys.stderr)
        return 2
    run(int(argv[1]) if len(argv) > 1 else 9800,
        int(argv[2]) if len(argv) > 2 else 2)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
