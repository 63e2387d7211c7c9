"""The sampler's nucleus search alone, on the chip, at the serving cells'
``[rows, vocab]``: ZAYA1 decode, Granite decode, Keye decode, OLMoE decode,
the chat cell's decode.

    python3 benchmarks/nucleus_search_alone.py [all]

For each shape: ``ops/sampling.py``'s plain loop (``_nucleus_threshold``),
the tiled kernel (``_nucleus_threshold_tiled``) and, with ``all``, the
kernel at other tiles / unrolls and the plain loop under ``lax.map`` over
row slices; the top-k search's two forms; then ``filtered_logprobs``
whole, as the width rule routes it and with each form forced.  A time is
the host clock over 20 calls enqueued back to back and settled once (the
device runs them end to end, so the dispatch hides behind the device unless
a call is under ~0.05 ms), the least of 5 such rounds.  Thresholds of the
variants are compared with the plain loop's: rows whose mass lies within
float32 rounding of ``top_p`` may differ by the reduction's order, and
their count is printed.  Refuses to run without a TPU (exit 2).  PERF.md
section 6 (PR 67) has the table this printed.
"""

import sys
import time

SHAPES = [(128, 262272), (64, 100352), (16, 151936), (64, 50304),
          (24, 50272)]
CALLS, ROUNDS = 20, 5
HBM_BYTES_S = 819e9                                  # one v5e chip


def timed(fn, *args):
    import jax

    jax.block_until_ready(fn(*args))
    best = 1e9
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        outs = [fn(*args) for _ in range(CALLS)]
        jax.block_until_ready(outs)
        best = min(best, (time.perf_counter() - t0) / CALLS)
    return best * 1e3


def main(which=""):
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print(f"no TPU here ({jax.devices()}): a CPU's time is no device "
              "number", file=sys.stderr)
        return 2
    from deepspeed_tpu.ops import sampling as S

    def mapped(rows_a_slice):
        def run(probs, p):
            rows, vocab = probs.shape
            n = rows // rows_a_slice
            thr = jax.lax.map(
                lambda a: S._nucleus_threshold(*a),
                (probs.reshape(n, rows_a_slice, vocab),
                 p.reshape(n, rows_a_slice, 1)))
            return thr.reshape(rows, 1)
        return run

    def kernel(**kw):
        return lambda probs, p: S._nucleus_threshold_tiled(
            probs, p, interpret=False, **kw)

    variants = {"plain": S._nucleus_threshold, "tiled": kernel()}
    if which == "all":
        variants.update({
            "tiled unroll=4": kernel(unroll=4),
            "tiled unroll=16": kernel(unroll=16),
            "tiled tile=8": kernel(tile=8),
            "tiled tile=8 u=16": kernel(tile=8, unroll=16),
            "map 8": mapped(8), "map 16": mapped(16)})
    for rows, vocab in SHAPES:
        # the cells' regime: near-uniform logits at T 0.7 / top-p 0.9
        logits = jax.random.normal(jax.random.PRNGKey(rows + vocab),
                                   (rows, vocab), jnp.float32) * 0.5
        probs = jax.nn.softmax(logits / 0.7, axis=-1)
        p = jnp.full((rows, 1), 0.9, jnp.float32)
        nbytes = rows * vocab * 4
        print(f"[{rows}, {vocab}] {nbytes / 1e6:.1f} MB: once over HBM "
              f"{nbytes / HBM_BYTES_S * 1e3:.3f} ms, 30 times "
              f"{30 * nbytes / HBM_BYTES_S * 1e3:.3f} ms", flush=True)
        want = None
        for name, fn in variants.items():
            if name.startswith("map") and rows % int(name.split()[1]):
                continue
            fn = jax.jit(fn)
            try:
                ms = timed(fn, probs, p)
            except Exception as e:                      # a Mosaic refusal
                print(f"  {name:18s} REFUSED {str(e)[:300]}", flush=True)
                continue
            thr = fn(probs, p)
            want = thr if want is None else want
            differ = int(jnp.sum(thr != want))
            kept = jnp.sum(jnp.where(probs >= thr, probs, 0.0), axis=-1)
            print(f"  {name:18s} {ms:8.3f} ms  rows off the plain loop's "
                  f"threshold {differ}  least kept mass "
                  f"{float(kept.min()):.7f}", flush=True)
        k = jnp.full((rows, 1), 50, jnp.int32)
        for name, fn in (("top-k plain", S._kth_largest),
                         ("top-k tiled", lambda x, k: S._kth_largest_tiled(
                             x, k, interpret=False))):
            fn = jax.jit(fn)
            ms = timed(fn, logits, k)
            exact = int(jnp.sum(
                fn(logits, k)[:, 0] == jnp.sort(logits, axis=-1)[:, -50]))
            print(f"  {name:18s} {ms:8.3f} ms  rows whose 50th largest is "
                  f"the sort's: {exact} of {rows}", flush=True)
        knobs = (jnp.full((rows,), 0.7, jnp.float32),
                 jnp.zeros((rows,), jnp.int32),
                 jnp.full((rows,), 0.9, jnp.float32))
        line = S.TILED_FROM
        for S.TILED_FROM in dict.fromkeys((line, 1 << 62, 1)):
            # (a function of its own each: a trace is cached by function)
            ms = timed(jax.jit(lambda *a: S.filtered_logprobs(*a)),
                       logits.astype(jnp.bfloat16), *knobs)
            print(f"  filtered_logprobs, TILED_FROM {S.TILED_FROM} "
                  f"({S.thresholds(vocab)}) {ms:8.3f} ms", flush=True)
        S.TILED_FROM = line
    return 0


if __name__ == "__main__":
    sys.path.insert(0, ".")
    sys.exit(main(*sys.argv[1:2]))
