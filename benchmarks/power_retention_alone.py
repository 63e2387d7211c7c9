"""``power_step`` and ``power_chunk_state`` alone, on the chip, at
Brumby-14B-Base's shapes (40 query / 8 KV heads x 128, 10 layers, 12 rows).

    python3 benchmarks/power_retention_alone.py

Each kernel against its plain body at a small row count first (the largest
difference of the outputs and of the states), then its time: a program is
a call a layer (10 calls), the least of 10 runs, beside the bytes / FLOPs
that ``chipbench/families/brumby.py`` counts for it (the ceilings that the
``power_*_roofline`` metrics read against: no reading may pass 100 %).
Refuses to run without a TPU (exit 2): both kernels are asked for compiled
(``interpret=False``), and a CPU's time is no device number.
PERF.md section 6 (PR 57) has the table this printed.
"""

import sys
import time

ROWS, LAYERS, HQ, H, N, RUNS = 12, 10, 40, 8, 128, 10
HBM_BYTES_S, PEAK_FLOPS = 819e9, 197e12              # one v5e chip


def main():
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print(f"no TPU here ({jax.devices()}): a CPU's time is no device "
              "number", file=sys.stderr)
        return 2
    from deepspeed_tpu.ops import power_retention as pr

    nd = pr.distances(N)
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 64))
    rnd = lambda *s: jax.random.normal(next(keys), s, jnp.float32)

    def gates(*s):
        return jax.nn.log_sigmoid(rnd(*s) + 5.0)

    # ---- the step: agreement at 2 rows, then the time of 12 rows x 10 layers
    leaf = rnd(2, 2, H, nd, N, N) * 0.1
    zl = jnp.abs(rnd(2, 2, H, nd, N))
    q, k, v, lg = rnd(2, HQ, N), rnd(2, H, N), rnd(2, H, N), gates(2, H)
    got = jax.jit(lambda *a: pr.step(
        *a, 1, kernel=True, interpret=False))(q, k, v, lg, leaf, zl)
    want = jax.jit(lambda *a: pr.step(*a, 1, kernel=False))(q, k, v, lg, leaf,
                                                            zl)
    print("power_step vs plain: y %.3g  state %.3g  z %.3g  (|y| %.3g)" % (
        *(float(jnp.abs(a - b).max()) for a, b in zip(got, want)),
        float(jnp.abs(want[0]).max())), flush=True)

    leaf = jnp.zeros((LAYERS, ROWS, H, nd, N, N), jnp.float32)
    zl = jnp.zeros((LAYERS, ROWS, H, nd, N), jnp.float32)
    q, k, v, lg = rnd(ROWS, HQ, N), rnd(ROWS, H, N), rnd(ROWS, H, N), \
        gates(ROWS, H)

    def decode(q, k, v, lg, leaf, zl):
        def layer(l, c):
            acc, leaf, zl = c
            y, leaf, zl = pr.step(q, k, v, lg, leaf, zl, l, kernel=True,
                                  interpret=False)
            return acc + y, leaf, zl
        return jax.lax.fori_loop(0, LAYERS, layer,
                                 (jnp.zeros((ROWS, HQ, N)), leaf, zl))

    decode = jax.jit(decode, donate_argnums=(4, 5))
    acc, leaf, zl = jax.block_until_ready(decode(q, k, v, lg, leaf, zl))
    best = 1e9
    for _ in range(RUNS):
        t0 = time.perf_counter()
        acc, leaf, zl = jax.block_until_ready(decode(q, k, v, lg, leaf, zl))
        best = min(best, time.perf_counter() - t0)
    nbytes = 2 * 4 * LAYERS * ROWS * H * pr.monomials(N) * (N + 1)
    print("power_step: %d rows x %d layers %.3f ms; %.3f GB at 8,256 rows a "
          "head = %.1f %% of %g GB/s" % (
              ROWS, LAYERS, best * 1e3, nbytes / 1e9,
              100 * nbytes / HBM_BYTES_S / best, HBM_BYTES_S / 1e9),
          flush=True)
    del leaf, zl

    # ---- the chunk: agreement at [1, 256], then each rung of the ladder
    s0 = rnd(1, H, nd, N, N) * 0.1
    z0 = jnp.abs(rnd(1, H, nd, N)) * 10
    args = (rnd(1, 256, HQ, N), rnd(1, 256, H, N), rnd(1, 256, H, N),
            gates(1, 256, H), s0, z0)
    got = jax.jit(lambda *a: pr.chunked(*a, kernel=True,
                                        interpret=False))(*args)
    want = jax.jit(lambda *a: pr.chunked(*a, kernel=False))(*args)
    print("power_chunk_state vs plain: y %.3g  state %.3g  z %.3g  (|y| "
          "%.3g)" % (*(float(jnp.abs(a - b).max())
                       for a, b in zip(got, want)),
                     float(jnp.abs(want[0]).max())), flush=True)
    for b, t in ((4, 128), (2, 256), (1, 512)):
        args = (rnd(b, t, HQ, N), rnd(b, t, H, N), rnd(b, t, H, N),
                gates(b, t, H), jnp.zeros((b, H, nd, N, N)),
                jnp.zeros((b, H, nd, N)))

        @jax.jit
        def prefill(q, k, v, lg, s, z):
            def layer(l, c):
                acc, s, z = c
                y, s, z = pr.chunked(q, k, v, lg, s, z, kernel=True,
                                     interpret=False)
                return acc + y, s, z
            return jax.lax.fori_loop(0, LAYERS, layer,
                                     (jnp.zeros_like(q), s, z))

        out = jax.block_until_ready(prefill(*args))
        best = 1e9
        for _ in range(RUNS):
            t0 = time.perf_counter()
            out = jax.block_until_ready(prefill(*args))
            best = min(best, time.perf_counter() - t0)
        d = pr.monomials(N)
        flops = LAYERS * b * t * H * (
            2 * d * N * (HQ // H + 1)                 # phi(Q) S0, phi(K)^T V
            + pr.CHUNK * (HQ // H) * 2 * N * 2)       # Q K^T, A V
        print("power_chunk_state [%d, %d] x %d layers: %.3f ms; %.1f GFLOP = "
              "%.1f %% of %g TFLOP/s" % (
                  b, t, LAYERS, best * 1e3, flops / 1e9,
                  100 * flops / PEAK_FLOPS / best, PEAK_FLOPS / 1e12),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, ".")
    sys.exit(main())
