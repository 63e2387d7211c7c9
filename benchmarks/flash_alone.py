"""The flash kernels alone, on the chip: ms a call of each kernel, whole tiles
against strips inside a tile, at the training cells' own calls.

    python3 benchmarks/flash_alone.py <checkout> <tag> [variant ...]

``<checkout>``: the tree whose ``deepspeed_tpu`` is timed (``.`` or a
``git archive`` of the parent unpacked beside it: run both in ONE chiprun
call, one process after the other); ``<tag>`` is printed on every line.  A
tree from before strips existed (no ``_resolve_strip``) has the one variant
``whole``.

Calls (bf16, causal): ``[8,16,1024,64]`` at the blocks gpt2m-train-1k names
(1024 x 1024: v2, ``flash_fwd_resident`` + ``flash_bwd_fused``);
``[8,32,2048,64]`` (opt13b-zero3-x4: v3, ``flash_fwd_chunked`` +
``flash_bwd_dq_chunked`` + ``flash_bwd_dkv_chunked``); ``[1,28,8192,128]`` on
4 KV heads with no window and with ``window`` 4,096 (smallthinker-train-8k's
full and windowed layers: v3) — the v3 calls at the blocks the rule chooses.

Variants, never settings of the program (the script sets the module's
constant and wraps its planner; the program reads the strip from its shapes):
``whole`` — ``strip`` 0, one masked whole-tile body; ``t128`` / ``t256`` /
``t512`` — strips of that many keys or queries, interior tiles forked to a
body without a mask; ``tN-nofork`` — strips on the edge tiles, the interior
tiles keep the whole-tile masked body.  Every variant's results are compared
with the first one's
(``maxdiff``: the largest absolute difference of any output element).

A kernel's time is the least of 20 runs of a jitted program that calls it
once (the backward kernels on the forward's own residuals; v3's ``dq`` and
``dkv`` apart: the other one is dead code to XLA).  READ IT AS A RATIO: a
call alone takes ~1.5 x what the same call takes inside a training step
(6.13 ms against 3.95 for opt13b-zero3-x4's forward), whatever the kernel
does inside, so a saving reads smaller here than in a cell;
``benchmarks/flash_bundles.py`` reads the kernels' schedules without a chip.
PERF.md section 6 (PR 62) has the table this printed.
"""

import math
import os
import statistics
import sys
import time

RUNS = 20
#: (cell, [B, H, S, hd], KV heads, window, blocks given)
CALLS = [("gpt2m-train-1k", (8, 16, 1024, 64), 16, 0, (1024, 1024)),
         ("opt13b-zero3-x4", (8, 32, 2048, 64), 32, 0, (None, None)),
         ("smallthinker-full", (1, 28, 8192, 128), 4, 0, (None, None)),
         ("smallthinker-window", (1, 28, 8192, 128), 4, 4096, (None, None))]
VARIANTS = ["whole"] + [f"t{t}{how}" for t in (128, 256, 512)
                        for how in ("", "-nofork")]


def timed(program, *args):
    import jax

    jax.block_until_ready(program(*args))
    seconds = []
    for _ in range(RUNS):
        t0 = time.perf_counter()
        jax.block_until_ready(program(*args))
        seconds.append(time.perf_counter() - t0)
    return min(seconds) * 1e3, statistics.median(seconds) * 1e3


def set_variant(fa, variant, planner):
    """Point the module at ``variant``; -> False where the tree cannot."""
    if not hasattr(fa, "_resolve_strip"):
        return variant == "whole"
    size, _, how = variant[1:].partition("-")
    fa._STRIP = int(size) if variant != "whole" else 2 ** 30
    interior = lambda n, m: ((0, n, ((0, m, None),)),)

    def strips(d, last, by_cols, causal, block_q, block_k, window, strip,
               kv_left):
        got = planner(d, last, by_cols, causal, block_q, block_k, window,
                      strip, kv_left)
        n, m = (block_k, block_q) if by_cols else (block_q, block_k)
        if got != interior(n, m) or how != "nofork":
            return got
        return ((0, n, ((0, m, fa._Mask(causal, window, False)),)),)

    fa._tile_strips = strips
    fa._kinds.cache_clear()
    return True


def programs(fa, shape, hkv, window, blocks):
    """-> (choice, [(kernel, program, operands)]) of one call, forward first;
    the backward's operands are made by running the forward."""
    import jax
    import jax.numpy as jnp

    b, h, s, d = shape
    rep = h // hkv
    scale = 1.0 / math.sqrt(d)
    choice, pad_q, pad_k = fa._resolve_blocks(s, s, d, 2, *blocks)
    assert (pad_q, pad_k) == (0, 0), choice
    bq, bk, gen = choice.block_q, choice.block_k, choice.generation
    extra = ()
    if hasattr(fa, "_resolve_strip"):
        strip = fa._resolve_strip(gen, True, s, s, s, bq, bk, window)
        choice = choice._replace(window=window, strip=strip)
        extra = (strip,)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b * h, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b * hkv, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b * hkv, s, d), jnp.bfloat16)
    do = jax.random.normal(ks[3], (b * h, s, d), jnp.bfloat16)
    if gen == "v2":
        fwd = jax.jit(lambda q, k, v: fa._fwd_v2(
            q, k, v, scale, True, bq, False, s, rep, window, *extra))
        bwd = jax.jit(lambda q, k, v, o, do: fa._bwd_v2(
            q, k, v, o, do, scale, True, bq, False, s, rep, window, *extra))
        o = fwd(q, k, v)
        return choice, [(fa.KERNELS[gen][0], fwd, (q, k, v)),
                        (fa.KERNELS[gen][1], bwd, (q, k, v, o, do))]
    assert gen == "v3", choice
    fwd = jax.jit(lambda q, k, v: fa._fwd_v3(
        q, k, v, scale, True, bq, bk, False, s, rep, window, *extra))
    both = lambda *a: fa._bwd_v3(*a, scale, True, bq, bk, False, s, rep,
                                 window, *extra)
    o, lse = fwd(q, k, v)
    back = (q, k, v, o, lse, do)
    return choice, [(fa.KERNELS[gen][0], fwd, (q, k, v)),
                    (fa.KERNELS[gen][1], jax.jit(lambda *a: both(*a)[0]),
                     back),
                    (fa.KERNELS[gen][2], jax.jit(lambda *a: both(*a)[1:]),
                     back)]


def main(argv):
    if len(argv) < 3:
        print(__doc__, file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.abspath(argv[1]))
    tag = argv[2]
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print(f"no TPU here ({jax.devices()}): a CPU's time is no device "
              "number", file=sys.stderr)
        return 2
    from deepspeed_tpu.ops import flash_attention as fa

    planner = getattr(fa, "_tile_strips", None)
    reference = {}
    for variant in argv[3:] or VARIANTS:
        if not set_variant(fa, variant, planner):
            continue
        for cell, shape, hkv, window, blocks in CALLS:
            try:
                choice, kernels = programs(fa, shape, hkv, window, blocks)
            except Exception as e:       # a strip the compiler refuses
                print(f"ALONE {tag} {cell} {variant}: refused "
                      f"({str(e).splitlines()[0][:160]})", flush=True)
                continue
            share = ""
            if hasattr(fa, "computed_pairs"):
                visible, computed = fa.computed_pairs(choice)
                share = f" visible/computed {100 * visible / computed:.1f} %"
            for kernel, program, operands in kernels:
                try:
                    least, median = timed(program, *operands)
                except Exception as e:
                    print(f"ALONE {tag} {cell} {variant} {kernel}: refused "
                          f"({str(e).splitlines()[0][:160]})", flush=True)
                    continue
                outs = [jnp.asarray(x, jnp.float32) for x in
                        jax.tree_util.tree_leaves(program(*operands))]
                want = reference.setdefault((cell, kernel), outs)
                diff = max(float(jnp.max(jnp.abs(a - b)))
                           for a, b in zip(outs, want))
                print(f"ALONE {tag} {cell} {variant} {kernel} blocks "
                      f"{choice.block_q}x{choice.block_k} strip "
                      f"{getattr(choice, 'strip', 0)}: min {least:.3f} "
                      f"median {median:.3f} ms a call; maxdiff {diff:.3g}"
                      f"{share}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
