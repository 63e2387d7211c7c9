"""Collective benchmark sweep — the ``ds_bench`` analog (reference
``bin/ds_bench`` -> ``benchmarks/communication/run_all.py``): latency and
algorithmic bandwidth for all_reduce / all_gather / reduce_scatter /
all_to_all / ppermute over a size sweep on the current mesh.

Usage: python benchmarks/comm_bench.py [--dp N] [--trials T]
       [--maxsize-mb M | --sizes-mb A,B,...] [--op all|all_reduce|...]

``--sizes-mb`` times the given per-device buffer sizes (MB, decimal) in
place of the sweep: the sizes a step's own collectives have.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def algo_bw(op: str, nbytes: int, n: int, seconds: float) -> float:
    """Algorithmic bandwidth GB/s (reference ``communication/utils.py``
    conventions: ring all-reduce moves 2(n-1)/n of the data)."""
    if op == "all_reduce":
        moved = 2 * nbytes * (n - 1) / n
    elif op in ("all_gather", "reduce_scatter", "all_to_all"):
        moved = nbytes * (n - 1) / n
    else:  # ppermute
        moved = nbytes
    return moved / seconds / 1e9


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=None,
                    help="mesh size (default: all devices)")
    ap.add_argument("--trials", type=int, default=20)
    ap.add_argument("--maxsize-mb", type=float, default=64.0)
    ap.add_argument("--sizes-mb", default=None,
                    help="comma-separated per-device sizes in MB (10^6 B) "
                         "to time in place of the sweep")
    ap.add_argument("--op", default="all",
                    choices=["all", "all_reduce", "all_gather",
                             "reduce_scatter", "all_to_all", "ppermute"])
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.parallel.topology import MeshTopology

    n = args.dp or len(jax.devices())
    mesh = MeshTopology(dp=n).mesh

    ops = {}

    def reg(name):
        def deco(fn):
            ops[name] = fn
            return fn
        return deco

    reg("all_reduce")(lambda x: jax.lax.psum(x, "dp"))
    reg("all_gather")(lambda x: jax.lax.all_gather(x, "dp"))
    reg("reduce_scatter")(
        lambda x: jax.lax.psum_scatter(x, "dp", tiled=True))
    reg("all_to_all")(
        lambda x: jax.lax.all_to_all(x.reshape(n, -1), "dp", 0, 0,
                                     tiled=False))
    reg("ppermute")(lambda x: jax.lax.ppermute(
        x, "dp", [(i, (i + 1) % n) for i in range(n)]))

    selected = list(ops) if args.op == "all" else [args.op]
    sizes = []
    s = 1 << 12
    while s <= args.maxsize_mb * 2 ** 20:
        sizes.append(int(s))
        s *= 8
    if args.sizes_mb:
        sizes = [int(float(mb) * 1e6) for mb in args.sizes_mb.split(",")]

    results = []
    for op in selected:
        fn = ops[op]
        for nbytes in sizes:
            elems = nbytes // 4
            if elems % n:  # psum_scatter/all_to_all need n | elems
                elems += n - elems % n

            @jax.jit
            def bench(x):
                def body(xw):
                    acc = jnp.zeros((), jnp.float32)
                    for _ in range(args.trials):
                        # chain iterations through a scalar so the compiler
                        # cannot parallelize or elide the collectives
                        y = xw[0] + acc
                        acc = acc + 0.0 * jnp.sum(fn(y)).astype(jnp.float32)
                    return (xw[0] + acc)[None]

                return jax.shard_map(body, mesh=mesh, in_specs=P("dp"),
                                 out_specs=P("dp"))(x)

            # placed as the program wants it, so that no call moves it
            x = jax.device_put(jnp.ones((n, elems), jnp.float32),
                               NamedSharding(mesh, P("dp")))
            with mesh:
                jax.block_until_ready(bench(x))        # compile
                t0 = time.perf_counter()
                jax.block_until_ready(bench(x))
                dt = (time.perf_counter() - t0) / args.trials
            results.append({
                "op": op, "bytes": nbytes,
                "latency_us": round(dt * 1e6, 1),
                "algo_bw_gbps": round(algo_bw(op, nbytes, n, dt), 2),
            })
            print(json.dumps(results[-1]))
    return results


if __name__ == "__main__":
    main()
