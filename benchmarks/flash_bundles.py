"""The flash kernels' own schedules, read WITHOUT a chip: bundles a grid step
and how busy each unit is, from the compiler's dump of a compile for a
described v5e.

    JAX_PLATFORMS=cpu python3 benchmarks/flash_bundles.py [<checkout>] [strip ...]

A TPU core issues one VLIW bundle a cycle and the compiler schedules them
statically, so a kernel's ``total scheduled bundles`` is the cycles one pass
through its body takes, less what it waits for (DMA).  Per kernel this prints
that count and, from the per-bundle utilisation table, the cycles each unit is
held: ``MXU`` (4 units; a packed 16-row push holds one 13.6 cycles, a latched
weight vreg 4), ``XLU`` (3: cross-lane reductions, lane permutes, transposes),
``VALU`` (4), ``EUP`` (1: exp2, reciprocal), vector loads (3) and stores (1),
spills among them.  A whole tile of these kernels is MXU-bound (90 % busy); a
body whose count stands far above ``MXU / 4`` is held by something else, and
the table says by what.  It is a count of a SCHEDULE, not a time: in the
training cells a grid step takes 1.2-1.35 x its bundles at 1.5 GHz, and a
saving shows at about two thirds of its size (PERF.md section 6, PR 62).

A chunked (v3) kernel's count sums every body it holds (init, each kind of
tile, finalize), so each call is compiled at shapes that isolate one:
``S = 1,024`` in one tile (init + EDGE + finalize), ``S = 2,048`` not causal
(init + INTERIOR + finalize), ``S = 2,048`` causal (init + every kind +
finalize).  ``strip`` arguments (default ``0 256``) set ``_STRIP`` for the
compile — 0: whole tiles — as ``benchmarks/flash_alone.py`` does on the chip;
never a setting of the program.  Each compile runs in a child process (libtpu
aborts at exit once it has dumped) and takes ~10 s.
"""

import glob
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile

UNITS = ("MXU", "XLU", "VALU", "EUP", "VLD", "VLD:FILL", "VST", "VST:SPILL",
         "SALU")
#: (label, [B, H, S, hd], KV heads, causal, window, blocks, forced to v3)
CALLS = [
    ("resident edge (gpt2m-train-1k)", (8, 16, 1024, 64), 16, True, 0,
     (1024, 1024), False),
    ("chunked hd64 edge", (8, 16, 1024, 64), 16, True, 0, (None, None), True),
    ("chunked hd64 interior", (8, 32, 2048, 64), 32, False, 0, (None, None),
     False),
    ("chunked hd64 all (opt13b-zero3-x4)", (8, 32, 2048, 64), 32, True, 0,
     (None, None), False),
    ("chunked hd128 window all (smallthinker-train-8k)", (1, 28, 8192, 128),
     4, True, 4096, (None, None), False),
]

CHILD = r'''
import json, os, sys
os.environ.setdefault("TPU_LOG_DIR", "disabled")
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from deepspeed_tpu.ops import flash_attention as fa
shape, hkv, causal, window, blocks, strip = json.loads(sys.argv[1])
if hasattr(fa, "_STRIP"):
    fa._STRIP = strip or 2 ** 30
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])
b, h, s, d = shape
q = jax.ShapeDtypeStruct(tuple(shape), jnp.bfloat16, sharding=one)
kv = jax.ShapeDtypeStruct((b, hkv, s, d), jnp.bfloat16, sharding=one)
def loss(q, k, v):
    return fa.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=blocks[0], block_k=blocks[1],
                              interpret=False).astype(jnp.float32).sum()
jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile()
'''


def schedules(checkout, shape, hkv, causal, window, blocks, v3, strip):
    """{kernel: (bundles, {unit: cycles held})} of one call's kernels."""
    dump = tempfile.mkdtemp(prefix="flash_bundles_")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={dump}")
    if v3:
        env.update(DS_FLASH_V2="0", DS_FLASH_V3_MIN_KV="8")
    subprocess.run(
        [sys.executable, "-c", CHILD,
         json.dumps([shape, hkv, causal, window, blocks, strip])],
        env=env, cwd=checkout, capture_output=True)
    found = {}
    for path in sorted(glob.glob(
            os.path.join(dump, "*flash*schedule-analysis_final_bundles.txt"))):
        kernel = re.search(r"(flash[a-z_0-9]*?)_*\.",
                           os.path.basename(path)).group(1)
        bundles = int(re.search(r"total scheduled bundles:\s+(\d+)",
                                open(path).read()).group(1))
        stem = os.path.basename(path).split("-")[1]
        held = {}
        for table in glob.glob(os.path.join(
                dump, f"*-{stem}-*final_hlo-static-per-bundle-"
                      "utilization.txt")):
            rows = [line.split() for line in open(table)
                    if re.fullmatch(r"(\d+ ){8}\d+\s*", line)]
            held = {unit: sum(int(r[i]) for r in rows)
                    for i, unit in enumerate(UNITS)}
        found[kernel] = (bundles, held)
    shutil.rmtree(dump, ignore_errors=True)
    return found


def main(argv):
    checkout = "."
    if len(argv) > 1 and not argv[1].isdigit():
        checkout, argv = argv[1], argv[1:]
    strips = [int(x) for x in argv[1:]] or [0, 256]
    for label, shape, hkv, causal, window, blocks, v3 in CALLS:
        for strip in strips:
            got = schedules(os.path.abspath(checkout), shape, hkv, causal,
                            window, blocks, v3, strip)
            if not got:
                print(f"BUNDLES {label} strip {strip}: no schedule was "
                      "dumped (does this libtpu take --xla_jf_dump_to?)",
                      flush=True)
            for kernel, (bundles, held) in got.items():
                print(f"BUNDLES {label} strip {strip} {kernel}: {bundles} "
                      "bundles; held "
                      + " ".join(f"{u} {n}" for u, n in held.items()),
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
