"""What the scope-table readers share (the loader skips ``_*.py``).

From PR 68 an engine records, at each program's first call, what finds its
compiled executable again (``deepspeed_tpu/telemetry/programs.py``), and
builds on demand a SCOPE TABLE of it from its scheduled text
(``telemetry/hlo_text.py scope_table``): per scope of the program's own
vocabulary (``telemetry/scopes.py``: ``layer/attn/qkv``, ``head``,
``sample/filter`` ... and ``unscoped`` for what carries none) and per pass
(``fwd`` | ``bwd`` | ``remat``) the bytes ONE call of the program moves —
``bytes`` through HBM at its instructions' boundaries, ``onchip_bytes`` in
arrays the compiler keeps outside HBM, ``kernel_bytes`` the operands a
Pallas call is handed whole (an upper bound) — its matmul flops and its
instruction count, each instruction times the trips of the loops around it.
The newest engine's tables stay reachable through
``telemetry.trace.kept("programs")`` after the engine is closed.

These are counters of the COMPILED program: no profiler, no clock.  The
sums over the rows are each reader's own.  A program without such tables
(the parent of the PR that added them), a context that names no cell, or an
engine none of whose programs was called gives ``None``, and the metric is
left out of the line.
"""

#: the programs of an engine's decode side, by the names its sentry
#: registered: the plain step, or a speculative round's two
DECODE_SIDE = ("decode", "verify", "draft")
TRAIN_SIDE = ("train_step",)
UNSCOPED = "unscoped"


def tables(ctx, names):
    """``{program: scope table}`` of the newest engine's programs among
    ``names``, or None."""
    if not ctx.get("cell"):
        return None
    try:
        from deepspeed_tpu.telemetry import trace
    except ImportError:
        return None
    kept = getattr(trace, "kept", None)
    programs = kept("programs") if kept else None
    if programs is None or not hasattr(programs, "table"):
        return None
    found = {}
    for name in names:
        if name in getattr(programs, "records", {}):
            found[name] = programs.table(name)
    return found or None


def rows(found):
    """Every per-scope row of the tables, with its program."""
    return [{**row, "program": name} for name, table in found.items()
            for row in table["scopes"]]


def cover(ctx, names, what):
    """Of the HBM bytes a call of the programs ``names`` moves, the share
    (%) under an entry of the vocabulary; prints the ``mixed`` share and
    the heaviest unscoped instructions."""
    found = tables(ctx, names)
    if found is None:
        return None
    every = rows(found)
    total = sum(r["bytes"] for r in every)
    if not total:
        return None
    bare = sum(r["bytes"] for r in every if r["scope"] == UNSCOPED)
    mixed = sum(r["mixed_bytes"] for r in every)
    worst = sorted(((i["bytes"] * i["trips"], f"{name}:{serial}")
                    for name, table in found.items()
                    for serial, i in table["instructions"].items()
                    if i["scope"] == UNSCOPED and i["bytes"]),
                   reverse=True)[:6]
    built = {name: round(t.get("build_s", 0.0), 3)
             for name, t in found.items()}
    print(f"chipbench: scope tables of the {what} side {sorted(found)}: "
          f"{total / 1e6:.1f} MB through HBM a call, {bare / 1e6:.3f} MB "
          f"unscoped, {100 * mixed / total:.2f} % in mixed fusions; built "
          f"in {built} s with "
          f"{[t.get('backend_compiles') for t in found.values()]} backend "
          "compiles; heaviest unscoped: "
          + (", ".join(f"{n} {b / 1e6:.3f} MB" for b, n in worst) or "none"),
          flush=True)
    return 100.0 * (total - bare) / total
