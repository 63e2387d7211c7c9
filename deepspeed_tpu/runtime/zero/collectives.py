"""What collectives a compiled step has, read from its scheduled HLO text.

``count(compiled.as_text())`` says, per kind (``all-gather``,
``reduce-scatter``, ``all-to-all``, ``all-reduce``), how many collectives
the program has, how many of them sit inside a ``while`` body (the layer
loops of a ZeRO-3 step) and, of those, how many the compiler made
asynchronous — ``fused`` into an ``async_collective_fusion`` chain that runs
beside compute fusions (one chain, however many instructions carry its
``chain_id``), ``started`` as a ``-start`` / ``-done`` pair — and how many it
left ``plain``: a synchronous instruction in the body's own schedule, which
holds the core while it runs.  A plain one that carries
``async_collective_name`` was made asynchronous and turned back (nothing
stood between its start and its done); a trace shows it as time no other
operation covers (``collective_exposed``).  Collective permutes — the ring
steps of a windowed einsum — run inside matmul fusions and are not counted.

The text is what ``jax.jit(f).lower(...).compile().as_text()`` returns,
``is_scheduled=true``: on a TPU the chip's schedule, on a CPU mesh what the
CPU compiler emitted (no chains: everything in a loop is plain).  The
engine keeps the count of each compiled step as ``engine.collectives[name]``
(``runtime/engine.py``); ``docs/zero.md`` shows how to read a step's text
for a described chip from a machine without one.  The text is cut into
computations and the loops' bodies are walked by the package's one reader of
compiled text, ``telemetry/hlo_text.py``.
"""

from __future__ import annotations

import re
from typing import Dict

from ...telemetry import hlo_text

KINDS = ("all-gather", "reduce-scatter", "all-to-all", "all-reduce")
FIELDS = ("total", "in_loop", "fused", "started", "plain")

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? (" + "|".join(KINDS) +
    r")(-start)?\(")
_CHAIN = re.compile(r'chain_id="?(\d+)')


def count(text: str) -> Dict[str, Dict[str, int]]:
    """-> ``{kind: {total, in_loop, fused, started, plain}}`` for the four
    ``KINDS``; ``in_loop = fused + started + plain``."""
    comps = hlo_text.computations(text)
    # every computation a while body reaches: its fusions, its nested calls
    in_loop = hlo_text.reached_from_loops(comps)
    out = {kind: dict.fromkeys(FIELDS, 0) for kind in KINDS}
    chains = set()
    for name, lines in comps.items():
        for line in lines:
            found = _INSTRUCTION.match(line)
            if not found:
                continue
            kind, started = found.group(1), bool(found.group(2))
            if kind == "all-reduce" and name.startswith("all-reduce-scatter"):
                kind = "reduce-scatter"   # the TPU's fused form of one
            chain = _CHAIN.search(line)
            if chain:
                # one chain: start, steps and done carry the same id
                key = (kind, chain.group(1), name in in_loop)
                if key in chains:
                    continue
                chains.add(key)
            row = out[kind]
            row["total"] += 1
            if name not in in_loop:
                continue
            row["in_loop"] += 1
            if chain:
                row["fused"] += 1
            elif started:
                row["started"] += 1
            else:
                row["plain"] += 1
    return out


def line(counts: Dict[str, Dict[str, int]]) -> str:
    """One log line of a count: the kinds the program has."""
    said = [f"{kind} {row['total']} ({row['in_loop']} in a loop: "
            f"{row['fused']} fused, {row['started']} started, "
            f"{row['plain']} plain)"
            for kind, row in counts.items() if row["total"]]
    return "; ".join(said) or "none"
