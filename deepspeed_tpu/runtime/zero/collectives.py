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
for a described chip from a machine without one.
"""

from __future__ import annotations

import re
from typing import Dict, List

KINDS = ("all-gather", "reduce-scatter", "all-to-all", "all-reduce")
FIELDS = ("total", "in_loop", "fused", "started", "plain")

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? (" + "|".join(KINDS) +
    r")(-start)?\(")
_CALLED = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_BODY = re.compile(r"\bbody=%?([\w.\-]+)")
_CHAIN = re.compile(r'chain_id="?(\d+)')


def _computations(text: str) -> Dict[str, List[str]]:
    found: Dict[str, List[str]] = {}
    lines = None
    for line in text.splitlines():
        start = _COMPUTATION.match(line)
        if start:
            lines = found.setdefault(start.group(1), [])
        elif line.startswith("}"):
            lines = None
        elif lines is not None:
            lines.append(line)
    return found


def count(text: str) -> Dict[str, Dict[str, int]]:
    """-> ``{kind: {total, in_loop, fused, started, plain}}`` for the four
    ``KINDS``; ``in_loop = fused + started + plain``."""
    comps = _computations(text)
    called = {name: {c for line in lines for c in _CALLED.findall(line)}
              for name, lines in comps.items()}
    # every computation a while body reaches: its fusions, its nested calls
    in_loop, stack = set(), [b for lines in comps.values()
                             for line in lines for b in _BODY.findall(line)]
    while stack:
        name = stack.pop()
        if name not in in_loop:
            in_loop.add(name)
            stack.extend(called.get(name, ()))
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
