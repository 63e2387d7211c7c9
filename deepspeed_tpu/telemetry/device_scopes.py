"""The device's BUSY time, by the program's own scopes — from one profile.

    python -m deepspeed_tpu.telemetry.device_scopes <profile_dir-or-xplane.pb> [--tables tables.json]

``idle_gaps.py`` says where the device was idle, by what the host was doing;
this is its sibling for a busy chip: for each program inside the window
(``jit_decode_step``, ``jit_prefill``, ``jit_train_step`` ...) the SELF
seconds (a ``while`` encloses its body) of every ``XLA Ops`` event, joined by
the instruction's serial name to the compiled program's scope table
(``telemetry/hlo_text.py scope_table``; the TPU runtime writes no ``op_name``
into an event, ``telemetry/profile.py``) and summed by scope and pass.
Beside each scope the bytes and matmul flops its instructions moved — the
table's a call, once for every event — hence achieved GB/s and TFLOP/s
against the chip's peaks: a scope near 819 GB/s or 197 TFLOP/s (one v5e chip)
sits at a floor, one far from both is latency, launch overhead or on-chip
work, and the next step is the instruction list under it.  ``unscoped``
(instructions under no entry of ``telemetry/scopes.py VOCABULARY``) and
``not_in_table`` (events no table names) are printed, never folded away.

The tables come from the process that took the profile:
``trace.kept("programs").tables()`` (or ``engine.program_table(name)``),
dumped as JSON for ``--tables``; without tables every second is
``not_in_table`` and the reader still gives the seconds by instruction.
Two programs of one module name (the prefill ladder's rungs are both
``jit_prefill``) are told apart by the result types of their instructions.

What it cannot see: the time INSIDE a Pallas kernel (one event, one row,
under ``kernels``), how a ``mixed`` fusion's seconds split between its scopes
(``mixed_s`` says how many there are), and bytes a Pallas call really reads
of the operands it is handed whole (``kernel_bytes`` is an upper bound and
takes no part in GB/s).
"""

from __future__ import annotations

import bisect
import json
import re
import sys
from typing import Any, Dict, List, Optional, Tuple, Union

from . import scopes
from .profile import Profile, load, self_times, union, window_of

__all__ = ["by_scope", "render", "NOT_IN_TABLE", "PEAKS"]

NOT_IN_TABLE = "not_in_table"
#: (HBM bytes/s, bf16 flop/s) of one chip, by a substring of its
#: ``device_kind`` (Google Cloud documentation, "TPU v5e": 819 GB/s, 197
#: TFLOP/s)
PEAKS = {"v5 lite": (819e9, 197e12), "v5e": (819e9, 197e12)}


def _module(name: str) -> str:
    """``jit_decode_step(1234567890)`` -> ``jit_decode_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def _match(seen: Dict[str, str], tables: List[Dict[str, Any]]
           ) -> Optional[Dict[str, Any]]:
    """Of ``tables`` (one module name), the one whose instructions have the
    serial names AND result types this execution's events show."""
    if len(tables) <= 1:
        return tables[0] if tables else None
    return max(tables, key=lambda table: sum(
        1 for name, kind in seen.items()
        if table["instructions"].get(name, {}).get("type") == kind))


def by_scope(profile: Union[str, Profile],
             tables: Optional[Dict[str, Dict[str, Any]]] = None,
             top: int = 5) -> Dict[str, Any]:
    """``profile`` (a directory, an ``.xplane.pb`` or a loaded
    :class:`~deepspeed_tpu.telemetry.profile.Profile`) and ``tables``
    (``{program: scope table}``) ->

    ``window_s`` / ``busy_s``  the window (``profile.window_of``) and the
                               union of the device's operations inside it
    ``modules``  ``{module: {program, calls, busy_s, scoped_s, unscoped_s,
                 not_in_table_s, mixed_s, rows, not_in_table}}`` where
                 ``rows`` is ``[{scope, pass, seconds, events, bytes,
                 onchip_bytes, kernel_bytes, flops, gb_s, tflop_s, mixed_s,
                 kernels: {name: seconds}, top: [[serial, seconds], ...]},
                 ...]`` most seconds first, and ``not_in_table`` the
                 heaviest instructions no table names.  ``busy_s`` is the
                 union of the module's own events, ``scoped_s + unscoped_s +
                 not_in_table_s`` the sum of their SELF seconds: the two
                 agree unless events overlap that do not nest.
    """
    if isinstance(profile, str):
        profile = load(profile)
    tables = tables or {}
    ops, names, kinds = profile.ops, profile.names, profile.kinds
    lo, hi = window_of(ops, profile.spans)
    mods = sorted((s, e, name) for name, s, e in profile.modules)
    starts = [m[0] for m in mods]
    by_module_tables: Dict[str, List[Dict[str, Any]]] = {}
    for table in tables.values():
        by_module_tables.setdefault(table.get("module") or "", []).append(
            table)

    # which module execution an event lies in, and what each module showed
    owner: List[str] = []
    seen: Dict[str, Dict[str, str]] = {}
    calls: Dict[str, int] = {}
    for s, e, name in mods:
        if lo <= s < hi:
            calls[name] = calls.get(name, 0) + 1
    for i, (s, e) in enumerate(ops):
        at = bisect.bisect_right(starts, s) - 1
        name = mods[at][2] if at >= 0 and s < mods[at][1] else ""
        owner.append(name)
        seen.setdefault(name, {}).setdefault(names[i], kinds[i])
    matched = {name: _match(shown, by_module_tables.get(_module(name), []))
               for name, shown in seen.items()}

    out: Dict[str, Dict[str, Any]] = {}
    intervals: Dict[str, List[Tuple[float, float]]] = {}
    for i, own in enumerate(self_times(ops)):
        s, e = ops[i]
        inside = min(e, hi) - max(s, lo)
        if inside <= 0:
            continue
        ns = min(own, inside)
        module = owner[i]
        mod = out.setdefault(module, {"rows": {}, "missing": {}})
        intervals.setdefault(module, []).append((max(s, lo), min(e, hi)))
        table = matched.get(module)
        inst = table["instructions"].get(names[i]) if table else None
        if inst is None:
            mod["missing"][names[i]] = mod["missing"].get(names[i], 0.0) + ns
            continue
        row = mod["rows"].setdefault((inst["scope"], inst["pass"]), {
            "scope": inst["scope"], "pass": inst["pass"], "seconds": 0.0,
            "events": 0, "bytes": 0, "onchip_bytes": 0, "kernel_bytes": 0,
            "flops": 0, "mixed_s": 0.0, "kernels": {}, "by": {}})
        row["seconds"] += ns
        row["events"] += 1
        for key in ("bytes", "onchip_bytes", "kernel_bytes", "flops"):
            row[key] += inst[key]
        if inst["mixed"]:
            row["mixed_s"] += ns
        if inst["kernel"]:
            row["kernels"][inst["kernel"]] = \
                row["kernels"].get(inst["kernel"], 0.0) + ns
        row["by"][names[i]] = row["by"].get(names[i], 0.0) + ns

    modules: Dict[str, Any] = {}
    for module, mod in out.items():
        rows = []
        for row in sorted(mod["rows"].values(), key=lambda r: -r["seconds"]):
            sec = row["seconds"] * 1e-9
            ranked = sorted(row.pop("by").items(), key=lambda kv: -kv[1])
            rows.append({
                **row, "seconds": sec, "mixed_s": row["mixed_s"] * 1e-9,
                "kernels": {k: v * 1e-9 for k, v in row["kernels"].items()},
                "gb_s": row["bytes"] / sec * 1e-9 if sec else 0.0,
                "tflop_s": row["flops"] / sec * 1e-12 if sec else 0.0,
                "top": [[k, v * 1e-9] for k, v in ranked[:top]]})
        unscoped = sum(r["seconds"] for r in rows
                       if r["scope"] == scopes.UNSCOPED)
        missing = sorted(mod["missing"].items(), key=lambda kv: -kv[1])
        table = matched.get(module)
        modules[module or "(no module)"] = {
            "program": table.get("program") if table else None,
            "calls": calls.get(module, 0),
            "busy_s": sum(e - s for s, e in union(intervals[module])) * 1e-9,
            "scoped_s": sum(r["seconds"] for r in rows) - unscoped,
            "unscoped_s": unscoped,
            "not_in_table_s": sum(v for _, v in missing) * 1e-9,
            "mixed_s": sum(r["mixed_s"] for r in rows),
            "rows": rows,
            "not_in_table": [[k, v * 1e-9] for k, v in missing[:4 * top]]}
    busy = union((max(s, lo), min(e, hi)) for s, e in ops
                 if min(e, hi) > max(s, lo))
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "modules": dict(sorted(modules.items(),
                                   key=lambda kv: -kv[1]["busy_s"]))}


def render(result: Dict[str, Any],
           peaks: Tuple[float, float] = PEAKS["v5e"]) -> str:
    """The table :func:`main` prints."""
    hbm, mxu = peaks
    out = [f"window {result['window_s']:.3f} s, device busy "
           f"{result['busy_s']:.3f} s "
           f"({100 * result['busy_s'] / result['window_s']:.2f} %); "
           f"peaks {hbm * 1e-9:.0f} GB/s, {mxu * 1e-12:.0f} TFLOP/s"]
    for module, mod in result["modules"].items():
        total = mod["scoped_s"] + mod["unscoped_s"] + mod["not_in_table_s"]
        out.append(
            f"{module} (program {mod['program']}): {mod['calls']} calls, "
            f"busy {mod['busy_s']:.3f} s; by scope {total:.3f} s = scoped "
            f"{mod['scoped_s']:.3f} + unscoped {mod['unscoped_s']:.3f} + "
            f"not_in_table {mod['not_in_table_s']:.3f}; in mixed fusions "
            f"{mod['mixed_s']:.3f}")
        for r in mod["rows"]:
            kernels = "".join(f" {k} {v:.3f}" for k, v in
                              sorted(r["kernels"].items(),
                                     key=lambda kv: -kv[1]))
            out.append(
                f"  {r['scope']:<26} {r['pass']:<5} {r['seconds']:9.4f} s "
                f"{100 * r['seconds'] / max(mod['busy_s'], 1e-12):6.2f} %  "
                f"{r['gb_s']:7.1f} GB/s ({100 * r['gb_s'] * 1e9 / hbm:5.1f} %)"
                f"  {r['tflop_s']:6.2f} TFLOP/s "
                f"({100 * r['tflop_s'] * 1e12 / mxu:5.1f} %)"
                + (f"  kernels:{kernels}" if kernels else "")
                + (f"  mixed {r['mixed_s']:.3f} s" if r["mixed_s"] else ""))
            out.append("      " + ", ".join(f"{k} {v:.3f}"
                                            for k, v in r["top"]))
        if mod["not_in_table"]:
            out.append("  not in the table: " + ", ".join(
                f"{k} {v:.4f}" for k, v in mod["not_in_table"]))
    return "\n".join(out)


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="the device's busy time by scope, from a profile")
    ap.add_argument("path", help="profile directory or .xplane.pb")
    ap.add_argument("--tables", default=None,
                    help="JSON of {program: scope table} "
                         "(trace.kept('programs').tables())")
    ap.add_argument("--json", default=None, help="write the result there")
    args = ap.parse_args(argv)
    tables = None
    if args.tables:
        with open(args.tables) as f:
            tables = json.load(f)
    result = by_scope(args.path, tables)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f)
    print(render(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
