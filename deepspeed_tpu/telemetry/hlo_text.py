"""The package's ONE reader of compiled HLO text.

``compiled.as_text()`` of a jitted function (``is_scheduled=true``: on a TPU
the chip's schedule) is a list of computations, each a list of instructions

    [ROOT] %name = <type> opcode(<operands>), attr=..., metadata={op_name="..."}

Three things are read from it, all here:

* :func:`computations` / :func:`callees` / :func:`reached_from_loops` — the
  text cut into computations, who calls whom, and what a ``while`` body
  reaches (``runtime/zero/collectives.py count`` walks these);
* :func:`parse` — one instruction line into its name, result type, opcode,
  operands and attributes;
* :func:`scope_table` — per instruction OF THE SCHEDULE (a fusion is one, a
  Pallas call one, a ``while`` one whose seconds are the loop's own;
  ``parameter`` / ``bitcast`` / ``tuple`` take no time and are left out) its serial name as a profile prints it (``fusion.123``),
  opcode, scope and pass (``telemetry/scopes.py normalise``), the bytes at
  its boundary, the matmul flops inside it and how often a call of the
  program runs it; and the same summed per scope and pass.

**A fusion's scope.**  Of its matmul (``convolution`` on a TPU, ``dot``
elsewhere) if it has one — a norm fused into the product it feeds is that
product's; else the one scope all its fused instructions share; else their
longest common prefix if that is an entry of the vocabulary; else the scope
that holds most of its bytes, and the row is marked ``mixed``.

**Bytes.**  Operands plus results of the instruction as the schedule sees
it, with what an in-place or sliced access TOUCHES in place of the buffer:
an operand a fusion only ``dynamic-slice``s / ``slice``s / ``gather``s from
counts the slices, the buffer of a ``dynamic-update-slice`` / ``scatter``
counts nothing and its result the update.  Arrays the compiler keeps outside
HBM (a layout's ``S(n)``) are not HBM traffic and are summed apart
(``onchip_bytes``).  A Pallas call's operands are handed over WHOLE (a paged
kernel gets the pool and reads the blocks its tables name), so its bytes —
less the pairs it aliases — are an upper bound and are summed apart too
(``kernel_bytes``).

**Trips.**  The product of the trip counts of the loops that enclose an
instruction: ``known_trip_count`` where the text has it, else the constant a
loop's condition compares its counter with (the TPU's scheduled text drops
the former); a loop that shows neither counts once and is listed in
``unknown_trips``.  Both branches of a ``conditional`` count.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterable, List, Optional, Set, Tuple

from . import scopes

__all__ = ["computations", "callees", "reached_from_loops", "parse",
           "array_bytes", "scope_table", "module_name", "FREE"]

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$")
_ENTRY = re.compile(r"^ENTRY %?([\w.\-]+) ", re.M)
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_LINE = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"([a-z][\w\-]*)\(")
_CALLED = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_BODY = re.compile(r"\bbody=%?([\w.\-]+)")
_ARRAY = re.compile(r"\b([a-z]+\d*(?:e\d+m\d+\w*)?)\[([\d,]*)\](\{[^}]*\})?")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_TRIPS = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_ALIASED = re.compile(r"\{(\d*)\}: \((\d+),")
_ITEMSIZE = {"pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2,
             "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
             "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}

#: opcodes a profile shows no event for: a view, a tuple's plumbing
FREE = frozenset((
    "parameter", "constant", "bitcast", "tuple", "get-tuple-element",
    "after-all", "partition-id", "replica-id", "opt-barrier"))
#: opcodes whose event holds no traffic of its own: a container (its work
#: is its body's; its SELF time is the loop's own)
_NO_BYTES = ("while", "conditional", "call")
MOSAIC = "tpu_custom_call"
#: custom calls that are XLA's own plumbing and move nothing: an allocation,
#: a view, a promise about indices (a profile shows them at ~0 seconds)
_PLUMBING = frozenset(("AllocateBuffer", "ConcatBitcast", "SliceToDynamic",
                       "AssumeGatherIndicesInBound",
                       "GatherScatterIndicesBitpacked"))
_MATMUL = ("convolution", "dot")


# ------------------------------------------------------------- computations
def module_name(text: str) -> Optional[str]:
    """``HloModule jit_decode_step, ...`` -> ``jit_decode_step``: the name a
    profile's ``XLA Modules`` line gives the program."""
    found = _MODULE.match(text)
    return found.group(1) if found else None


def computations(text: str) -> Dict[str, List[str]]:
    """computation name -> its instruction lines, in the text's order (the
    schedule's)."""
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


def _calls_of(line: str) -> List[str]:
    names = _CALLED.findall(line)
    for group in _BRANCHES.findall(line):
        names += [n.strip().lstrip("%") for n in group.split(",") if n.strip()]
    return names


def callees(comps: Dict[str, List[str]]) -> Dict[str, Set[str]]:
    """computation -> the computations its instructions name (a fusion's,
    a reducer's, a loop's body and condition, a branch's)."""
    return {name: {c for line in lines for c in _calls_of(line)}
            for name, lines in comps.items()}


def reached_from_loops(comps: Dict[str, List[str]]) -> Set[str]:
    """Every computation a ``while`` body reaches: the body, its fusions,
    its nested calls."""
    called = callees(comps)
    seen: Set[str] = set()
    stack = [b for lines in comps.values()
             for line in lines for b in _BODY.findall(line)]
    while stack:
        name = stack.pop()
        if name not in seen:
            seen.add(name)
            stack.extend(called.get(name, ()))
    return seen


# -------------------------------------------------------------- instructions
def _balanced(text: str, at: int) -> int:
    """Index just past the parenthesis that closes the one at ``at``."""
    depth = 0
    for i in range(at, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if not depth:
                return i + 1
    return len(text)


def _top_level(text: str) -> List[str]:
    """``text`` split at the commas outside any bracket."""
    out, depth, cur = [], 0, []
    for ch in text:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and not depth:
            out.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    last = "".join(cur).strip()
    return out + [last] if last else out


def parse(line: str) -> Optional[Dict[str, Any]]:
    """One instruction line -> ``{name, root, type, opcode, operands,
    attrs}`` (``operands``: the names; ``attrs``: the text after the operand
    list), or None for a line that is none."""
    found = _LINE.match(line)
    if not found:
        return None
    root, name, rest = found.groups()
    end = _balanced(rest, 0) if rest.startswith("(") else \
        (rest.find(" ") if " " in rest else len(rest))
    kind, after = rest[:end], rest[end:].lstrip()
    op = _OPCODE.match(after)
    if not op:
        return None
    close = _balanced(after, op.end() - 1)
    # (every fifth operand is printed behind a ``/*index=5*/`` comment)
    operands = [part.split()[-1].rpartition("*/")[2].lstrip("%")
                for part in _top_level(after[op.end():close - 1]) if part]
    return {"name": name, "root": bool(root), "type": kind,
            "opcode": op.group(1), "operands": operands,
            "attrs": after[close:]}


def _arrays(kind: str) -> List[Tuple[int, bool]]:
    """(bytes, kept outside HBM) of every array of a type's text."""
    out = []
    for dtype, dims, layout in _ARRAY.findall(kind):
        if dtype not in _ITEMSIZE and not dtype.startswith("f8"):
            continue
        n = 1
        for d in dims.split(","):
            n *= int(d) if d else 1
        out.append((n * _ITEMSIZE.get(dtype, 1),
                    bool(layout and re.search(r"S\([1-9]", layout))))
    return out


def array_bytes(kind: str) -> Tuple[int, int]:
    """``(bytes in HBM, bytes the layout keeps outside it)`` of a type."""
    arrays = _arrays(kind)
    return (sum(b for b, on in arrays if not on),
            sum(b for b, on in arrays if on))


def _dims(kind: str) -> List[int]:
    found = _ARRAY.search(kind)
    return [int(d) for d in found.group(2).split(",") if d] if found else []


def _matmul_flops(inst: Dict[str, Any], types: Dict[str, str]) -> int:
    """2 x the multiply-adds of a ``convolution`` / ``dot``."""
    attrs = inst["attrs"]
    lhs = _dims(types.get(inst["operands"][0], "")) \
        if inst["operands"] else []
    rhs = _dims(types.get(inst["operands"][1], "")) \
        if len(inst["operands"]) > 1 else []
    result = _dims(inst["type"])
    if inst["opcode"] == "dot":
        found = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", attrs)
        macs = 1
        for d in result:
            macs *= d
        for i in (found.group(1).split(",") if found else ()):
            if i and int(i) < len(lhs):
                macs *= lhs[int(i)]
        return 2 * macs
    labels = re.search(r"dim_labels=(\w+)_(\w+)->(\w+)", attrs)
    if not labels or len(labels.group(2)) != len(rhs) \
            or len(labels.group(1)) != len(lhs) \
            or len(labels.group(3)) != len(result):
        return 0
    l_lab, r_lab, o_lab = labels.groups()
    window = re.search(r"window=\{([^}]*)\}", attrs)
    fields = {k: v.split("x") for k, v in (
        f.split("=", 1) for f in window.group(1).split())} if window else {}

    def field(name, at, default):
        got = fields.get(name, ())
        return got[at] if at < len(got) else default

    macs = 1
    for label, size in zip(o_lab, result):
        if not label.isdigit():          # batch and output features
            macs *= size
    for label, size in zip(r_lab, rhs):
        if label == "i":
            macs *= size
    spatial = sorted(c for c in r_lab if c.isdigit())
    for at, label in enumerate(spatial):
        macs *= _valid_taps(
            lhs[l_lab.index(label)], result[o_lab.index(label)],
            rhs[r_lab.index(label)], int(field("stride", at, 1)),
            int(field("pad", at, "0_0").split("_")[0]),
            int(field("lhs_dilate", at, 1)), int(field("rhs_dilate", at, 1)))
    return 2 * macs


def _valid_taps(n_in: int, n_out: int, taps: int, stride: int, pad_lo: int,
                base: int, dilate: int) -> int:
    """(output position, kernel tap) pairs of one spatial dimension that
    meet an element of the input — not padding, not a hole of its base
    dilation.  The TPU prints a batched product as a convolution whose batch
    dimensions are spatial ones with ``lhs_dilate`` = the window's size:
    every output position then meets ONE tap, and the pairs are the batch."""
    if n_out * taps > 4_000_000:
        return n_out * taps              # (too long to walk: an upper bound)
    last = (n_in - 1) * base
    pairs = 0
    for o in range(n_out):
        first = o * stride - pad_lo
        for k in range(taps):
            at = first + k * dilate
            if 0 <= at <= last and at % base == 0:
                pairs += 1
    return pairs


class _Computation:
    """A computation's instructions by name, with who uses whom."""

    def __init__(self, lines: Iterable[str]):
        self.order: List[Dict[str, Any]] = []
        self.by_name: Dict[str, Dict[str, Any]] = {}
        self.users: Dict[str, List[Dict[str, Any]]] = {}
        for line in lines:
            inst = parse(line)
            if inst is None:
                continue
            self.order.append(inst)
            self.by_name[inst["name"]] = inst
            for o in inst["operands"]:
                self.users.setdefault(o, []).append(inst)
        self.types = {i["name"]: i["type"] for i in self.order}
        self.root = next((i for i in self.order if i["root"]),
                         self.order[-1] if self.order else None)

    def through_views(self, name: str) -> List[Dict[str, Any]]:
        """The users of ``name``, looking through views of it."""
        out, stack = [], [name]
        while stack:
            for user in self.users.get(stack.pop(), ()):
                if user["opcode"] == "bitcast":
                    stack.append(user["name"])
                else:
                    out.append(user)
        return out

    def source(self, name: str) -> Optional[Dict[str, Any]]:
        """``name`` itself, or what it is a bitcast of."""
        inst = self.by_name.get(name)
        while inst is not None and inst["opcode"] == "bitcast" \
                and inst["operands"]:
            inst = self.by_name.get(inst["operands"][0])
        return inst


_SLICERS = ("dynamic-slice", "slice", "gather")
_UPDATERS = ("dynamic-update-slice", "scatter")


def _parameters(fused: _Computation) -> Dict[int, Dict[str, Any]]:
    return {int(p["operands"][0]): p for p in fused.order
            if p["opcode"] == "parameter" and p["operands"]
            and p["operands"][0].isdigit()}


def _touched(fused: _Computation, param: Dict[str, Any],
             comps: Dict[str, "_Computation"]) -> Optional[int]:
    """HBM bytes a fused computation touches of ``param`` where every use
    of it is a slice or an in-place update's buffer (a nested fusion's use
    is looked into); None: all of it."""
    users = fused.through_views(param["name"])
    if fused.root is not None \
            and fused.source(fused.root["name"]) is param:
        return None                      # handed on whole (a view of it)
    if not users:
        return 0
    total = 0
    for user in users:
        if user["opcode"] == "fusion":
            called = _CALLED.search(user["attrs"])
            nested = comps.get(called.group(1)) if called else None
            if nested is None:
                return None
            inner = _parameters(nested)
            for j, name in enumerate(user["operands"]):
                if fused.source(name) is param:
                    got = _touched(nested, inner[j], comps) \
                        if j in inner else None
                    if got is None:
                        return None
                    total += got
            continue
        first = fused.source(user["operands"][0]) if user["operands"] \
            else None
        if first is not param:
            return None
        if user["opcode"] in _SLICERS:
            total += array_bytes(user["type"])[0]
        elif user["opcode"] not in _UPDATERS:
            return None                  # an update's buffer: nothing read
    return total


def _written(fused: _Computation, inst: Optional[Dict[str, Any]]
             ) -> Optional[int]:
    """HBM bytes a fused computation's result writes where it is an
    in-place update (or a tuple of them); None: all of it."""
    if inst is None:
        return None
    inst = fused.source(inst["name"]) or inst
    if inst["opcode"] == "tuple":
        parts = [_written(fused, fused.by_name.get(o))
                 for o in inst["operands"]]
        if all(p is None for p in parts):
            return None
        return sum(array_bytes(fused.types[o])[0] if p is None else p
                   for o, p in zip(inst["operands"], parts))
    if inst["opcode"] in _UPDATERS:
        update = inst["operands"][1 if inst["opcode"].startswith("dynamic")
                                  else 2]
        return array_bytes(fused.types.get(update, ""))[0]
    return None


def _boundary(inst: Dict[str, Any], comp: _Computation,
              fused: Optional[_Computation],
              comps: Dict[str, _Computation]) -> Tuple[int, int]:
    """``(HBM bytes, bytes outside HBM)`` at an instruction's boundary."""
    hbm, on = 0, 0
    params = _parameters(fused) if fused is not None else {}
    for i, name in enumerate(inst["operands"]):
        kind = comp.types.get(name, "")
        full, outside = array_bytes(kind)
        touched = _touched(fused, params[i], comps) \
            if fused is not None and i in params and full else None
        hbm += full if touched is None else min(touched, full)
        on += outside
    full, outside = array_bytes(inst["type"])
    written = _written(fused, fused.root) if fused is not None and full \
        else None
    return hbm + (full if written is None else min(written, full)), \
        on + outside


def _bare(inst: Dict[str, Any], comp: _Computation,
          comps: Dict[str, _Computation]) -> Tuple[int, int]:
    """The boundary of an instruction that is no fusion: a bare slice or
    update touches what a fused one would."""
    if inst["opcode"] in _SLICERS:
        hbm, on = array_bytes(inst["type"])
        return 2 * hbm, 2 * on
    if inst["opcode"] in _UPDATERS:
        update = inst["operands"][1 if inst["opcode"].startswith("dynamic")
                                  else 2]
        hbm, on = array_bytes(comp.types.get(update, ""))
        return 2 * hbm, 2 * on
    if inst["opcode"] in _NO_BYTES or inst["opcode"].endswith("-start"):
        return 0, 0
    if inst["opcode"].endswith("-done"):
        # the seconds of an asynchronous operation are its wait's: so are
        # its bytes (a copy reads what it writes)
        hbm, on = array_bytes(inst["type"])
        n = 2 if inst["opcode"] == "copy-done" else 1
        return n * hbm, n * on
    return _boundary(inst, comp, None, comps)


def _kernel(inst: Dict[str, Any], comp: _Computation) -> int:
    """A Pallas call's operands and results, less the pairs it aliases."""
    pairs = _ALIASED.findall(inst["attrs"])
    operands = {int(i) for _, i in pairs}
    results = {int(o or 0) for o, _ in pairs}
    total = sum(sum(array_bytes(comp.types.get(name, "")))
                for i, name in enumerate(inst["operands"])
                if i not in operands)
    return total + sum(b for i, (b, _) in enumerate(_arrays(inst["type"]))
                       if i not in results)


def _fused_scope(fused: _Computation, all_comps: Dict[str, _Computation],
                 cache: Dict[str, Tuple[str, str]]
                 ) -> Tuple[str, str, bool, int]:
    """(scope, pass, mixed, matmul flops) of a fused computation."""
    weight: Dict[Tuple[str, str], int] = {}
    flops = 0
    hero: Optional[Tuple[str, str]] = None
    hero_flops = -1
    stack = [fused]
    while stack:
        comp = stack.pop()
        for inst in comp.order:
            if inst["opcode"] in ("parameter", "constant", "tuple",
                                  "get-tuple-element"):
                continue
            inner = _CALLED.search(inst["attrs"]) \
                if inst["opcode"] == "fusion" else None
            if inner and inner.group(1) in all_comps:
                stack.append(all_comps[inner.group(1)])
                continue
            name = _OP_NAME.search(inst["attrs"])
            if not name:
                continue
            key = cache.get(name.group(1))
            if key is None:
                key = cache[name.group(1)] = scopes.normalise(name.group(1))
            if inst["opcode"] in _MATMUL:
                got = _matmul_flops(inst, comp.types)
                flops += got
                if got > hero_flops:
                    hero, hero_flops = key, got
            weight[key] = weight.get(key, 0) + sum(array_bytes(inst["type"]))
    if hero is not None:
        return hero[0], hero[1], False, flops
    if not weight:
        return scopes.UNSCOPED, "fwd", False, flops
    heaviest = max(weight, key=lambda k: (weight[k], k))
    names = {k[0] for k in weight}
    if len(names) == 1:
        return heaviest[0], heaviest[1], False, flops
    common = scopes.common_scope(names)
    if common is not None:
        return common, heaviest[1], False, flops
    return heaviest[0], heaviest[1], True, flops


def _loop_trips(inst: Dict[str, Any], comps: Dict[str, _Computation]
                ) -> Optional[int]:
    known = _TRIPS.search(inst["attrs"])
    if known:
        return int(known.group(1))
    cond = re.search(r"condition=%?([\w.\-]+)", inst["attrs"])
    comp = comps.get(cond.group(1)) if cond else None
    root = comp.root if comp is not None else None
    if root is None or root["opcode"] != "compare" \
            or "direction=LT" not in root["attrs"]:
        return None
    for name in root["operands"]:
        bound = comp.source(name)
        if bound is not None and bound["opcode"] == "constant" \
                and bound["operands"] and bound["operands"][0].isdigit():
            return int(bound["operands"][0])
    return None


_MOVES = ("copy", "copy-start", "copy-done")


def _inherit(instructions: Dict[str, Dict[str, Any]],
             comps: Dict[str, _Computation]) -> None:
    """A copy the COMPILER put in (an operand moved into fast memory ahead of
    its use, a result moved out) carries no name stack of the program: it
    takes the scope of the first scheduled instruction that reads it — else
    of the one that made what it moves — and is marked ``inherited``."""
    for comp in comps.values():
        for inst in comp.order:
            row = instructions.get(inst["name"])
            if row is None or inst["opcode"] not in _MOVES \
                    or row["scope"] != scopes.UNSCOPED:
                continue
            seen, stack, found = set(), [inst["name"]], None
            while stack and found is None:
                for user in comp.users.get(stack.pop(), ()):
                    got = instructions.get(user["name"])
                    if got is not None and got["scope"] != scopes.UNSCOPED:
                        found = got
                        break
                    if user["name"] not in seen and (
                            got is None or user["opcode"] in _MOVES):
                        seen.add(user["name"])
                        stack.append(user["name"])
            source = comp.source(inst["operands"][0]) \
                if inst["operands"] else None
            while found is None and source is not None:
                got = instructions.get(source["name"])
                if got is not None and got["scope"] != scopes.UNSCOPED:
                    found = got
                elif source["opcode"] in _MOVES + ("get-tuple-element",) \
                        and source["operands"]:
                    source = comp.source(source["operands"][0])
                    continue
                break
            if found is not None:
                row.update(scope=found["scope"], inherited=True)
                row["pass"] = found["pass"]


# --------------------------------------------------------------- the table
def scope_table(text: str) -> Dict[str, Any]:
    """The scheduled text of ONE compiled program ->

    ``module``         the program's name on a profile's ``XLA Modules`` line
    ``instructions``   serial name -> ``{opcode, type, scope, pass, mixed, bytes,
                       onchip_bytes, kernel_bytes, flops, trips, kernel}``
                       for every instruction of the schedule that is work
                       (``kernel``: a Pallas call's name without its serial)
    ``scopes``         ``[{scope, pass, instructions, bytes, onchip_bytes,
                       kernel_bytes, flops, mixed_bytes, kernels: {name:
                       calls}}, ...]`` summed over the schedule with each
                       instruction's ``trips``: what ONE call of the program
                       does, most bytes first; :data:`scopes.UNSCOPED` is a
                       row like any other
    ``unknown_trips``  the loops whose trip count the text does not show
    """
    comps = {name: _Computation(lines)
             for name, lines in computations(text).items()}
    entry = _ENTRY.search(text)
    cache: Dict[str, Tuple[str, str]] = {}
    instructions: Dict[str, Dict[str, Any]] = {}
    unknown: List[str] = []

    def walk(comp_name: str, trips: int) -> None:
        comp = comps.get(comp_name)
        if comp is None:
            return
        for inst in comp.order:
            op, attrs = inst["opcode"], inst["attrs"]
            if op == "while":
                n = _loop_trips(inst, comps)
                if n is None:
                    unknown.append(inst["name"])
                body = _BODY.search(attrs)
                if body:
                    walk(body.group(1), trips * (n or 1))
            elif op in ("conditional", "call"):
                for name in _calls_of(attrs):
                    walk(name, trips)
            if op in FREE:
                continue
            row = instructions.get(inst["name"])
            if row is not None:          # a body two loops share
                row["trips"] += trips
                continue
            name = _OP_NAME.search(attrs)
            scope, which = scopes.normalise(name.group(1) if name else None)
            mixed, flops, kernel = False, 0, None
            hbm = on = kernel_bytes = 0
            target = _TARGET.search(attrs) if op == "custom-call" else None
            called = _CALLED.search(attrs)
            fused = comps.get(called.group(1)) \
                if op == "fusion" and called else None
            if target is not None and target.group(1) == MOSAIC:
                kernel = re.sub(r"[.\d]+$", "", inst["name"]) or inst["name"]
                kernel_bytes = _kernel(inst, comp)
            elif fused is not None:
                scope, which, mixed, flops = _fused_scope(fused, comps, cache)
                hbm, on = _boundary(inst, comp, fused, comps)
            elif target is not None and target.group(1) in _PLUMBING:
                pass
            else:
                if op in _MATMUL:
                    flops = _matmul_flops(inst, comp.types)
                hbm, on = _bare(inst, comp, comps)
            instructions[inst["name"]] = {
                "opcode": op, "type": inst["type"], "scope": scope,
                "pass": which, "mixed": mixed,
                "bytes": hbm, "onchip_bytes": on,
                "kernel_bytes": kernel_bytes, "flops": flops,
                "trips": trips, "kernel": kernel}

    if entry:
        walk(entry.group(1), 1)
    _inherit(instructions, comps)
    rows: Dict[Tuple[str, str], Dict[str, Any]] = {}
    for inst in instructions.values():
        row = rows.setdefault((inst["scope"], inst["pass"]), {
            "scope": inst["scope"], "pass": inst["pass"], "instructions": 0,
            "bytes": 0, "onchip_bytes": 0, "kernel_bytes": 0, "flops": 0,
            "mixed_bytes": 0, "kernels": {}})
        n = inst["trips"]
        row["instructions"] += n
        for key in ("bytes", "onchip_bytes", "kernel_bytes", "flops"):
            row[key] += n * inst[key]
        if inst["mixed"]:
            row["mixed_bytes"] += n * inst["bytes"]
        if inst["kernel"]:
            row["kernels"][inst["kernel"]] = \
                row["kernels"].get(inst["kernel"], 0) + n
    return {"module": module_name(text), "instructions": instructions,
            "scopes": sorted(rows.values(),
                             key=lambda r: -(r["bytes"] + r["kernel_bytes"])),
            "unknown_trips": unknown}
