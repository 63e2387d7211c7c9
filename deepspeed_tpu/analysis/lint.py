"""graft-lint: rule-based static AST lint for TPU/JAX recompile and
host-sync hazards (``bin/graft-lint``).

The serving and training hot paths live and die by a handful of tracing
conventions that nothing in Python enforces: jitted bodies must never
materialize traced values on the host (a silent device sync per step),
never bake closure-captured shapes into a program (a retrace — or worse,
a stale shape — per new input), always donate the KV pool they update (a
full pool copy per step otherwise), and only ever name mesh axes that the
engines actually build.  This module turns each of those conventions into
a numbered, suppressible lint rule:

========  =============================================================
GL001     host-side materialization of a traced value inside a
          jit/shard_map body (``.item()`` / ``.tolist()`` /
          ``float()`` / ``int()`` / ``bool()`` / ``np.asarray`` on a
          traced argument) — forces a device sync and breaks tracing.
GL002     Python-scalar shape/string leakage into a jit body: f-strings
          or ``str()`` over traced values (concretization error at trace
          time), and ``.shape`` reads of arrays captured from an
          enclosing *non-jit* builder's arguments (the shape is baked at
          closure creation — a new input shape silently reuses it).
GL003     ``jax.jit`` of a pool/cache/state-updating function without a
          ``donate_argnums``/``donate_argnames`` decision — XLA keeps
          the input buffer alive and every step pays a full copy.  An
          explicit empty tuple counts as a decision (the serving engine
          passes ``donate_argnums=()`` on CPU where donation is
          ignored).
GL004     mesh-axis string literal that is not one of the axes the
          engines build (default: {tp, dp, pp, ep, sp, batch, model,
          data, pipe}) in a collective call or a ``PartitionSpec`` — a
          typo here raises only at trace time, on device, deep inside a
          compiled program.
GL005     traced-array comparison (or bare truthiness) as an ``if`` /
          ``while`` test inside a jit body — `TracerBoolConversionError`
          at best, silently trace-time-constant control flow at worst.
          ``is`` / ``is not`` (None checks) are static and exempt.
GL006     host timer call (``time.time()`` / ``time.perf_counter()`` /
          ``monotonic`` / ``*_ns`` / ``process_time`` variants) inside a
          jit/shard_map body — the Python body runs ONCE, at trace time,
          so the two stamps measure tracing (or nothing: both land in
          the same trace), never device execution.  Time around the
          compiled call after a sync instead (``utils/timer.py``,
          ``telemetry/``).
GL007     blocking device transfer (``jax.device_get`` /
          ``jax.block_until_ready`` / ``.block_until_ready()``) inside a
          host-side loop body outside a sanctioned transfer helper — a
          scheduler/driver loop that syncs per iteration serializes the
          device pipeline (the decode step cannot overlap the next
          iteration's host work).  Sanctioned helpers are functions
          whose (enclosing) name carries a transfer verb — ``demote``,
          ``promote``, ``swap``, ``sync``, ``prefetch`` — the documented
          commit points (e.g. the tiered-KV demotion helper's one
          ``device_get`` per swap batch, ``inference/serving.py``).
GL008     metric family registration outside the telemetry naming
          convention (``registry.counter/gauge/histogram`` with a
          literal name): counters must end in ``_total`` (the Prometheus
          monotone-counter convention scrapers reset-detect on), every
          family must carry a subsystem namespace prefix (``serving_`` /
          ``train_`` / ``inference_``, or the process's ``program_`` /
          ``compile_`` — the federated fleet registry
          stays greppable by subsystem), gauges/histograms must NOT end
          in ``_total``, and label keys must come from the documented
          closed set (``docs/observability.md``) — an ad-hoc label key
          is usually a per-request value about to become unbounded
          series cardinality.
GL012     per-iteration scalar device sync in a host scheduler loop:
          ``<jnp expr>.item()``, ``int()/float()/bool()`` over a
          ``jnp``/``jax``-rooted expression, or a ``jnp``-rooted call as
          an ``if``/``while`` test — each iteration round-trips ONE
          scalar to the host, so the loop runs at device-latency per
          token instead of dispatching ahead (what the serving engine's
          one call of lookahead avoids, ``docs/inference.md``).
          Batch the decision onto the device (``lax.while_loop`` with an
          on-device ``active`` mask) and read results back once at a
          sanctioned fence helper — GL007's transfer verbs plus
          ``harvest``/``settle`` (``ServingEngine._harvest``,
          ``._settle``).
          (GL009..GL011, the lock-discipline rules, live in
          ``analysis/concurrency.py``.)
GL013     silent exception swallow in fleet-path code (``serving/``,
          ``telemetry/``, ``inference/serving.py``): an ``except`` body
          that neither re-raises, nor references the caught exception
          (typed-error store, repr into a report), nor emits telemetry
          (a counter ``.inc()``, a timeline ``.instant()``/flow event)
          or a logger/warnings message.  The serving fleet's whole
          observability story (docs/observability.md) rests on "every
          swallowed failure leaves a trace" — a bare ``except: pass``
          here is an incident the flight recorder can never trigger on.
GL014     module-level RNG singleton (``random.*`` / ``np.random.*``
          calls on the process-global generators) in fleet-path code
          (same scope as GL013): global-stream draws are order-dependent
          across requests, so a crash replay / re-homed request can
          never reproduce the sampled stream — exactly the determinism
          the serving sampler's counter-based PRNG (``ops/sampling.py``,
          keyed by request seed + emission position) exists to provide.
          Seeded instances (``np.random.default_rng``, ``Generator``,
          ``SeedSequence``, ``RandomState``, ``random.Random``) are
          fine — the seed pins the stream to the owner, not the process.
========  =============================================================

Suppression: append ``# graft: noqa(GLxxx)`` (one or more codes,
comma-separated) to the offending line, with a short justification after
it; a bare ``# graft: noqa`` suppresses every rule on that line.  The
runner exits nonzero on any *unsuppressed* finding — CI wires
``bin/graft-lint deepspeed_tpu/`` as a device-free job.

A "jit body" is any function (a) decorated with ``jit``/``pjit``/
``shard_map``/``partial(jax.jit, ...)``, (b) referenced by name anywhere
inside the arguments of such a call — including through wrappers like
``jax.jit(sentry.wrap(step, "decode"), ...)`` — or (c) lexically nested
inside one.  Traced names are the body's own parameters plus those of
enclosing jit bodies (closures over tracers).

Everything here is stdlib-only on purpose: the CI lint job and
``bin/graft-lint`` run without jax installed.
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

#: axis names the engines actually build (parallel/topology.py builds the
#: short spelling, runtime/pipe builds the long one)
DEFAULT_MESH_AXES = frozenset(
    {"tp", "dp", "pp", "ep", "sp", "batch", "model", "data", "pipe"})

#: callables whose function-valued arguments become traced bodies
_JIT_WRAPPERS = frozenset({"jit", "pjit", "shard_map", "head_shard_map"})

#: collectives whose axis argument is an axis NAME (positional index 1 or
#: the ``axis_name=`` keyword)
_COLLECTIVES = frozenset({
    "psum", "pmean", "pmax", "pmin", "all_gather", "all_to_all",
    "ppermute", "pshuffle", "axis_index", "pswapaxes", "psum_scatter"})

#: attribute chains through these never leave trace-static land
_STATIC_ATTRS = frozenset({"shape", "ndim", "size", "dtype"})

#: parameter names that mark a jitted function as pool/cache-updating
_POOLISH_PARAMS = frozenset(
    {"cache", "dcache", "kv_cache", "pool", "kv_pool", "state"})

RULES: Dict[str, str] = {
    "GL001": "host-side materialization of a traced value in a jit body",
    "GL002": "shape/string leakage of traced or closure-captured arrays "
             "into a jit body",
    "GL003": "jax.jit of a pool/cache/state-updating function without a "
             "donate_argnums decision",
    "GL004": "mesh-axis string literal unknown to the engine meshes",
    "GL005": "traced-array comparison or truthiness as an if/while test "
             "in a jit body",
    "GL006": "host timer (time.time/perf_counter/...) in a jit body — "
             "measures trace time, not device execution",
    "GL007": "blocking device transfer (device_get/block_until_ready) in "
             "a host loop body outside a sanctioned transfer helper",
    "GL008": "metric family name or label key outside the telemetry "
             "naming convention (docs/observability.md)",
    "GL012": "per-iteration scalar device sync (.item()/int()/bool() or "
             "jnp truthiness test) in a host scheduler loop outside a "
             "sanctioned fence helper",
    "GL013": "except block in serving/telemetry fleet code swallows the "
             "exception without re-raise, caught-name use, or a "
             "telemetry/log emit",
    "GL014": "process-global RNG draw (random.*/np.random.* singleton) "
             "in serving/telemetry fleet code — order-dependent streams "
             "break replay/re-homing determinism; seed an instance",
}

#: GL008 — the documented metric naming convention: registry method
#: tails, family namespace prefixes, the closed label-key set, and the
#: registry-method keywords that are NOT labels
_METRIC_CTORS = frozenset({"counter", "gauge", "histogram"})
#: (``program_`` / ``compile_``: the PROCESS's families — what JAX builds
#: and what the compile cache answers belong to no engine,
#: ``telemetry/metrics.py process_registry``)
_METRIC_NAMESPACES = ("serving_", "train_", "inference_", "program_",
                      "compile_")
_METRIC_LABEL_KEYS = frozenset(
    {"replica", "direction", "timer", "slo_class", "slo", "phase",
     "lock", "tier", "mode", "cause", "shape"})
_METRIC_PARAM_KWARGS = frozenset({"help", "monitor_name", "buckets"})

#: substrings marking a function as a sanctioned blocking-transfer helper
#: for GL007/GL012 (the documented sync/swap commit points; "harvest" /
#: "settle" name the decode path's, ``ServingEngine._harvest`` /
#: ``._settle``, which take the results of the one call the scheduler
#: keeps in flight)
_SANCTIONED_XFER = ("demote", "promote", "swap", "sync", "prefetch",
                    "harvest", "settle")

#: ``time`` module entry points whose call inside a traced body is GL006;
#: the bare spellings (from-imports) are distinctive enough to flag as
#: Names, ``time``/``clock`` themselves only as ``time.<attr>`` accesses
_HOST_TIMER_ATTRS = frozenset({
    "time", "time_ns", "perf_counter", "perf_counter_ns", "monotonic",
    "monotonic_ns", "process_time", "process_time_ns"})
_HOST_TIMER_NAMES = _HOST_TIMER_ATTRS - {"time"}

#: GL013 — directories whose modules are fleet-path code (plus the one
#: file-level exception, ``inference/serving.py``), and the method names
#: whose call inside an except body counts as "the swallow left a
#: trace": telemetry registry emits (``Counter.inc`` / ``Gauge.set`` /
#: ``Histogram.observe``), timeline events (``instant`` / flow pairs /
#: ``complete``), and logger/``warnings`` emit methods.  Name-based on
#: purpose (the lint runs without importing the package); ``set`` is the
#: noisiest member but a false CLEAN is a near-miss, never a false fire.
_GL013_DIRS = frozenset({"serving", "telemetry"})

#: GL014 — constructors that SEED a private generator instance: calling
#: them through the random/np.random module is the fix, not the bug
_GL014_SEEDED_CTORS = frozenset({
    "default_rng", "Generator", "SeedSequence", "RandomState", "Random"})
_GL013_EMITS = frozenset({
    "inc", "observe", "set", "instant", "flow_start", "flow_end",
    "complete", "warning", "warn", "error", "exception", "info",
    "debug", "critical"})


def _gl013_in_scope(path: str) -> bool:
    """True for modules under a ``serving/`` or ``telemetry/`` directory
    and for ``inference/serving.py`` — the code whose swallowed
    exceptions the incident recorder exists to observe."""
    parts = Path(path).as_posix().split("/")
    if set(parts[:-1]) & _GL013_DIRS:
        return True
    return parts[-1] == "serving.py" and "inference" in parts[:-1]


_NOQA_RE = re.compile(
    r"#\s*graft:\s*noqa(?:\s*\(\s*([A-Za-z0-9_,\s]+)\s*\))?")


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    line: int
    col: int
    code: str
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col + 1}: " \
               f"{self.code} {self.message}"


def _func_tail(node: ast.AST) -> Optional[str]:
    """Last attribute segment of a call target: ``jax.lax.psum`` -> "psum",
    ``jit`` -> "jit"; None for anything not Name/Attribute-shaped."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _root_name(node: ast.AST) -> Optional[str]:
    """Leftmost Name of an Attribute/Subscript/Call-free access chain:
    ``x.shape[0]`` -> "x"; None when the chain roots in a call/literal."""
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _chain_attrs(node: ast.AST) -> Set[str]:
    """All attribute names along an access chain (``x.shape[0]`` ->
    {"shape"}) — used to whitelist static ``.shape``-style reads."""
    attrs: Set[str] = set()
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        if isinstance(node, ast.Attribute):
            attrs.add(node.attr)
        node = node.value
    return attrs


def _jax_rooted(node: ast.AST) -> bool:
    """True when an expression chain roots in the ``jnp``/``jax`` module
    — walking THROUGH calls (``jnp.argmax(x).item()`` roots in ``jnp``),
    so host numpy (``np.asarray(v).item()``) and plain variables never
    match (GL012 stays a no-false-positive heuristic)."""
    while isinstance(node, (ast.Attribute, ast.Subscript, ast.Call)):
        node = node.func if isinstance(node, ast.Call) else node.value
    return isinstance(node, ast.Name) and node.id in ("jnp", "jax")


class _Scope:
    """One function's lint context inside the scope tree."""

    def __init__(self, node, parent: Optional["_Scope"]):
        self.node = node
        self.parent = parent
        self.is_jit = False
        args = node.args
        names = [a.arg for a in getattr(args, "posonlyargs", [])]
        names += [a.arg for a in args.args + args.kwonlyargs]
        for special in (args.vararg, args.kwarg):
            if special is not None:
                names.append(special.arg)
        self.params: Set[str] = set(names)
        #: names bound by assignment inside the body (not traced roots)
        self.locals: Set[str] = set()

    def traced_names(self) -> Set[str]:
        """Parameters of this jit body and of every enclosing jit body
        (closures over tracers stay traced)."""
        out: Set[str] = set()
        scope: Optional[_Scope] = self
        while scope is not None:
            if scope.is_jit:
                out |= scope.params - self.locals
            scope = scope.parent
        return out

    def builder_params(self) -> Set[str]:
        """Parameters of enclosing NON-jit functions: concrete values whose
        shapes get baked into the traced program at closure creation."""
        out: Set[str] = set()
        scope = self.parent
        while scope is not None:
            if not scope.is_jit:
                out |= scope.params
            scope = scope.parent
        return (out - self.params) - self.locals


class _Analyzer:
    def __init__(self, tree: ast.Module, path: str,
                 axes: frozenset = DEFAULT_MESH_AXES):
        self.path = path
        self.axes = axes
        self._gl013 = _gl013_in_scope(path)
        self.findings: List[Finding] = []
        self._scopes: Dict[ast.AST, _Scope] = {}
        self._by_name: Dict[str, List[ast.AST]] = {}
        self._build_scopes(tree, None)
        self._mark_jit_bodies(tree)

    # ------------------------------------------------------------ scope pass
    def _build_scopes(self, node: ast.AST, parent: Optional[_Scope]) -> None:
        scope = parent
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scope = _Scope(node, parent)
            self._scopes[node] = scope
            self._by_name.setdefault(node.name, []).append(node)
        elif isinstance(node, ast.Lambda):
            scope = _Scope(node, parent)
            self._scopes[node] = scope
        elif scope is not None and isinstance(node, ast.Name) and \
                isinstance(node.ctx, ast.Store):
            scope.locals.add(node.id)
        for child in ast.iter_child_nodes(node):
            self._build_scopes(child, scope)

    def _mark_jit_bodies(self, tree: ast.Module) -> None:
        jitted: Set[ast.AST] = set()
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if self._is_jit_expr(dec):
                        jitted.add(node)
            elif isinstance(node, ast.Call) and \
                    _func_tail(node.func) in _JIT_WRAPPERS:
                for arg in self._callable_args(node):
                    for name_node in ast.walk(arg):
                        if isinstance(name_node, ast.Name):
                            for fn in self._by_name.get(name_node.id, []):
                                jitted.add(fn)
                        elif isinstance(name_node, ast.Lambda):
                            jitted.add(name_node)
        # lexical closure: everything nested inside a jit body is traced too
        for fn in jitted:
            self._scopes[fn].is_jit = True
        for scope in self._scopes.values():
            parent = scope.parent
            while parent is not None:
                if parent.is_jit:
                    scope.is_jit = True
                    break
                parent = parent.parent

    @staticmethod
    def _callable_args(call: ast.Call) -> List[ast.AST]:
        """The expressions that may carry the traced callable: every
        positional arg plus f=/fun=/fn= keywords (``shard_map(fn, mesh=...,
        in_specs=...)`` and ``jax.jit(wrapper(step), ...)`` both resolve)."""
        out = list(call.args)
        out += [kw.value for kw in call.keywords
                if kw.arg in ("f", "fun", "fn")]
        return out

    def _is_jit_expr(self, dec: ast.AST) -> bool:
        if _func_tail(dec) in _JIT_WRAPPERS:
            return True
        if isinstance(dec, ast.Call):
            if _func_tail(dec.func) in _JIT_WRAPPERS:
                return True
            if _func_tail(dec.func) == "partial" and dec.args and \
                    _func_tail(dec.args[0]) in _JIT_WRAPPERS:
                return True
        return False

    # --------------------------------------------------------------- helpers
    def _emit(self, node: ast.AST, code: str, message: str) -> None:
        self.findings.append(Finding(self.path, node.lineno, node.col_offset,
                                     code, message))

    def _enclosing_scope(self, stack: List[_Scope]) -> Optional[_Scope]:
        return stack[-1] if stack else None

    def _is_traced(self, expr: ast.AST, scope: _Scope) -> bool:
        root = _root_name(expr)
        return root is not None and root in scope.traced_names()

    @staticmethod
    def _sanctioned_xfer(stack: List[_Scope]) -> bool:
        """True when any enclosing function's name marks it a sanctioned
        blocking-transfer helper (GL007)."""
        for scope in stack:
            name = getattr(scope.node, "name", "")
            if any(tag in name.lower() for tag in _SANCTIONED_XFER):
                return True
        return False

    # ------------------------------------------------------------- main walk
    def analyze(self, tree: ast.Module) -> List[Finding]:
        self._walk(tree, [], False)
        return self.findings

    def _walk(self, node: ast.AST, stack: List[_Scope],
              in_loop: bool) -> None:
        scope = self._scopes.get(node)
        def_time_loop = False
        if scope is not None:
            stack = stack + [scope]
            # a nested def's BODY is not "in" the enclosing loop until
            # called — but its decorators, default values, and
            # annotations evaluate AT DEF TIME, once per iteration
            def_time_loop, in_loop = in_loop, False
        cur = self._enclosing_scope(stack)
        in_jit = cur is not None and cur.is_jit

        if isinstance(node, ast.Call):
            self._check_call(node, cur, in_jit,
                             in_loop and self._sanctioned_xfer(stack) is False)
        elif isinstance(node, ast.ExceptHandler) and self._gl013:
            self._check_except(node)
        elif isinstance(node, ast.JoinedStr) and in_jit:
            self._check_fstring(node, cur)
        elif isinstance(node, ast.Attribute) and in_jit:
            self._check_shape_capture(node, cur)
        elif isinstance(node, (ast.If, ast.While)) and in_jit:
            self._check_branch(node, cur)
        elif isinstance(node, (ast.If, ast.While)) and not in_jit:
            # GL012: a jnp-rooted call as a host branch test concretizes
            # one bool per evaluation — per-iteration for a While's own
            # test (the While IS the loop) or an If inside a loop body
            per_iter = isinstance(node, ast.While) or in_loop
            if per_iter and isinstance(node.test, ast.Call) and \
                    _jax_rooted(node.test) and \
                    not self._sanctioned_xfer(stack):
                self._emit(node.test, "GL012",
                           "jnp truthiness as a host loop test syncs one "
                           "bool per iteration — fold the condition into "
                           "an on-device lax.while_loop cond and fence "
                           "once")

        if scope is not None:
            # function node: body runs per call (loop context cleared),
            # everything else (decorator_list, ast.arguments with its
            # defaults/annotations) runs at def time in the caller's
            # loop context
            body = node.body if isinstance(node.body, list) \
                else [node.body]               # Lambda: body is an expr,
            body_ids = set(map(id, body))      # evaluated per call too
            for child in ast.iter_child_nodes(node):
                self._walk(child, stack,
                           False if id(child) in body_ids
                           else def_time_loop)
            return
        if isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
            # only the BODY re-executes per iteration (plus a While's
            # test); a For's iter/target and either loop's else clause
            # run once and stay at the caller's loop depth
            per_iter = set(map(id, node.body))
            if isinstance(node, ast.While):
                per_iter.add(id(node.test))
            for child in ast.iter_child_nodes(node):
                self._walk(child, stack,
                           in_loop or id(child) in per_iter)
            return
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                             ast.GeneratorExp)):
            # comprehensions are loops too: everything re-evaluates per
            # element EXCEPT the first generator's iterable (evaluated
            # once, exactly like a For's iter)
            first_iter = node.generators[0].iter
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.comprehension):
                    for sub in ast.iter_child_nodes(child):
                        self._walk(sub, stack,
                                   in_loop or sub is not first_iter)
                else:
                    self._walk(child, stack, True)  # elt / key / value
            return
        for child in ast.iter_child_nodes(node):
            self._walk(child, stack, in_loop)

    # ----------------------------------------------------------------- rules
    def _check_call(self, node: ast.Call, scope, in_jit: bool,
                    in_unsanctioned_loop: bool = False) -> None:
        tail = _func_tail(node.func)
        # GL003 runs everywhere (the jit CALL lives in host code)
        if tail in ("jit", "pjit"):
            self._check_donation(node)
        # GL014 shares GL013's fleet-path scope
        if self._gl013:
            self._check_global_rng(node)
        # GL008 runs everywhere too (registries are built in host code)
        if tail in _METRIC_CTORS and isinstance(node.func, ast.Attribute):
            self._check_metric_convention(node, tail)
        if tail in _COLLECTIVES:
            self._check_axis_literal(node)
        if tail in ("PartitionSpec", "P"):
            self._check_pspec_literals(node)
        if not in_jit:
            # GL007: a blocking transfer inside a HOST loop body — each
            # iteration stalls on the device instead of overlapping it
            # jax.device_get / bare from-import device_get / any
            # *.block_until_ready() — all three spellings block
            if in_unsanctioned_loop and (
                    tail == "block_until_ready" or
                    (tail == "device_get" and
                     (isinstance(node.func, ast.Name) or
                      _root_name(node.func) == "jax"))):
                self._emit(node, "GL007",
                           f"{tail}() in a host loop body serializes the "
                           "device pipeline — batch the sync into a "
                           "sanctioned transfer helper (demote/promote/"
                           "swap/sync/prefetch) or hoist it out of the "
                           "loop")
            # GL012: a per-iteration SCALAR sync — same stall as GL007
            # but spelled as a concretization, one token at a time
            if in_unsanctioned_loop:
                if tail == "item" and not node.args and \
                        isinstance(node.func, ast.Attribute) and \
                        _jax_rooted(node.func.value):
                    self._emit(node, "GL012",
                               ".item() on a jnp value in a host loop "
                               "body syncs one scalar per iteration — "
                               "move the loop on-device (lax.while_loop "
                               "+ active mask) and read back once at a "
                               "fence helper")
                elif isinstance(node.func, ast.Name) and \
                        node.func.id in ("int", "float", "bool") and \
                        node.args and _jax_rooted(node.args[0]):
                    self._emit(node, "GL012",
                               f"{node.func.id}() over a jnp expression "
                               "in a host loop body syncs one scalar per "
                               "iteration — keep the decision on-device "
                               "and harvest at a fence helper")
            return
        # GL006: a host timer inside a traced body stamps TRACE time —
        # the body executes once, while XLA replays the compiled program
        # without re-entering Python, so the reading is dispatch/tracing
        # overhead at best and a trace-time constant at worst
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr in _HOST_TIMER_ATTRS and \
                _root_name(node.func) == "time":
            self._emit(node, "GL006",
                       f"time.{node.func.attr}() in a jit body measures "
                       "trace/dispatch time, not device execution — time "
                       "around the compiled call after a sync instead")
        elif isinstance(node.func, ast.Name) and \
                node.func.id in _HOST_TIMER_NAMES:
            self._emit(node, "GL006",
                       f"{node.func.id}() in a jit body measures trace/"
                       "dispatch time, not device execution — time around "
                       "the compiled call after a sync instead")
        # GL001: device->host materialization in a traced body
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr in ("item", "tolist") and not node.args:
            self._emit(node, "GL001",
                       f".{node.func.attr}() in a jit body forces a host "
                       "sync (use the traced value directly)")
        elif isinstance(node.func, ast.Name) and \
                node.func.id in ("float", "int", "bool") and node.args:
            arg = node.args[0]
            if self._is_traced(arg, scope) and \
                    not (_chain_attrs(arg) & _STATIC_ATTRS):
                self._emit(node, "GL001",
                           f"{node.func.id}() on traced value "
                           f"'{_root_name(arg)}' in a jit body (host "
                           "concretization; use jnp casts)")
        elif tail in ("asarray", "array") and \
                isinstance(node.func, ast.Attribute) and \
                _root_name(node.func) in ("np", "numpy") and node.args and \
                self._is_traced(node.args[0], scope):
            self._emit(node, "GL001",
                       f"np.{tail}() on traced value "
                       f"'{_root_name(node.args[0])}' in a jit body "
                       "(host materialization; use jnp.asarray)")
        elif isinstance(node.func, ast.Name) and node.func.id == "str" and \
                node.args and self._is_traced(node.args[0], scope):
            self._emit(node, "GL002",
                       f"str() of traced value '{_root_name(node.args[0])}' "
                       "in a jit body concretizes at trace time")

    def _check_donation(self, node: ast.Call) -> None:
        kwargs = {kw.arg for kw in node.keywords}
        if "donate_argnums" in kwargs or "donate_argnames" in kwargs:
            return
        for arg in self._callable_args(node):
            for name_node in ast.walk(arg):
                if not isinstance(name_node, ast.Name):
                    continue
                for fn in self._by_name.get(name_node.id, []):
                    pools = self._scopes[fn].params & _POOLISH_PARAMS
                    if pools:
                        self._emit(
                            node, "GL003",
                            f"jax.jit({name_node.id}) updates "
                            f"{sorted(pools)} but makes no donate_argnums "
                            "decision — every step copies the buffer "
                            "(pass donate_argnums=(...) or an explicit ())")
                        return

    def _check_metric_convention(self, node: ast.Call, kind: str) -> None:
        """GL008: registry ``counter``/``gauge``/``histogram`` calls with
        a literal family name must follow the documented convention
        (docstring rule table).  Non-literal names (the federation layer
        copying families programmatically) are out of scope."""
        first = node.args[0] if node.args else None
        if not (isinstance(first, ast.Constant) and
                isinstance(first.value, str)):
            return
        name = first.value
        if not name.startswith(_METRIC_NAMESPACES):
            self._emit(node, "GL008",
                       f"metric family '{name}' lacks a subsystem "
                       "namespace prefix "
                       f"({'/'.join(_METRIC_NAMESPACES)})")
        if kind == "counter" and not name.endswith("_total"):
            self._emit(node, "GL008",
                       f"counter '{name}' must end in '_total' "
                       "(Prometheus monotone-counter convention)")
        elif kind != "counter" and name.endswith("_total"):
            self._emit(node, "GL008",
                       f"{kind} '{name}' must not end in '_total' — "
                       "the suffix promises a monotone counter")
        for kw in node.keywords:
            if kw.arg is None or kw.arg in _METRIC_PARAM_KWARGS:
                continue
            if kw.arg not in _METRIC_LABEL_KEYS:
                self._emit(node, "GL008",
                           f"metric label key '{kw.arg}' is outside the "
                           "documented label set "
                           f"({', '.join(sorted(_METRIC_LABEL_KEYS))}) — "
                           "ad-hoc labels become unbounded series "
                           "cardinality")

    def _check_axis_literal(self, node: ast.Call) -> None:
        cand: List[ast.AST] = []
        # axis_index(axis_name) takes the name as its SOLE positional arg;
        # the data-carrying collectives take it at index 1
        pos = 0 if _func_tail(node.func) == "axis_index" else 1
        if len(node.args) > pos:
            cand.append(node.args[pos])
        cand += [kw.value for kw in node.keywords
                 if kw.arg in ("axis_name", "axis")]
        for expr in cand:
            exprs = expr.elts if isinstance(expr, ast.Tuple) else [expr]
            for e in exprs:
                if isinstance(e, ast.Constant) and isinstance(e.value, str) \
                        and e.value not in self.axes:
                    self._emit(
                        e, "GL004",
                        f"axis name '{e.value}' is not a mesh axis the "
                        f"engines build ({', '.join(sorted(self.axes))})")

    def _check_pspec_literals(self, node: ast.Call) -> None:
        for arg in node.args:
            elts = arg.elts if isinstance(arg, ast.Tuple) else [arg]
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, str) \
                        and e.value not in self.axes:
                    self._emit(
                        e, "GL004",
                        f"PartitionSpec axis '{e.value}' is not a mesh "
                        "axis the engines build")

    def _check_fstring(self, node: ast.JoinedStr, scope) -> None:
        for value in node.values:
            if not isinstance(value, ast.FormattedValue):
                continue
            if self._is_traced(value.value, scope):
                root = _root_name(value.value)
                what = "shape of" if "shape" in _chain_attrs(value.value) \
                    else "value of"
                self._emit(node, "GL002",
                           f"f-string over traced {what} '{root}' in a jit "
                           "body concretizes at trace time")

    def _check_shape_capture(self, node: ast.Attribute, scope) -> None:
        if node.attr != "shape":
            return
        root = _root_name(node)
        if root is None or self._is_traced(node, scope):
            return                      # own traced arg: shapes are static
        if root in scope.builder_params():
            self._emit(node, "GL002",
                       f"'.shape' of '{root}' captured from an enclosing "
                       "builder's arguments — the shape is baked into the "
                       "program at closure creation (pass the array into "
                       "the jit body instead)")

    def _check_global_rng(self, node: ast.Call) -> None:
        """GL014: a draw from the PROCESS-GLOBAL generator —
        ``random.<fn>(...)`` or ``np.random.<fn>(...)`` /
        ``numpy.random.<fn>(...)`` — in fleet-path code.  The global
        stream advances in whatever order requests happen to interleave,
        so a crash replay or a re-homed request can never reproduce its
        draws.  Seeded-instance constructors called through the same
        modules (``default_rng`` & co.) are the sanctioned spelling."""
        func = node.func
        if not isinstance(func, ast.Attribute) or \
                func.attr in _GL014_SEEDED_CTORS:
            return
        base = func.value
        if isinstance(base, ast.Name) and base.id == "random":
            spelled = f"random.{func.attr}"
        elif isinstance(base, ast.Attribute) and base.attr == "random" and \
                isinstance(base.value, ast.Name) and \
                base.value.id in ("np", "numpy"):
            spelled = f"{base.value.id}.random.{func.attr}"
        else:
            return
        self._emit(node, "GL014",
                   f"{spelled}() draws from the process-global RNG in "
                   "fleet scheduler code — the stream is interleaving-"
                   "order dependent, so replay/re-homing cannot reproduce "
                   "it; seed a private instance (np.random.default_rng / "
                   "random.Random) or use the engine's counter-based "
                   "sampler")

    def _check_except(self, node: ast.ExceptHandler) -> None:
        """GL013: in fleet-path modules, an except body must do ONE of —
        re-raise (any ``raise``), reference the caught exception by name
        (a typed-error store / repr into a report IS observation), or
        call a telemetry/log emit method.  Finding lands on the
        ``except`` line, so that's where a justifying
        ``# graft: noqa(GL013)`` goes."""
        for stmt in node.body:
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Raise):
                    return
                if node.name and isinstance(sub, ast.Name) and \
                        sub.id == node.name and isinstance(sub.ctx, ast.Load):
                    return
                if isinstance(sub, ast.Call) and \
                        isinstance(sub.func, ast.Attribute) and \
                        sub.func.attr in _GL013_EMITS:
                    return
        self._emit(node, "GL013",
                   "except block swallows the exception without a trace — "
                   "re-raise, store/log the caught exception, or emit a "
                   "telemetry counter/timeline event (a failure nothing "
                   "records is an incident nothing can trigger on)")

    @staticmethod
    def _truthy_parts(expr):
        """Subexpressions evaluated for their truth value by a test:
        ``a and not b`` -> [a, b] (BoolOp operands and ``not`` bodies are
        truthiness positions too)."""
        if isinstance(expr, ast.BoolOp):
            for v in expr.values:
                yield from _Analyzer._truthy_parts(v)
        elif isinstance(expr, ast.UnaryOp) and isinstance(expr.op, ast.Not):
            yield from _Analyzer._truthy_parts(expr.operand)
        else:
            yield expr

    def _check_branch(self, node, scope) -> None:
        test = node.test
        kind = "if" if isinstance(node, ast.If) else "while"
        for part in self._truthy_parts(test):
            if isinstance(part, (ast.Name, ast.Attribute, ast.Subscript)) \
                    and self._is_traced(part, scope) and \
                    not (_chain_attrs(part) & _STATIC_ATTRS):
                self._emit(node, "GL005",
                           f"truthiness of traced value "
                           f"'{_root_name(part)}' as an {kind} test in a "
                           "jit body (use jnp.where / lax.cond)")
                return
        for comp in ast.walk(test):
            if not isinstance(comp, ast.Compare):
                continue
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in comp.ops):
                continue                # None checks are static Python
            operands = [comp.left] + list(comp.comparators)
            for operand in operands:
                if self._is_traced(operand, scope) and \
                        not (_chain_attrs(operand) & _STATIC_ATTRS):
                    self._emit(
                        node, "GL005",
                        f"comparison on traced value "
                        f"'{_root_name(operand)}' as an {kind} test in a "
                        "jit body (ambiguous array truth value; use "
                        "jnp.where / lax.cond)")
                    return


# ------------------------------------------------------------------ driver
def _suppressed(finding: Finding, lines: Sequence[str]) -> bool:
    if not (1 <= finding.line <= len(lines)):
        return False
    m = _NOQA_RE.search(lines[finding.line - 1])
    if not m:
        return False
    if m.group(1) is None:
        return True
    codes = {c.strip().upper() for c in m.group(1).split(",")}
    return finding.code in codes


def check_source(source: str, path: str = "<string>",
                 axes: frozenset = DEFAULT_MESH_AXES,
                 keep_suppressed: bool = False) -> List[Finding]:
    """Lint one module's source text; returns unsuppressed findings (all
    findings with ``keep_suppressed=True``)."""
    tree = ast.parse(source, filename=path)
    findings = _Analyzer(tree, path, axes).analyze(tree)
    if keep_suppressed:
        return findings
    lines = source.splitlines()
    return [f for f in findings if not _suppressed(f, lines)]


def iter_py_files(paths: Sequence[str]) -> List[Path]:
    out: List[Path] = []
    for p in paths:
        path = Path(p)
        if path.is_dir():
            out.extend(sorted(path.rglob("*.py")))
        elif path.suffix == ".py":
            out.append(path)
    return out


def lint_paths(paths: Sequence[str],
               axes: frozenset = DEFAULT_MESH_AXES
               ) -> Tuple[List[Finding], int]:
    """Lint every ``*.py`` under ``paths``; returns (findings, files)."""
    findings: List[Finding] = []
    files = iter_py_files(paths)
    for f in files:
        try:
            source = f.read_text(encoding="utf-8")
            findings.extend(check_source(source, str(f), axes))
        except SyntaxError as e:
            findings.append(Finding(str(f), e.lineno or 0, 0, "GL000",
                                    f"syntax error: {e.msg}"))
    return findings, len(files)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="graft-lint",
        description="TPU/JAX recompile + host-sync hazard lint "
                    "(rules GL001..GL014; suppress with "
                    "'# graft: noqa(GLxxx)')")
    ap.add_argument("paths", nargs="*", default=["deepspeed_tpu"],
                    help="files/dirs to lint (default: deepspeed_tpu)")
    ap.add_argument("--axes", default=None,
                    help="comma-separated mesh axis allowlist "
                         "(default: %s)" % ",".join(sorted(DEFAULT_MESH_AXES)))
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule table and exit")
    args = ap.parse_args(argv)
    if args.list_rules:
        for code in sorted(RULES):
            print(f"{code}  {RULES[code]}")
        return 0
    axes = frozenset(a.strip() for a in args.axes.split(",")) \
        if args.axes else DEFAULT_MESH_AXES
    paths = args.paths or ["deepspeed_tpu"]
    findings, nfiles = lint_paths(paths, axes)
    if nfiles == 0:
        # a typo'd path must fail loudly, not turn the CI gate into a no-op
        print(f"graft-lint: no Python files under {paths}", file=sys.stderr)
        return 2
    for f in findings:
        print(f.render())
    status = f"graft-lint: {nfiles} files, {len(findings)} finding(s)"
    print(status, file=sys.stderr)
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
