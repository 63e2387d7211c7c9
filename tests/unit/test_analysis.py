"""Correctness tooling (``deepspeed_tpu/analysis/``): lint rule fixtures,
recompile-sentry budgets, and paged-state fault injection.

Tier-1 (fast) coverage:
 - ``graft-lint`` rule fixtures: per rule, one minimal snippet that MUST
   fire and a near-miss that must NOT, plus pragma suppression and the
   zero-findings gate over the real package (the same check CI's ``lint``
   job runs).
 - ``RecompileSentry``: a deliberately shape-unstable callable trips its
   budget with an abstract-signature diff; the serving engine's chunked
   and speculative traces do NOT (replacing the old after-the-fact
   ``_cache_size`` probes).
 - ``audit_paged_state`` fault injection: seeded corruption of allocator/
   trie/table state (leaked refcount, double-free, trie/table divergence,
   scratch aliasing) raises :class:`PagedStateError` naming the violated
   invariant; a clean mid-trace engine audits green.
"""

import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.analysis import invariants, lint, sentry
from deepspeed_tpu.analysis.invariants import (PagedStateError,
                                               audit_paged_state)
from deepspeed_tpu.analysis.sentry import RecompileSentry, RetraceError
from deepspeed_tpu.inference.paged import BlockAllocator, PrefixCache
from deepspeed_tpu.inference.serving import Request, ServingEngine
from deepspeed_tpu.models import gpt2

REPO = Path(__file__).resolve().parents[2]


# ------------------------------------------------------------------- lint
def _codes(src):
    return [f.code for f in lint.check_source(src)]


def test_gl001_host_sync_fires_and_near_miss():
    fires = """
import jax, jax.numpy as jnp, numpy as np

def step(x, cache):
    v = x.item()
    f = float(x)
    a = np.asarray(x)
    return v + f + a

jax.jit(step, donate_argnums=(1,))
"""
    codes = _codes(fires)
    assert codes.count("GL001") == 3, codes
    near_miss = """
import jax, jax.numpy as jnp, numpy as np

def step(x, cache):
    n = int(x.shape[0])          # static: shapes are concrete under trace
    y = jnp.asarray(x) * n       # jnp, not np
    return y

def host(x):
    return float(x)              # not a jit body

jax.jit(step, donate_argnums=(1,))
"""
    assert "GL001" not in _codes(near_miss)


def test_gl002_stringify_and_closure_shape_fire_and_near_miss():
    fires = """
import jax

def build(example):
    def step(x):
        msg = f"got {x.shape} / {x}"         # traced shape+value f-string
        n = example.shape[0]                 # baked closure shape
        return x.reshape(n, -1)
    return jax.jit(step)
"""
    codes = _codes(fires)
    assert codes.count("GL002") >= 2, codes
    near_miss = """
import jax

def build(width):
    def step(x):
        n = x.shape[0]                       # own traced arg: static
        return x.reshape(n, width)
    return jax.jit(step)

def host(example):
    print(f"shape {example.shape}")          # not a jit body
"""
    assert "GL002" not in _codes(near_miss)


def test_gl003_missing_donation_fires_and_near_miss():
    fires = """
import jax

def step(tokens, cache):
    return cache

fn = jax.jit(step)
"""
    assert _codes(fires) == ["GL003"]
    near_miss = """
import jax

def step(tokens, cache):
    return cache

def pure(tokens, weights):
    return tokens

a = jax.jit(step, donate_argnums=(1,))
b = jax.jit(step, donate_argnums=())     # explicit decision counts
c = jax.jit(pure)                        # nothing pool-like
"""
    assert "GL003" not in _codes(near_miss)


def test_gl004_axis_literal_fires_and_near_miss():
    fires = """
import jax
from jax.sharding import PartitionSpec as P

def f(x):
    y = jax.lax.psum(x, "tensor")
    spec = P(None, "modle")
    return y, spec
"""
    codes = _codes(fires)
    assert codes.count("GL004") == 2, codes
    near_miss = """
import jax
from jax.sharding import PartitionSpec as P

def f(x, axis_name):
    y = jax.lax.psum(x, "tp")
    z = jax.lax.pmean(x, axis_name)      # variable axis: host decides
    spec = P(None, ("dp", "ep"))
    return y, z, spec
"""
    assert "GL004" not in _codes(near_miss)
    # axis_index takes the name as its SOLE positional argument
    assert _codes("import jax\njax.lax.axis_index('tpx')\n") == ["GL004"]
    assert _codes("import jax\njax.lax.axis_index('dp')\n") == []


def test_gl005_traced_branch_fires_and_near_miss():
    fires = """
import jax

def step(x, y):
    if x == y:
        return x
    return y

jax.jit(step)
"""
    assert _codes(fires) == ["GL005"]
    near_miss = """
import jax, jax.numpy as jnp

def step(x, valid):
    if valid is None:                    # static None check
        valid = jnp.ones_like(x)
    k = 4
    if k > 2:                            # host ints
        x = x * 2
    return jnp.where(x == valid, x, 0)   # expression, not a branch

jax.jit(step)
"""
    assert "GL005" not in _codes(near_miss)
    # traced truthiness hides inside BoolOp / `not` too
    boolop = """
import jax

def step(mask, flag):
    if mask and flag:
        return mask
    while not mask:
        break
    return flag

jax.jit(step)
"""
    assert _codes(boolop).count("GL005") == 2
    static = """
import jax

def step(x):
    if x.shape and len(x.shape) > 1:     # static under trace
        return x
    return x

jax.jit(step)
"""
    assert "GL005" not in _codes(static)


def test_gl006_host_timer_fires_and_near_miss():
    fires = """
import jax, time
from time import perf_counter

def step(x, cache):
    t0 = time.perf_counter()             # trace-time stamp, not device
    t1 = perf_counter()                  # from-import spelling
    t2 = time.time()
    return cache

jax.jit(step, donate_argnums=(1,))
"""
    codes = _codes(fires)
    assert codes.count("GL006") == 3, codes
    near_miss = """
import jax, time

def step(x, cache):
    return cache

def host(x, cache):
    t0 = time.perf_counter()             # host code AROUND the jit call
    out = jax.jit(step, donate_argnums=(1,))(x, cache)
    jax.block_until_ready(out)
    return time.time() - t0, out

class Clock:
    def time(self):
        return 0.0

def host2(c: "Clock"):
    return c.time()                      # not the time module
"""
    assert "GL006" not in _codes(near_miss)


def test_gl007_blocking_transfer_in_loop_fires_and_near_miss():
    fires = """
import jax

def scheduler(reqs, pool):
    outs = []
    while reqs:
        out = step(pool)
        jax.block_until_ready(out)           # per-iteration sync
        outs.append(jax.device_get(out))     # and a second one
    for o in outs:
        o.block_until_ready()                # method spelling
    return outs
"""
    codes = _codes(fires)
    assert codes.count("GL007") == 3, codes
    near_miss = """
import jax

def scheduler(reqs, pool):
    outs = [step(pool) for r in reqs]
    jax.block_until_ready(outs)              # one sync, outside the loop
    return jax.device_get(outs)

def _demote_blocks(blocks, pool):
    for b in blocks:
        host = jax.device_get(gather(pool, b))   # sanctioned helper
    return host

def _promote_wait(staged):
    for leaf in staged:
        leaf.block_until_ready()             # sanctioned helper
    return staged

def driver(xs):
    while xs:
        y = jax.device_put(xs.pop())         # device_put is async
    return y

def once(xs):
    for x in jax.device_get(xs):             # iter expr runs ONCE
        use(x)
    for x in xs:
        pass
    else:
        jax.block_until_ready(xs)            # else clause runs ONCE
"""
    assert "GL007" not in _codes(near_miss)
    # a While TEST re-evaluates per iteration — that one does fire
    while_test = """
import jax

def driver(x):
    while jax.device_get(x) > 0:
        x = step(x)
"""
    assert _codes(while_test) == ["GL007"]
    # comprehensions are loops, and the from-import spelling counts;
    # the first generator's iterable still evaluates once (no fire)
    comp = """
import jax
from jax import device_get

def driver(xs, pool):
    a = [jax.device_get(step(pool)) for x in xs]
    b = {device_get(x) for x in xs}
    c = [f(x) for x in jax.device_get(xs)]      # iterable: runs once
    return a, b, c
"""
    assert _codes(comp).count("GL007") == 2, _codes(comp)
    # a nested def's DEFAULTS/decorators evaluate per iteration (fire);
    # its body only runs when called (no fire)
    nested = """
import jax

def driver(xs):
    for x in xs:
        def f(y=jax.device_get(x)):          # def-time, per iteration
            return jax.device_get(y)         # call-time: not the loop
        h = f
    return h
"""
    assert _codes(nested).count("GL007") == 1, _codes(nested)
    # pragma support: documented per-item commit points stay expressible
    pragma = """
import jax

def driver(xs):
    for x in xs:
        jax.device_get(x)  # graft: noqa(GL007) per-item commit, documented
"""
    assert _codes(pragma) == []


def test_gl008_metric_convention_fires_and_near_miss():
    fires = """
def build(m):
    a = m.counter("serving_requests")            # counter missing _total
    b = m.counter("things_total", "help")        # missing namespace
    c = m.gauge("serving_queue_total")           # gauge claiming _total
    d = m.histogram("serving_lat_seconds", uid="x")  # ad-hoc label key
"""
    codes = _codes(fires)
    assert codes.count("GL008") == 4, codes
    near_miss = """
import collections

def build(m, name):
    a = m.counter("serving_requests_admitted_total", "help")
    b = m.gauge("train_loss", "help", replica="0")
    c = m.histogram("inference_forward_seconds", buckets=(1.0, 2.0),
                    timer="fwd", monitor_name="X/y")
    d = m.counter("serving_kv_swaps_total", direction="out")
    e = m.counter(name)                          # non-literal: out of scope
    f = collections.Counter("abc")               # not a registry call
    g = m.gauge("serving_slo_burn_rate", slo_class="batch", slo="ttft")
"""
    assert "GL008" not in _codes(near_miss)
    # one bad call can violate two conventions at once — both fire
    double = """
def build(m):
    m.counter("queue_depth")   # no namespace AND not _total
"""
    assert _codes(double).count("GL008") == 2
    pragma = """
def build(m):
    m.counter("legacy_hits")  # graft: noqa(GL008) pre-registry name, migrating
"""
    assert _codes(pragma) == []


def test_gl012_scalar_sync_in_scheduler_loop_fires_and_near_miss():
    """GL012: the host-loop scalar concretizations (one
    .item()/int()/bool() per decoded token pins the scheduler to device
    latency)."""
    fires = """
import jax.numpy as jnp

def scheduler(srv, toks):
    while srv.pending:
        tok = jnp.argmax(toks).item()        # scalar per iteration
        if bool(jnp.any(toks > 0)):          # implicit bool sync
            srv.finish()
        n = int(jnp.sum(toks))               # int() concretization
    while jnp.any(toks):                     # While test: per iteration
        toks = step(toks)
"""
    codes = _codes(fires)
    assert codes.count("GL012") == 4, codes
    near_miss = """
import numpy as np
import jax.numpy as jnp

def scheduler(srv, v, out):
    while srv.pending:
        tok = np.asarray(v).item()           # host numpy: no device sync
        n = int(out[0, 0])                   # plain variable: unknowable
        if srv.done:                         # host-state test
            break
    last = jnp.argmax(v).item()              # outside any loop: one-off

def _harvest(arrays):
    for a in arrays:
        n = int(jnp.sum(a))                  # sanctioned fence helper
    return n

def _swap_commit(blocks):
    while blocks:
        b = blocks.pop()
        flag = bool(jnp.any(b))              # sanctioned transfer helper
    return flag
"""
    assert "GL012" not in _codes(near_miss)
    # inside a jit body the same spellings are GL001/GL005 territory —
    # GL012 is host-scheduler-only (no double reporting)
    in_jit = """
import jax, jax.numpy as jnp

def step(x, cache):
    for _ in range(4):
        v = x.item()
    return cache

jax.jit(step, donate_argnums=(1,))
"""
    assert "GL012" not in _codes(in_jit)
    pragma = """
import jax.numpy as jnp

def probe(xs):
    for x in xs:
        v = float(jnp.abs(x))  # graft: noqa(GL012) per-layer harvest, documented
"""
    assert _codes(pragma) == []


def test_gl012_the_plain_decode_paths_fence_is_settle():
    """ISSUE 44: the scheduler keeps ONE call in flight and takes its
    results in ``_settle`` (``_harvest`` under it) — the plain path's
    fence, sanctioned by name; the same loop under any other name still
    fires (``_fence``: the allowance went with the fused runner, PR 59)."""
    body = """
import jax.numpy as jnp

class Engine:
    def {name}(self, flights):
        for flight in flights:
            if int(jnp.sum(flight.out)) < 0:    # a scalar per call
                break
"""
    for name in ("_settle", "_harvest"):
        assert "GL012" not in _codes(body.format(name=name)), name
    for name in ("_commit_decode", "_run_plain_decode", "step", "_fence"):
        assert _codes(body.format(name=name)).count("GL012") == 1, name


def test_gl013_swallowed_exception_fires_scoped_and_pragma():
    """GL013: an ``except`` in fleet-path code (serving/, telemetry/,
    inference/serving.py) that neither re-raises, nor uses the caught
    name, nor emits telemetry/logging swallows the failure — invisible
    to the flight recorder."""
    fires = """
def pull(rep):
    try:
        rep.step()
    except Exception:
        pass
"""
    in_scope = "deepspeed_tpu/serving/router.py"
    codes = [f.code for f in lint.check_source(fires, path=in_scope)]
    assert codes == ["GL013"], codes
    # finding anchors to the `except` line (where the pragma goes)
    f = lint.check_source(fires, path=in_scope)[0]
    assert f.line == 5
    # same source outside the fleet path: silent by design (tests,
    # analysis tools, and models/ are allowed terse cleanup handlers)
    assert lint.check_source(fires, path="deepspeed_tpu/models/gpt2.py") \
        == []
    assert lint.check_source(fires) == []
    # inference/serving.py is in scope despite not living under serving/
    assert [f.code for f in lint.check_source(
        fires, path="deepspeed_tpu/inference/serving.py")] == ["GL013"]

    near_misses = """
from ..utils.logging import logger

def pull(rep, metrics, errors):
    try:
        rep.step()
    except Exception:
        raise
    try:
        rep.step()
    except Exception as e:
        errors["step"] = repr(e)
    try:
        rep.step()
    except Exception:
        metrics.counter("serving_pull_fail_total").inc()
    try:
        rep.step()
    except Exception:
        logger.warning("step failed; degrading")
"""
    assert lint.check_source(near_misses, path=in_scope) == []

    pragma = """
def close(path):
    try:
        os.unlink(path)
    except OSError:  # graft: noqa(GL013) best-effort temp cleanup
        pass
"""
    assert lint.check_source(pragma, path=in_scope) == []
    kept = lint.check_source(pragma, path=in_scope, keep_suppressed=True)
    assert [f.code for f in kept] == ["GL013"]


def test_gl014_global_rng_fires_scoped_exempts_and_pragma():
    """GL014: process-global RNG draws (``random.*`` / ``np.random.*``
    module singletons) in fleet-path code are interleaving-order
    dependent — a replayed/re-homed request cannot reproduce them.
    Seeded instance constructors through the same modules are the fix
    spelling and must stay CLEAN."""
    in_scope = "deepspeed_tpu/serving/router.py"
    fires = """
import random
import numpy as np

def jitter(base):
    d = random.uniform(0.0, base)
    k = np.random.randint(0, 4)
    np.random.seed(0)
    return d + k
"""
    codes = [f.code for f in lint.check_source(fires, path=in_scope)]
    assert codes == ["GL014"] * 3, codes
    # out of fleet scope (tests, models, analysis tools): silent
    assert lint.check_source(fires, path="deepspeed_tpu/models/gpt2.py") \
        == []
    assert lint.check_source(fires) == []
    # inference/serving.py shares GL013's file-level scope rule
    assert [f.code for f in lint.check_source(
        fires, path="deepspeed_tpu/inference/serving.py")] == ["GL014"] * 3

    near_misses = """
import random
import numpy as np

def jitter(base, rng, entry):
    g = np.random.default_rng([7, 11])     # seeded instance ctor
    r = random.Random(42)                  # seeded instance ctor
    ss = np.random.SeedSequence(3)
    d = rng.uniform(0.0, base)             # instance method, not module
    k = entry.random.choice([1, 2])        # attribute chain, not np.random
    return g.integers(0, 4) + r.random() + d + k, ss
"""
    assert lint.check_source(near_misses, path=in_scope) == []

    pragma = """
import random

def backoff(base):
    return random.uniform(0.0, base)  # graft: noqa(GL014) jitter, non-replayed path
"""
    assert lint.check_source(pragma, path=in_scope) == []
    kept = lint.check_source(pragma, path=in_scope, keep_suppressed=True)
    assert [f.code for f in kept] == ["GL014"]


def test_noqa_pragma_suppresses_named_rule_only():
    src = """
import jax

def step(x, cache):
    v = x.item()  # graft: noqa(GL001) host commit point, documented
    f = float(x)
    return v + f

jax.jit(step, donate_argnums=(1,))
"""
    assert _codes(src) == ["GL001"]          # only the unsuppressed float()
    all_kept = lint.check_source(src, keep_suppressed=True)
    assert [f.code for f in all_kept].count("GL001") == 2
    bare = src.replace("noqa(GL001) host commit point, documented", "noqa")
    bare = bare.replace("f = float(x)", "f = 0.0")
    assert _codes(bare) == []


def test_wrapped_jit_callable_still_detected():
    """jax.jit(sentry.wrap(step, ...)) — the body resolves through the
    wrapper call, so the serving engine's own entry points stay linted."""
    src = """
import jax

def step(tokens, cache):
    bad = float(tokens)
    return cache

fn = jax.jit(wrapper.wrap(step, "decode"), donate_argnums=(1,))
"""
    assert _codes(src) == ["GL001"]


def test_lint_package_is_clean_and_cli_exit_codes(tmp_path):
    """The merged tree lints clean (the CI gate), and the CLI exits
    nonzero on a finding."""
    findings, nfiles = lint.lint_paths([str(REPO / "deepspeed_tpu")])
    assert nfiles > 100
    assert findings == [], [f.render() for f in findings]

    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n\ndef f(x, cache):\n    return cache\n\n"
                   "jax.jit(f)\n")
    proc = subprocess.run(
        [sys.executable, str(REPO / "bin" / "graft-lint"), str(bad)],
        capture_output=True, text=True)
    assert proc.returncode == 1 and "GL003" in proc.stdout
    ok = subprocess.run(
        [sys.executable, str(REPO / "bin" / "graft-lint"),
         str(REPO / "deepspeed_tpu" / "analysis")],
        capture_output=True, text=True)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    # a typo'd path must fail loudly, not no-op the CI gate
    typo = subprocess.run(
        [sys.executable, str(REPO / "bin" / "graft-lint"),
         str(tmp_path / "no_such_dir")],
        capture_output=True, text=True)
    assert typo.returncode == 2 and "no Python files" in typo.stderr


# ----------------------------------------------------------------- sentry
def test_sentry_trips_on_shape_unstable_callable_with_diff():
    import jax
    import jax.numpy as jnp

    s = RecompileSentry(name="t", strict=True)
    f = jax.jit(s.wrap(lambda x: x * 2, "f"))
    np.testing.assert_array_equal(np.asarray(f(jnp.zeros(4))), np.zeros(4))
    np.testing.assert_array_equal(np.asarray(f(jnp.zeros(4))), np.zeros(4))
    assert s.traces == 1                       # cache hit: no retrace
    with pytest.raises(RetraceError) as ei:
        f(jnp.zeros(8))                        # new shape: budget 1 blown
    msg = str(ei.value)
    assert "'t:f'" in msg and "[4]" in msg and "[8]" in msg, msg
    assert ei.value.name == "f"


def test_sentry_nonstrict_counts_and_total_budget():
    import jax
    import jax.numpy as jnp

    s = RecompileSentry(strict=False)
    f = jax.jit(s.wrap(lambda x: x + 1, "f"))
    f(jnp.zeros(2)); f(jnp.zeros(3)); f(jnp.zeros(4))
    assert s.traces == 3 and s.retraces_observed == 2
    assert s.report()["f"]["traces"] == 3

    s2 = RecompileSentry(strict=True, total_budget=2)
    g = jax.jit(s2.wrap(lambda x: x - 1, "g", budget=None))
    g(jnp.zeros(2)); g(jnp.zeros(3))
    with pytest.raises(RetraceError, match="total compile budget"):
        g(jnp.zeros(4))

    # non-strict total-budget drift is still OBSERVED: two entries each
    # within their own budget can blow the engine total (an unexpected
    # new program), and retraces_observed must say so
    s3 = RecompileSentry(strict=False, total_budget=2)
    a = jax.jit(s3.wrap(lambda x: x, "a"))
    b = jax.jit(s3.wrap(lambda x: x, "b"))
    c = jax.jit(s3.wrap(lambda x: x, "c"))
    a(jnp.zeros(2)); b(jnp.zeros(2))
    assert s3.retraces_observed == 0
    c(jnp.zeros(2))                            # 3 programs vs budget 2
    assert s3.retraces_observed == 1


def test_compile_listener_counts_backend_compiles():
    """The jax.monitoring hook sees real backend compiles — pins the
    '/jax/core/compile/backend_compile' event prefix against jax renames
    (a silent rename would make backend_compiles() report 0 forever)."""
    import jax
    import jax.numpy as jnp

    counter = sentry.install_compile_listener()
    assert sentry.install_compile_listener() is counter   # idempotent
    before = counter.count
    jax.jit(lambda x: x * 3 + 1)(jnp.zeros(5))            # fresh program
    assert counter.count > before
    assert sentry.backend_compiles() == counter.count


def test_sentry_abstract_signature_distinguishes_dtype_and_statics():
    import jax.numpy as jnp

    a = sentry.abstract_signature((jnp.zeros((2, 3), jnp.int32),), {})
    b = sentry.abstract_signature((jnp.zeros((2, 3), jnp.float32),), {})
    assert a != b
    d = sentry.signature_diff(a, b)
    assert d and "int32" in d[0] and "float32" in d[0]


def _mixed_trace(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               int(rng.integers(3, 40))),
                    max_new_tokens=int(rng.integers(1, 12)))
            for i in range(n)]


def test_sentry_enforces_serving_compile_contracts(tiny_engine):
    """Acceptance: the chunked 2-program and speculative contracts are
    enforced LIVE (strict sentry raises at trace time) instead of the old
    after-the-fact compile_count asserts — two serve calls over fresh
    shapes stay within budget."""
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=4, max_seq_len=128, block_size=8,
                        prefill_chunk=16, prefill_batch=2,
                        debug_checks=True)
    assert srv.compile_budget == 1 + len(srv._rungs)
    srv.serve(_mixed_trace(cfg, 8, seed=0))
    srv.serve(_mixed_trace(cfg, 4, seed=1))    # new shapes: no new traces
    assert srv.sentry.traces == 1 + len(srv._rungs) == 3
    assert srv.stats()["retraces_observed"] == 0
    assert sorted(srv.sentry.report()) == ["decode", "prefill[1x32]",
                                           "prefill[2x16]"]

    spec = ServingEngine(engine, slots=4, max_seq_len=128, block_size=8,
                         prefill_chunk=16, prefill_batch=2, spec_tokens=4,
                         debug_checks=True)
    # n-gram: a prefill program a rung + verify
    assert spec.compile_budget == 1 + len(spec._rungs)
    spec.serve(_mixed_trace(cfg, 6, seed=2))
    assert sorted(spec.sentry.report()) == ["prefill[1x32]", "prefill[2x16]",
                                            "verify"]
    assert spec.stats()["retraces_observed"] == 0


def test_serve_debug_checks_override_and_counters(tiny_engine):
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=2, max_seq_len=128, block_size=8,
                        prefill_chunk=16, prefill_batch=2)
    assert not srv.debug_checks and not srv.sentry.strict
    srv.serve(_mixed_trace(cfg, 3, seed=3), debug_checks=True)
    assert srv.debug_checks and srv.sentry.strict
    st = srv.stats()
    assert st["debug_checks"] and st["invariant_checks_run"] > 0
    assert st["retraces_observed"] == 0 and st["compile_budget"] == 1 + len(srv._rungs)
    # debug_checks installs the process-wide compile listener
    assert st["backend_compiles"] is not None and st["backend_compiles"] > 0


def test_init_serving_plumbs_debug_checks(tiny_engine):
    _, cfg = tiny_engine
    deepspeed_tpu.comm.reset_topology()
    srv = deepspeed_tpu.init_serving(
        gpt2.build(cfg),
        config={"dtype": "fp32", "tensor_parallel": {"tp_size": 1}},
        slots=2, max_seq_len=128, block_size=8, debug_checks=True)
    assert srv.debug_checks and srv.sentry.strict


def test_training_engine_registers_step_with_sentry():
    """The DP training engine's fused step is a registered entry point:
    one trace for the whole run (fixed batch shapes), zero drift."""
    deepspeed_tpu.comm.reset_topology()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=gpt2.build(gpt2.GPT2Config.tiny()),
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "mesh": {}})
    rng = np.random.default_rng(0)
    for _ in range(3):
        batch = {"input_ids": rng.integers(
            0, 512, size=(engine.train_batch_size(), 33)).astype(np.int32)}
        engine.train_batch(batch)
    rep = engine.sentry.report()
    assert rep["train_step"]["traces"] == 1, rep
    assert engine.sentry.retraces_observed == 0


# ------------------------------------------------------- paged invariants
def _tiny_state():
    """A hand-built consistent state: 2 slots, block_size 4; slot 0 holds
    blocks [1, 2] (block 1 shared with the trie), slot 1 holds [3]."""
    a = BlockAllocator(8)
    pc = PrefixCache(block_size=4)
    b1, b2, b3 = a.alloc(), a.alloc(), a.alloc()
    pc.register(np.arange(4), [b1], a)          # trie holds b1 too
    tables = np.zeros((2, 4), np.int32)
    tables[0, :2] = [b1, b2]
    tables[1, 0] = b3
    held = [[b1, b2], [b3]]
    needs = {0: 7, 1: 3}
    return a, pc, tables, held, needs


def _audit(a, pc, tables, held, needs):
    audit_paged_state(a, tables, held, prefix=pc, active_needs=needs,
                      block_size=4)


def test_audit_passes_on_consistent_state():
    _audit(*_tiny_state())
    # the checker's scratch-id mirror must track the allocator's
    from deepspeed_tpu.inference import paged

    assert invariants.SCRATCH_BLOCK == paged.SCRATCH_BLOCK


def test_audit_catches_leaked_refcount():
    a, pc, tables, held, needs = _tiny_state()
    a.incref(held[0][1])                        # phantom owner
    with pytest.raises(PagedStateError, match="leaked") as ei:
        _audit(a, pc, tables, held, needs)
    assert ei.value.invariant == "refcount-conservation"


def test_audit_catches_double_free():
    a, pc, tables, held, needs = _tiny_state()
    a.decref(held[1][0])                        # freed while still held
    with pytest.raises(PagedStateError, match="double-free") as ei:
        _audit(a, pc, tables, held, needs)
    assert ei.value.invariant == "refcount-conservation"


def test_audit_catches_trie_table_divergence():
    a, pc, tables, held, needs = _tiny_state()
    tables[0, 0] = held[1][0]                   # table no longer matches held
    with pytest.raises(PagedStateError, match="diverge") as ei:
        _audit(a, pc, tables, held, needs)
    assert ei.value.invariant == "length-occupancy"


def test_audit_catches_trie_structure_corruption():
    a, pc, tables, held, needs = _tiny_state()
    pc.entries()[0].children = 3                # counter out of sync
    with pytest.raises(PagedStateError) as ei:
        _audit(a, pc, tables, held, needs)
    assert ei.value.invariant == "trie-parent-child"


def test_audit_catches_trie_out_of_range_block():
    a, pc, tables, held, needs = _tiny_state()
    pc.entries()[0].block = -1                  # corrupt id must not wrap
    with pytest.raises(PagedStateError, match="out-of-range") as ei:
        _audit(a, pc, tables, held, needs)
    assert ei.value.invariant == "refcount-conservation"


def test_audit_catches_scratch_aliasing():
    a, pc, tables, held, needs = _tiny_state()
    # slot 0 needs 2 blocks for 7 tokens; unset its second table entry so
    # its writes would land in (and reads come from) scratch block 0
    tables[0, 1] = 0
    held[0] = held[0][:1]
    a.decref(2)
    with pytest.raises(PagedStateError, match="scratch") as ei:
        _audit(a, pc, tables, held, needs)
    assert ei.value.invariant == "scratch-aliasing"


def test_audit_catches_inactive_slot_residue():
    a, pc, tables, held, needs = _tiny_state()
    del needs[1]                                # slot 1 "released" but dirty
    with pytest.raises(PagedStateError, match="inactive") as ei:
        _audit(a, pc, tables, held, needs)
    assert ei.value.invariant == "length-occupancy"


def test_audit_runs_green_mid_trace(tiny_engine):
    """audit_serving_engine holds on REAL scheduler state mid-iteration:
    hook the decode step to audit with live actives (prefix reuse +
    preemption pressure in the trace)."""
    engine, cfg = tiny_engine
    srv = ServingEngine(engine, slots=3, max_seq_len=64, block_size=8,
                        prefill_chunk=32, prefill_batch=2, num_blocks=12,
                        debug_checks=True)
    rng = np.random.default_rng(5)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, 17),
                    max_new_tokens=28) for i in range(5)]
    audits = []
    orig = srv._run_plain_decode

    def hooked(params):
        invariants.audit_serving_engine(srv, srv._active)
        audits.append(len(srv._active))
        return orig(params)

    srv._run_plain_decode = hooked
    srv.serve(reqs)
    assert srv.preempted > 0 and audits
    assert srv.invariant_checks_run > 0
