"""ISSUE 68: device time by the program's own scopes — the vocabulary and its
normaliser, the scope table of a compiled program, the reader of a profile,
the records both engines keep at a program's first call, and the scopes every
program a cell runs must name."""

import ast
import importlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.analysis import sentry
from deepspeed_tpu.inference.serving import Request
from deepspeed_tpu.models import gpt2, opt
from deepspeed_tpu.telemetry import (device_scopes, hlo_text, profile, scopes,
                                     trace)
from deepspeed_tpu.telemetry.flops import ServingFlopsProfiler
from deepspeed_tpu.telemetry.programs import Programs

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from chipbench import families  # noqa: E402
from chipbench import run as cb_run  # noqa: E402


# ------------------------------------------------------------ the vocabulary
@pytest.mark.parametrize("op_name,want", [
    ("jit(decode_step)/head/dot_general", ("head", "fwd")),
    ("jit(step)/layer/while/body/layer/attn/layer/attn/qkv/dot_general",
     ("layer/attn/qkv", "fwd")),
    ("jit(step)/layer/while/body/layer/attn/add", ("layer/attn", "fwd")),
    ("jit(step)/layer/while/body/dynamic_slice", ("layer", "fwd")),
    ("jit(loss)/jvp()/while/body/closed_call/layer/mlp/tanh",
     ("layer/mlp", "fwd")),
    ("jit(train_step)/grad/merge/transpose(jvp(layer/attn/layer/attn/core))"
     "/mul", ("layer/attn/core", "bwd")),
    ("jit(loss)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/layer/mlp/dot_general", ("layer/mlp", "remat")),
    ("jit(loss)/transpose(jvp())/while/body/closed_call/checkpoint/layer/mlp/"
     "mul", ("layer/mlp", "bwd")),
    ("jit(train_step)/grad/merge/transpose(jvp(loss))/add_any",
     ("loss", "bwd")),
    ("jit(train_step)/optim/update/mul", ("optim/update", "fwd")),
    ("jit(decode_step)/jit(sample)/sample/sample/filter/while/body/gt",
     ("sample/filter", "fwd")),
    ("jit(draft)/mtp/layer/attn/layer/attn/out/dot_general",
     ("layer/attn/out", "fwd")),
    ("jit(f)/vmap(sample/sample/draw)/threefry2x32",
     ("sample/draw", "fwd")),
    ("jit(x)/while/body/add", ("unscoped", "fwd")),
    ("params['embed_positions']", ("unscoped", "fwd")),
    (None, ("unscoped", "fwd")),
])
def test_an_op_name_is_its_innermost_scope_and_its_pass(op_name, want):
    assert scopes.normalise(op_name) == want


def test_a_common_scope_is_the_longest_prefix_the_vocabulary_has():
    assert scopes.common_scope(["layer/attn/qkv", "layer/attn/out"]) \
        == "layer/attn"
    assert scopes.common_scope(["sample/filter", "sample/draw"]) == "sample"
    assert scopes.common_scope(["layer/attn/qkv", "head"]) is None
    assert scopes.common_scope(["layer/attn/qkv", "unscoped"]) is None
    for name, (layer, what) in scopes.VOCABULARY.items():
        assert layer in ("model step", "kernels", "engine", "ZeRO") and what
        assert re.fullmatch(r"[a-z_]+(/[a-z_]+)*", name), name


def _named_scope_literals():
    """(file, line, the literal or None) of every ``named_scope`` call of
    the package."""
    found = []
    package = os.path.join(ROOT, "deepspeed_tpu")
    for folder, _, files in os.walk(package):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(folder, fname)
            for node in ast.walk(ast.parse(open(path).read())):
                if isinstance(node, ast.Call) and getattr(
                        node.func, "attr", getattr(node.func, "id", "")) \
                        == "named_scope":
                    arg = node.args[0] if node.args else None
                    found.append((os.path.relpath(path, ROOT), node.lineno,
                                  arg.value if isinstance(arg, ast.Constant)
                                  else None))
    return found


def test_every_named_scope_of_the_package_is_an_entry_of_the_vocabulary():
    calls = _named_scope_literals()
    assert len(calls) > 150          # 55 before ISSUE 68
    strays = [c for c in calls if c[2] not in scopes.VOCABULARY]
    assert not strays, strays
    used = {c[2] for c in calls}
    assert used == set(scopes.VOCABULARY), set(scopes.VOCABULARY) - used


# ----------------------------------------------------------------- the table
def _tiny_program():
    """A scan of two scoped products and a norm, an unscoped product behind
    it, and an elementwise tail whose two halves sit under unrelated
    scopes."""
    def f(x, w, u):
        def layer(c, wl):
            with jax.named_scope("layer/attn/qkv"):
                h = jnp.dot(c, wl, preferred_element_type=jnp.float32)
            with jax.named_scope("layer/norm"):
                h = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True))
            return h, None
        with jax.named_scope("layer"):
            c, _ = jax.lax.scan(layer, x, w)
        y = jnp.dot(c, u, preferred_element_type=jnp.float32)
        with jax.named_scope("head"):
            a = jnp.exp(y)
        with jax.named_scope("loss"):
            return jnp.sin(a) + 1.0
    x = jnp.ones((8, 16), jnp.float32)
    w = jnp.ones((3, 16, 16), jnp.float32)
    u = jnp.ones((16, 32), jnp.float32)
    return jax.jit(f), (x, w, u)


def test_the_table_of_a_tiny_compiled_program_is_hand_countable():
    fn, args = _tiny_program()
    table = hlo_text.scope_table(fn.lower(*args).compile().as_text())
    assert table["module"] == "jit_f" and table["unknown_trips"] == []
    insts = table["instructions"]
    dots = {k: v for k, v in insts.items() if v["flops"]}
    by_scope = {v["scope"]: v for v in dots.values()}
    assert set(by_scope) == {"layer/attn/qkv", "unscoped"}
    # the scanned product: 2 x 8 x 16 x 16 flops, three trips; it reads the
    # carry and ONE layer of the stack, and writes the carry
    scanned = by_scope["layer/attn/qkv"]
    assert scanned["flops"] == 2 * 8 * 16 * 16 and scanned["trips"] == 3
    assert scanned["pass"] == "fwd" and not scanned["mixed"]
    # the product behind the loop carries no scope of the vocabulary
    bare = by_scope["unscoped"]
    assert bare["flops"] == 2 * 8 * 16 * 32 and bare["trips"] == 1
    assert bare["bytes"] == 4 * (8 * 16 + 16 * 32 + 8 * 32)
    # everything inside the loop runs three times, nothing outside does
    for row in insts.values():
        assert row["trips"] in (1, 3)
        if row["scope"] in ("layer/attn/qkv", "layer/norm"):
            assert row["trips"] == 3
    # exp under ``head`` and sin under ``loss`` fuse: no common entry, so the
    # fusion is the heavier scope's and marked mixed
    mixed = [v for v in insts.values() if v["mixed"]]
    assert len(mixed) == 1 and mixed[0]["scope"] in ("head", "loss")
    assert mixed[0]["bytes"] == 4 * 2 * 8 * 32
    rows = {(r["scope"], r["pass"]): r for r in table["scopes"]}
    assert rows["layer/attn/qkv", "fwd"]["flops"] == 3 * 2 * 8 * 16 * 16
    assert rows["unscoped", "fwd"]["flops"] == 2 * 8 * 16 * 32
    assert sum(r["mixed_bytes"] for r in table["scopes"]) == 4 * 2 * 8 * 32
    # a loop is a row of its own (its seconds are the loop's own), free
    assert [v for v in insts.values() if v["opcode"] == "while"
            and v["bytes"] == 0 and v["scope"] == "layer"]


_TPU_TEXT = """HloModule jit_step, is_scheduled=true

%fused_slice (p0: bf16[4,64,64], p1: s32[]) -> bf16[64,64] {
  %p0 = bf16[4,64,64]{2,1,0:T(8,128)(2,1)} parameter(0)
  %p1 = s32[]{:T(128)} parameter(1)
  %c0 = s32[]{:T(128)} constant(0)
  %ds = bf16[1,64,64]{2,1,0:T(8,128)(2,1)} dynamic-slice(%p0, %p1, %c0, %c0), dynamic_slice_sizes={1,64,64}
  ROOT %bc = bf16[64,64]{1,0:T(8,128)(2,1)} bitcast(%ds)
}

%fused_dot (q0: bf16[8,64], q1: bf16[4,64,64], q2: s32[]) -> bf16[8,64] {
  %q0 = bf16[8,64]{1,0:T(8,128)(2,1)S(1)} parameter(0)
  %q1 = bf16[4,64,64]{2,1,0:T(8,128)(2,1)} parameter(1)
  %q2 = s32[]{:T(128)} parameter(2)
  %fusion.9 = bf16[64,64]{1,0:T(8,128)(2,1)} fusion(%q1, %q2), kind=kLoop, calls=%fused_slice
  %mul.1 = bf16[8,64]{1,0:T(8,128)(2,1)S(1)} multiply(%q0, %q0), metadata={op_name="jit(step)/layer/while/body/layer/norm/mul"}
  ROOT %convolution.1 = bf16[8,64]{1,0:T(8,128)(2,1)S(1)} convolution(%mul.1, %fusion.9), dim_labels=bf_io->bf, metadata={op_name="jit(step)/layer/while/body/layer/mlp/dot_general"}
}

%fused_write (r0: bf16[4,8,64], r1: bf16[8,64], r2: s32[]) -> bf16[4,8,64] {
  %r0 = bf16[4,8,64]{2,1,0:T(8,128)(2,1)} parameter(0)
  %r1 = bf16[8,64]{1,0:T(8,128)(2,1)S(1)} parameter(1)
  %r2 = s32[]{:T(128)} parameter(2)
  %c1 = s32[]{:T(128)} constant(0)
  %b1 = bf16[1,8,64]{2,1,0:T(8,128)(2,1)} bitcast(%r1)
  ROOT %dus = bf16[4,8,64]{2,1,0:T(8,128)(2,1)} dynamic-update-slice(%r0, %b1, %r2, %c1, %c1), metadata={op_name="jit(step)/layer/while/body/layer/attn/layer/attn/kv_write/dynamic_update_slice"}
}

%body (arg: (s32[], bf16[8,64], bf16[4,64,64], bf16[4,8,64])) -> (s32[], bf16[8,64], bf16[4,64,64], bf16[4,8,64]) {
  %arg = (s32[]{:T(128)}, bf16[8,64]{1,0:T(8,128)(2,1)S(1)}, bf16[4,64,64]{2,1,0:T(8,128)(2,1)}, bf16[4,8,64]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%arg), index=0
  %x = bf16[8,64]{1,0:T(8,128)(2,1)S(1)} get-tuple-element(%arg), index=1
  %w = bf16[4,64,64]{2,1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=2
  %kv = bf16[4,8,64]{2,1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=3
  %fusion.1 = bf16[8,64]{1,0:T(8,128)(2,1)S(1)} fusion(%x, %w, %i), kind=kOutput, calls=%fused_dot, metadata={op_name="jit(step)/layer/while/body/layer/mlp/dot_general"}
  %fusion.2 = bf16[4,8,64]{2,1,0:T(8,128)(2,1)} fusion(%kv, %fusion.1, %i), kind=kLoop, calls=%fused_write, metadata={op_name="jit(step)/layer/while/body/layer/attn/layer/attn/kv_write/dynamic_update_slice"}
  %walk.3 = bf16[8,64]{1,0:T(8,128)(2,1)S(1)} custom-call(%fusion.1, %fusion.2), custom_call_target="tpu_custom_call", output_to_operand_aliasing={}, metadata={op_name="jit(step)/layer/while/body/layer/attn/layer/attn/core/pallas_call"}
  %one = s32[]{:T(128)} constant(1)
  %next = s32[]{:T(128)} add(%i, %one), metadata={op_name="jit(step)/layer/while/body/add"}
  ROOT %out = (s32[]{:T(128)}, bf16[8,64]{1,0:T(8,128)(2,1)S(1)}, bf16[4,64,64]{2,1,0:T(8,128)(2,1)}, bf16[4,8,64]{2,1,0:T(8,128)(2,1)}) tuple(%next, %walk.3, %w, %fusion.2)
}

%cond (carg: (s32[], bf16[8,64], bf16[4,64,64], bf16[4,8,64])) -> pred[] {
  %carg = (s32[]{:T(128)}, bf16[8,64]{1,0:T(8,128)(2,1)S(1)}, bf16[4,64,64]{2,1,0:T(8,128)(2,1)}, bf16[4,8,64]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %n = s32[]{:T(128)} constant(4)
  %j = s32[]{:T(128)} get-tuple-element(%carg), index=0
  ROOT %lt = pred[]{:T(512)} compare(%j, %n), direction=LT
}

ENTRY %main (a: bf16[8,64], b: bf16[4,64,64], c: bf16[4,8,64]) -> bf16[8,64] {
  %a = bf16[8,64]{1,0:T(8,128)(2,1)} parameter(0)
  %b = bf16[4,64,64]{2,1,0:T(8,128)(2,1)} parameter(1)
  %c = bf16[4,8,64]{2,1,0:T(8,128)(2,1)} parameter(2)
  %z = s32[]{:T(128)} constant(0)
  %copy.7 = bf16[8,64]{1,0:T(8,128)(2,1)S(1)} copy(%a)
  %t = (s32[]{:T(128)}, bf16[8,64]{1,0:T(8,128)(2,1)S(1)}, bf16[4,64,64]{2,1,0:T(8,128)(2,1)}, bf16[4,8,64]{2,1,0:T(8,128)(2,1)}) tuple(%z, %copy.7, %b, %c)
  %while.1 = (s32[]{:T(128)}, bf16[8,64]{1,0:T(8,128)(2,1)S(1)}, bf16[4,64,64]{2,1,0:T(8,128)(2,1)}, bf16[4,8,64]{2,1,0:T(8,128)(2,1)}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(step)/layer/while"}
  ROOT %res = bf16[8,64]{1,0:T(8,128)(2,1)S(1)} get-tuple-element(%while.1), index=1
}
"""


def test_the_table_counts_what_a_tpu_schedule_touches_not_its_buffers():
    """A hand-written text in the TPU's print: the trip count off the
    condition's constant, a layer sliced out of its stack inside a NESTED
    fusion, an in-place write, arrays the compiler keeps outside HBM, a
    Pallas call, a norm fused into the product it feeds, a compiler's copy."""
    table = hlo_text.scope_table(_TPU_TEXT)
    i = table["instructions"]
    assert table["module"] == "jit_step" and table["unknown_trips"] == []
    assert {k: v["trips"] for k, v in i.items()} == {
        "copy.7": 1, "while.1": 1, "fusion.1": 4, "fusion.2": 4, "walk.3": 4,
        "next": 4}
    # the product's: the norm in front of it is its own; of the [4, 64, 64]
    # stack ONE layer is read (2 x 64 x 64 B), the scalar index is 4 B, the
    # activations live on the chip
    assert (i["fusion.1"]["scope"], i["fusion.1"]["mixed"]) \
        == ("layer/mlp", False)
    assert i["fusion.1"]["flops"] == 2 * 8 * 64 * 64
    assert i["fusion.1"]["bytes"] == 2 * 64 * 64 + 4
    assert i["fusion.1"]["onchip_bytes"] == 2 * (2 * 8 * 64)
    # the write touches its update (on the chip: 0 B read from HBM) and
    # writes [1, 8, 64], never the [4, 8, 64] buffer
    assert i["fusion.2"]["scope"] == "layer/attn/kv_write"
    assert i["fusion.2"]["bytes"] == 2 * 8 * 64 + 4
    assert i["walk.3"]["kernel"] == "walk" \
        and i["walk.3"]["scope"] == "layer/attn/core"
    assert i["walk.3"]["kernel_bytes"] == 2 * (8 * 64 + 4 * 8 * 64 + 8 * 64)
    assert i["walk.3"]["bytes"] == 0
    # the compiler's copy of the input into fast memory feeds the loop
    assert i["copy.7"]["scope"] == "layer" and i["copy.7"]["inherited"]
    assert i["next"]["scope"] == "layer"
    rows = {r["scope"]: r for r in table["scopes"]}
    assert rows["layer/mlp"]["flops"] == 4 * 2 * 8 * 64 * 64
    assert rows["layer/attn/core"]["kernels"] == {"walk": 4}
    assert "unscoped" not in rows


# ---------------------------------------------------------------- the reader
def test_by_scope_sums_self_seconds_and_prints_what_no_table_names():
    ms = 1e6
    table = hlo_text.scope_table(_TPU_TEXT)
    table["program"] = "train_step"
    kind = "bf16[8,64]{1,0:T(8,128)(2,1)S(1)}"
    # two executions; in each the loop (10 ms) encloses 4 x (1 ms product +
    # 0.5 ms write + 0.25 ms kernel); the second also runs an instruction
    # the table does not have
    ops, names, kinds, modules = [], [], [], []
    for run, t0 in enumerate((100 * ms, 200 * ms)):
        modules.append((f"jit_step({7})", t0, t0 + 20 * ms))
        ops.append((t0 + 1 * ms, t0 + 2 * ms)); names.append("copy.7")
        ops.append((t0 + 2 * ms, t0 + 12 * ms)); names.append("while.1")
        for k in range(4):
            s = t0 + 2 * ms + k * 2.5 * ms
            for name, a, b in (("fusion.1", 0.0, 1.0), ("fusion.2", 1.0, 1.5),
                               ("walk.3", 1.5, 1.75)):
                ops.append((s + a * ms, s + b * ms)); names.append(name)
        if run:
            ops.append((t0 + 13 * ms, t0 + 15 * ms)); names.append("ghost.9")
    kinds = [kind] * len(ops)
    spans = [("cb.window", 90 * ms, 230 * ms)]
    got = device_scopes.by_scope(
        profile.Profile(ops, spans, modules, names, kinds),
        {"train_step": table})
    assert got["window_s"] == pytest.approx(0.140)
    assert got["busy_s"] == pytest.approx(2 * 0.011 + 0.002)
    (module, mod), = got["modules"].items()
    assert module == "jit_step(7)" and mod["program"] == "train_step"
    assert mod["calls"] == 2
    rows = {r["scope"]: r for r in mod["rows"]}
    assert rows["layer/mlp"]["seconds"] == pytest.approx(8 * 0.001)
    assert rows["layer/attn/kv_write"]["seconds"] == pytest.approx(8 * 0.0005)
    assert rows["layer/attn/core"]["kernels"] == {
        "walk": pytest.approx(8 * 0.00025)}
    # the loop's SELF time: 10 ms less the 7 ms its body's events cover
    assert rows["layer"]["seconds"] == pytest.approx(2 * (0.001 + 0.003))
    assert mod["not_in_table_s"] == pytest.approx(0.002)
    assert mod["not_in_table"] == [["ghost.9", pytest.approx(0.002)]]
    assert mod["unscoped_s"] == 0.0
    assert mod["scoped_s"] + mod["not_in_table_s"] \
        == pytest.approx(mod["busy_s"])
    # bytes a call x calls, hence GB/s: 8 products x (one layer + the index)
    assert rows["layer/mlp"]["bytes"] == 8 * (2 * 64 * 64 + 4)
    assert rows["layer/mlp"]["gb_s"] == pytest.approx(
        8 * (2 * 64 * 64 + 4) / 0.008 * 1e-9)
    assert rows["layer/mlp"]["tflop_s"] == pytest.approx(
        8 * 2 * 8 * 64 * 64 / 0.008 * 1e-12)
    text = device_scopes.render(got)
    assert "not in the table: ghost.9" in text and "layer/mlp" in text
    # without tables every second is printed as what it is
    bare = device_scopes.by_scope(
        profile.Profile(ops, spans, modules, names, kinds), {})
    (_, mod), = bare["modules"].items()
    assert mod["scoped_s"] == 0 and mod["not_in_table_s"] \
        == pytest.approx(mod["busy_s"])


def test_two_programs_of_one_module_name_are_told_apart_by_their_types():
    narrow = hlo_text.scope_table(_TPU_TEXT)
    wide = hlo_text.scope_table(_TPU_TEXT.replace("[8,64]", "[16,64]"))
    narrow["program"], wide["program"] = "prefill[1x8]", "prefill[1x16]"
    ops = [(0.0, 10.0), (20.0, 30.0)]
    prof = profile.Profile(
        ops, [], [("jit_step(1)", 0.0, 10.0), ("jit_step(2)", 20.0, 30.0)],
        ["fusion.1", "fusion.1"],
        ["bf16[16,64]{1,0:T(8,128)(2,1)S(1)}",
         "bf16[8,64]{1,0:T(8,128)(2,1)S(1)}"])
    got = device_scopes.by_scope(prof, {"a": narrow, "b": wide})["modules"]
    assert got["jit_step(1)"]["program"] == "prefill[1x16]"
    assert got["jit_step(2)"]["program"] == "prefill[1x8]"


def test_an_operand_behind_an_index_comment_keeps_its_place():
    got = hlo_text.parse(
        "  ROOT %f.1 = (bf16[2]{0}, s32[]) fusion(%a, %b.2, %c, %d, %e, "
        "/*index=5*/%w.880, %g), kind=kLoop, calls=%fused.3, "
        'metadata={op_name="jit(f)/head/dot_general"}')
    assert got["operands"] == ["a", "b.2", "c", "d", "e", "w.880", "g"]
    assert (got["name"], got["root"], got["opcode"]) == ("f.1", True,
                                                        "fusion")
    assert got["type"] == "(bf16[2]{0}, s32[])"
    assert hlo_text.array_bytes(
        "(bf16[4,8]{1,0:T(8,128)(2,1)S(1)}, f32[3]{0}, pred[])") == (13, 64)


def test_a_batched_product_printed_as_a_convolution_counts_its_own_macs():
    """The TPU prints ``bhqd,bhkd->bhqk`` as a convolution whose batch
    dimensions are SPATIAL ones under a base dilation of the window's size:
    every output position meets one tap, never the window's product."""
    text = """HloModule jit_f, is_scheduled=true

ENTRY %main (q: bf16[2,8,256,64], k: bf16[2,8,512,64]) -> bf16[2,8,256,512] {
  %q = bf16[2,8,256,64]{3,2,1,0} parameter(0)
  %k = bf16[2,8,512,64]{3,2,1,0} parameter(1)
  %pad.1 = bf16[8]{0} custom-call(), custom_call_target="AllocateBuffer"
  ROOT %conv.3 = bf16[2,8,256,512]{3,2,1,0} convolution(%q, %k), window={size=2x8 stride=1x7 lhs_dilate=2x8}, dim_labels=01bf_01oi->01bf, metadata={op_name="jit(f)/layer/attn/layer/attn/core/dot_general"}
}
"""
    table = hlo_text.scope_table(text)
    conv = table["instructions"]["conv.3"]
    assert conv["flops"] == 2 * 2 * 8 * 256 * 512 * 64
    assert conv["scope"] == "layer/attn/core"
    assert conv["bytes"] == 2 * (2 * 8 * 256 * 64 + 2 * 8 * 512 * 64
                                 + 2 * 8 * 256 * 512)
    # XLA's own plumbing moves nothing
    assert table["instructions"]["pad.1"]["bytes"] == 0
    # a true window over a spatial dimension multiplies by its taps
    assert hlo_text._valid_taps(10, 8, 3, 1, 0, 1, 1) == 8 * 3
    assert hlo_text._valid_taps(1, 4, 4, 1, 3, 1, 1) == 4


def test_an_events_text_gives_its_serial_name_and_its_result_type():
    assert profile.instruction_head(
        "%fusion.16 = bf16[512]{0:T(512)(128)(2,1)S(1)} fusion(f32[512]{0} "
        "%get-tuple-element.36), kind=kLoop, calls=%fused_computation.7") \
        == ("fusion.16", "bf16[512]{0:T(512)(128)(2,1)S(1)}")
    name, kind = profile.instruction_head(
        "%while = (s32[]{:T(128)}, bf16[512,1024]{1,0}) while((s32[]{:T(128)},"
        " bf16[512,1024]{1,0}) %tuple.16), condition=%c, body=%b")
    assert name == "while" and kind.startswith("(s32[]")
    assert profile.self_times([(0.0, 10.0), (1.0, 4.0), (5.0, 6.0)]) \
        == [6.0, 3.0, 1.0]


# ------------------------------------------------- the engines' own records
@pytest.fixture(scope="module")
def served():
    """A tiny sampling engine that has served, and is closed."""
    deepspeed_tpu.comm.reset_topology()
    sentry.install_compile_listener()
    cfg = opt.OPTConfig(vocab_size=128, max_seq_len=64, num_layers=2,
                        num_heads=4, hidden_size=32, ffn_size=64)
    srv = deepspeed_tpu.init_serving(
        opt.build(cfg), config={"dtype": "fp32"}, slots=3, max_seq_len=64,
        block_size=8, prefill_chunk=16)
    srv.serve([Request(i, list(range(3, 12 + i)), 6, temperature=0.7,
                       top_p=0.9, seed=i) for i in range(3)])
    srv.close()
    return srv


def test_a_table_built_after_close_costs_no_trace_and_no_compile(served):
    programs = trace.kept("programs")
    assert programs is served.programs and isinstance(programs, Programs)
    assert "decode" in programs.records
    assert any(n.startswith("prefill[") for n in programs.records)
    # what is kept is abstract: no device array
    args, kwargs = programs.signature("decode")
    for leaf in jax.tree_util.tree_leaves((args, kwargs)):
        assert isinstance(leaf, jax.ShapeDtypeStruct)
    traces = served.sentry.traces
    before = sentry.backend_compiles()
    table = served.program_table("decode")
    assert sentry.backend_compiles() == before
    assert table["backend_compiles"] == 0 and table["program"] == "decode"
    assert table["module"] == "jit_decode_step" and table["build_s"] < 5.0
    assert served.sentry.traces == traces
    assert served.program_table("decode") is table        # built once
    found = {r["scope"] for r in table["scopes"]}
    assert {"head", "layer/mlp", "layer/attn/qkv", "layer/attn/kv_write",
            "layer/attn/core", "layer/attn/out", "embed", "sample/filter",
            "sample/draw", "sample/softmax", "sample/argmax"} <= found
    json.dumps(programs.tables())                   # a reader's JSON


def test_flops_report_prices_the_program_that_was_built(served):
    """PERF.md section 7 (19): on a sampling engine the priced signature has
    the sampler's operands; a program not yet called falls back to the
    hand-derived greedy body."""
    profiler = ServingFlopsProfiler(served)
    assert profiler.built("decode") == "decode"
    assert profiler.built("verify") is None
    got = profiler.profile_programs()["decode"]
    assert got["priced"] == "built" and got["flops_per_call"] > 0
    (params, cache, devtok, packed), _ = served.programs.signature("decode")
    layout = served._layouts["decode"]
    assert packed.shape == (layout.words,) and packed.dtype == np.int32
    greedy = profiler._abstract_args("decode")
    sampling = profiler._abstract_args("decode", sampling=True)
    assert len(sampling) == len(greedy) + 5     # the five sampling vectors
    # ... and they ride in the built program's one packed operand
    assert layout.words >= sum(int(np.prod(a.shape)) for a in sampling[2:])
    # lower(family, rung, sampling) keeps working for a caller without a
    # built program (chipbench/drivers/serve_ssm.py, test_chip_lowering.py)
    assert "stablehlo" in profiler.lower("decode", sampling=True).as_text()


def test_the_training_engine_records_its_step_and_tables_it():
    deepspeed_tpu.comm.reset_topology()
    sentry.install_compile_listener()
    cfg = gpt2.GPT2Config.tiny()
    data = [{"input_ids": np.random.randint(
        0, cfg.vocab_size, (cfg.max_seq_len,), dtype=np.int32)}
        for _ in range(32)]
    engine, *_ = deepspeed_tpu.initialize(
        model=gpt2.build(cfg), training_data=data, config={
            "train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "adam", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True}})
    engine.train_batch()
    before = sentry.backend_compiles()
    table = engine.program_table("train_step")
    assert sentry.backend_compiles() == before
    assert table["backend_compiles"] == 0
    assert trace.kept("programs") is engine.programs
    found = {(r["scope"], r["pass"]) for r in table["scopes"]}
    scopes_found = {s for s, _ in found}
    assert {"loss", "optim/update", "optim/cast", "grad/merge", "layer/mlp",
            "layer/attn/qkv", "layer/attn/core", "head", "embed"} \
        <= scopes_found
    assert ("layer/mlp", "bwd") in found and ("layer/mlp", "fwd") in found


# ------------------------------------ every program a cell runs, by its cell
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
ATTENTION = {"layer/attn/qkv", "layer/attn/kv_write", "layer/attn/out"}
EVERY = {"embed", "head", "layer", "layer/norm", "sample/filter",
         "sample/softmax", "sample/draw", "sample/argmax"} | ATTENTION
ROUTED = {"layer/moe/route", "layer/moe/gather", "layer/moe/experts",
          "layer/moe/combine"}
STATE = {"layer/state/step", "layer/state/gate"}
#: what a family's decode-side programs must name beyond ``EVERY``
REQUIRED = {
    "opt-1.3b": {"layer/attn/core", "layer/mlp"},
    "olmoe-1b-7b": {"layer/attn/core"} | ROUTED,
    "keye-vl2-30b-a3b": {"layer/attn/core", "layer/attn/select/score",
                         "layer/attn/select/select",
                         "layer/attn/select/read"} | ROUTED,
    "command-a-plus-05-2026": {"layer/attn/core", "layer/moe/shared"}
    | ROUTED,
    "mistral-small-4-119b-2603": {"layer/attn/core", "layer/attn/latent_up",
                                  "layer/moe/shared"} | ROUTED,
    "kimi-linear-48b-a3b": {"layer/attn/core", "layer/attn/latent_up",
                            "layer/state/conv", "layer/mlp"} | ROUTED | STATE,
    "granite-4.0-h-micro": {"layer/attn/core", "layer/state/conv",
                            "layer/mlp"} | STATE,
    "Brumby-14B-Base": {"layer/mlp"} | STATE,
    "dots3-note-prev": {"layer/attn/core", "layer/attn/latent_up",
                        "layer/attn/select/score", "layer/mlp"} | ROUTED,
    "GLM-5": {"layer/attn/core", "layer/attn/latent_up", "mtp", "mtp/join",
              "verdict"} | ROUTED,
    "ZAYA1-8B": {"layer/attn/core", "layer/state/conv", "layer/mlp"}
    | ROUTED,
}
TRAINING = {"embed", "head", "layer/norm", "layer/attn/qkv",
            "layer/attn/core", "layer/attn/out", "loss", "grad/merge",
            "optim/update"}
REQUIRED_TRAINING = {
    "gpt2-medium": {"layer/mlp"},
    "opt-1.3b": {"layer/mlp", "layer"},
    "smallthinker-21b-a3b": ROUTED | {"layer"},
}


def _scopes_of(lowered):
    """(scope, pass) of every location a lowered program names."""
    text = lowered.as_text(debug_info=True)
    # (a call of an inner jit — ``jnp.argmax`` — is located at its name
    # stack alone, an operation at its stack + its primitive: both are read)
    return {scopes.normalise(name + tail)
            for name in set(re.findall(r'loc\("([^"]+)"', text))
            for tail in ("", "/call")}


def _serving_cells():
    return sorted({w["config"] for w in BENCH["workloads"]
                   if w["traffic"].endswith("closed")
                   and not w["traffic"].startswith("train")})


@pytest.mark.parametrize("config", _serving_cells())
def test_a_serving_familys_decode_names_the_required_leaves(config):
    cell = next(w["name"] for w in BENCH["workloads"]
                if w["config"] == config and "train" not in w["traffic"])
    spec = cb_run.load_cell(cell, rehearse=True)
    model = families.load(spec["config"]).build(spec["config"])
    deepspeed_tpu.comm.reset_topology()
    srv = deepspeed_tpu.init_serving(
        model, config={"dtype": "fp32"}, **spec["sizing"]["serving"])
    try:
        # the bodies are made, none is run
        (srv._get_round_fn if srv._self_draft else srv._get_decode_fn)()
        profiler = ServingFlopsProfiler(srv)
        found = set()
        for family in srv._program_bodies:
            if family in ("decode", "verify", "draft"):
                found |= {s for s, _ in _scopes_of(
                    profiler.lower(family, sampling=True))}
    finally:
        srv.close()
    want = (EVERY | REQUIRED[config]) - (
        ATTENTION if config == "Brumby-14B-Base" else set())
    if config == "Brumby-14B-Base":       # no K/V: its writes are the state's
        want |= {"layer/attn/qkv", "layer/attn/out"}
    assert want <= found, sorted(want - found)
    assert found <= set(scopes.VOCABULARY) | {scopes.UNSCOPED}


@pytest.mark.parametrize("cell", sorted(
    w["name"] for w in BENCH["workloads"] if "train" in w["traffic"]))
def test_a_training_cells_step_names_the_required_leaves(cell):
    spec = cb_run.load_cell(cell, rehearse=True)
    config = spec["cell"]["config"]
    model = families.load(spec["config"]).build(spec["config"],
                                                spec["sizing"].get("model"))
    deepspeed_tpu.comm.reset_topology()
    ds = dict(spec["sizing"]["ds_config"])
    ds["gradient_accumulation_steps"] = 2
    engine, *_ = deepspeed_tpu.initialize(model=model, config=ds)
    seq = int(spec["traffic"]["seq_len"])
    rows = engine.train_batch_size()
    batch = {"input_ids": np.zeros((rows, seq + 1), np.int32)}
    fn = engine._train_step_fn
    from deepspeed_tpu.telemetry import programs as programs_mod

    state = programs_mod.abstract(engine.state)
    shaped = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((2, rows // 2) + a.shape[1:],
                                       a.dtype), batch)
    found = _scopes_of(fn.lower(state, shaped, jax.random.PRNGKey(0)))
    names = {s for s, _ in found}
    want = TRAINING | REQUIRED_TRAINING[config]
    if ds.get("bf16", {}).get("enabled"):
        want |= {"optim/cast"}        # (a rehearsal may train in float32)
    assert want <= names, sorted(want - names)
    # (a checkpointed block is a function of its own in the lowered text,
    # located without the wrappers around its call: the passes of what is
    # inside it are read off the COMPILED text, where calls are inlined)
    assert {p for s, p in found if s.startswith("layer")} >= {"fwd", "bwd"}
    assert names <= set(scopes.VOCABULARY) | {scopes.UNSCOPED}
    if ds.get("zero_optimization", {}).get("stage") == 3 \
            and len(jax.devices()) > 1:
        assert {"zero/gather", "zero/reduce"} <= names
