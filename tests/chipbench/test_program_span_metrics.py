"""The per-layer metrics that read the program's own spans
(``chipbench/layer_metrics/{sched_host_share,sched_host_ms,queue_wait_ms,
kv_host_ms,flash_share}.py``), on hand-countable synthetic events; and the
rule every Pallas kernel's name has to keep for ``trace_reduce`` to print
it whole.
"""

import ast
import glob
import os

import pytest

from chipbench import run as cb_run
from chipbench import trace_reduce
from chipbench.layer_metrics import _program_spans as ps

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
READERS = cb_run.layer_metric_readers()
EPOCH = 1000.0                     # the ring's epoch on perf_counter, s


def X(name, t0_ms, dur_ms, **args):
    return {"name": name, "ph": "X", "ts": t0_ms * 1e3, "dur": dur_ms * 1e3,
            "pid": 0, "tid": 0, "args": args}


def I(name, t_ms, **args):
    return {"name": name, "ph": "i", "s": "t", "ts": t_ms * 1e3, "pid": 0,
            "tid": 0, "args": args}


def ring_of(events, dropped=0):
    """Events in the order the ring would hold them: by END time."""
    return (sorted(events, key=lambda e: e["ts"] + e.get("dur", 0.0)),
            EPOCH, dropped)


def ctx_of(lo_ms, hi_ms, **more):
    return {"window": (EPOCH + lo_ms * 1e-3, EPOCH + hi_ms * 1e-3), **more}


#: three steps.  Step 1 (0-100 ms): prefill in flight 20-50, decode 40-90
#: (they overlap 40-50: the union is 70, not 80) -> 30 ms of host time.
#: Step 2 (100-200): decode in flight 110-190 and the first 5 ms of a swap
#: 195-210 that straddles the step boundary -> 15 ms.  Step 3 (200-260):
#: the swap's other 10 ms (clipped to 200-210) and a decode 215-255 ->
#: 60 - 10 - 40 = 10 ms.
STEPS = [
    X("step", 0, 100, iteration=1, kv_s=0.004, step=1),
    X("step.admit", 0, 10, step=1), X("step.prefill", 10, 45, step=1),
    X("step.decode", 55, 40, step=1), X("step.post", 95, 5, step=1),
    X("prefill", 20, 30, step=1), X("decode", 40, 50, step=1),
    X("step", 100, 100, iteration=2, kv_s=0.001, step=2),
    X("step.admit", 100, 5, step=2), X("step.prefill", 105, 1, step=2),
    X("step.decode", 106, 90, step=2), X("step.post", 196, 4, step=2),
    X("decode", 110, 80, step=2),
    X("step", 200, 60, iteration=3, kv_s=0.010, step=3),
    X("swap", 195, 15, step=3), X("decode", 215, 40, step=3),
]


@pytest.fixture
def ring(monkeypatch):
    def put(events, dropped=0):
        monkeypatch.setattr(ps, "serve_ring",
                            lambda: ring_of(events, dropped))
    return put


def test_self_time_is_the_step_minus_the_union_of_in_flight_spans(
        ring, capsys):
    ring(STEPS)
    ctx = ctx_of(-1, 1000)
    # (30 + 15 + 10) / (100 + 100 + 60)
    assert READERS["sched_host_share"](ctx) == \
        pytest.approx(100 * 55 / 260)
    assert READERS["sched_host_ms"](ctx) == pytest.approx(15.0)  # median
    assert READERS["kv_host_ms"](ctx) == pytest.approx(5.0)   # mean kv_s
    line = capsys.readouterr().out
    # phase self time: step.prefill of step 1 is 10-55 with prefill+decode
    # in flight 20-55 -> 10 ms, of step 2 1 ms: median 5.5; step.decode of
    # step 1 55-95 with decode in flight until 90 -> 5, of step 2 106-196
    # minus 110-190 and 195-196 -> 9
    assert "step.prefill 5.500 / 5.500" in line
    assert "step.decode 7.000 / 7.000" in line
    assert "step.admit 7.500 / 7.500" in line
    assert "of 3 steps" in line


def test_steps_are_kept_by_where_they_start(ring):
    ring(STEPS)
    # the window opens at 50 ms: step 1 began before it and is left out
    ctx = ctx_of(50, 1000)
    assert READERS["sched_host_share"](ctx) == pytest.approx(100 * 25 / 160)
    assert READERS["sched_host_ms"](ctx) == pytest.approx(12.5)
    # ... and closes at 150: only step 2 starts inside
    ctx = ctx_of(50, 150)
    assert READERS["sched_host_share"](ctx) == pytest.approx(15.0)
    assert READERS["kv_host_ms"](ctx) == pytest.approx(1.0)
    # no step starts inside: nothing to read
    assert READERS["sched_host_ms"](ctx_of(300, 400)) is None


def test_a_ring_that_wrapped_inside_the_window_reads_as_nothing(ring):
    names = ("sched_host_share", "sched_host_ms", "kv_host_ms",
             "queue_wait_ms")
    events = STEPS + [I("submit", 120, uid="a"), I("admit", 121, uid="a")]
    # events were dropped, and the oldest one left ended at 10 ms, after
    # the window opened at 5 ms: part of the window is gone
    ring(events, dropped=7)
    assert all(READERS[n](ctx_of(5, 1000)) is None for n in names)
    # dropped, but the oldest one left ended before the window opened
    ring(events, dropped=7)
    assert all(READERS[n](ctx_of(60, 1000)) is not None for n in names)
    # no ring at all (a program without one), or an empty one
    ring([])
    assert all(READERS[n](ctx_of(5, 1000)) is None for n in names)


def test_a_program_without_a_kept_ring_reads_as_nothing(monkeypatch):
    from deepspeed_tpu.telemetry import trace

    monkeypatch.delattr(trace, "kept", raising=False)
    assert ps.serve_ring() is None
    for n in ("sched_host_share", "sched_host_ms", "kv_host_ms",
              "queue_wait_ms"):
        assert READERS[n](ctx_of(0, 1000)) is None


def test_queue_wait_pairs_submit_with_the_next_admit_of_its_uid(
        ring, capsys):
    events = [I("submit", 10 * k, uid=str(k)) for k in range(1, 21)]
    # request k waits k ms; uid "1" was also admitted once BEFORE this
    # submit (an earlier life of the uid), which must not pair
    events += [I("admit", 10 * k + k, uid=str(k)) for k in range(1, 21)]
    events += [I("admit", 5, uid="1"), I("submit", 500, uid="late"),
               I("submit", 900, uid="outside"), I("admit", 950,
                                                  uid="outside")]
    ring(events)
    got = READERS["queue_wait_ms"](ctx_of(0, 600))
    # 20 waits of 1..20 ms: the 95th percentile, linear, is 19.05
    assert got == pytest.approx(19.05)
    line = capsys.readouterr().out
    assert "20 requests" in line and "(1 more never admitted" in line


def test_the_ngram_proposer_is_not_in_flight(ring):
    ring([X("step", 0, 100, iteration=1, kv_s=0.0, step=1),
          X("spec_propose", 10, 20, mode="ngram", step=1),
          X("spec_verify", 30, 60, step=1)])
    assert READERS["sched_host_ms"](ctx_of(-1, 200)) == pytest.approx(40.0)
    ring([X("step", 0, 100, iteration=1, kv_s=0.0, step=1),
          X("spec_propose", 10, 20, mode="draft", step=1),
          X("spec_verify", 30, 60, step=1)])
    assert READERS["sched_host_ms"](ctx_of(-1, 200)) == pytest.approx(20.0)


def test_flash_share_sums_the_flash_kernels_of_the_train_program():
    trace = {"window_s": 20.0, "device_ops": [
        ["jit_train_step:fusion", 9.0],
        ["jit_train_step:mosaic:flash_bwd_fused", 2.5],
        ["jit_train_step:mosaic:flash_fwd_resident", 1.0],
        ["jit_train_step:mosaic:qmm_w8a8_matmul", 4.0],
        ["jit_decode_step:mosaic:flash_fwd", 3.0],
        ["jit_train_step:copy", 1.0]]}
    assert READERS["flash_share.train"]({"trace": trace}) == \
        pytest.approx(100 * 3.5 / 20.0)
    # kernels named after the transform around them (the program before
    # its kernels had names): nothing to read
    trace["device_ops"] = [["jit_train_step:mosaic:checkpoint", 2.5],
                           ["jit_train_step:mosaic:jvp__", 1.0]]
    assert READERS["flash_share.train"]({"trace": trace}) is None
    assert READERS["flash_share.train"]({"trace": None}) is None


# ------------------------------------------------------------ kernel names
def kernel_names():
    """(file, line, name) of every ``pallas_call`` site under
    ``deepspeed_tpu/ops``; ``name`` is None where it is not a string
    constant."""
    out = []
    for path in sorted(glob.glob(os.path.join(
            ROOT, "deepspeed_tpu", "ops", "*.py"))):
        for node in ast.walk(ast.parse(open(path).read())):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "pallas_call":
                name = next((kw.value.value for kw in node.keywords
                             if kw.arg == "name"
                             and isinstance(kw.value, ast.Constant)), None)
                out.append((os.path.basename(path), node.lineno, name))
    return out


def name_survives(name) -> bool:
    """Whether a device trace prints the kernel under its whole name: XLA
    appends a serial number (``<name>.12``), which ``base_name`` strips
    together with ANY trailing digits and dots."""
    return isinstance(name, str) and bool(name) \
        and trace_reduce.base_name(f"%{name}.12") == name


def test_every_pallas_call_names_its_kernel_and_the_name_survives():
    sites = kernel_names()
    assert len(sites) >= 14
    for fname, line, name in sites:
        assert name_survives(name), f"{fname}:{line} name={name!r}"
    names = [n for _, _, n in sites]
    assert len(set(names)) == len(names), "two kernels share a name"
    families = {"decode_attention.py": ("decode_attn", "paged_"),
                "flash_attention.py": ("flash_",),
                "quantized_matmul.py": ("qmm_",)}
    for fname, _, name in sites:
        assert name.startswith(families[fname]), (fname, name)


@pytest.mark.parametrize("bad", ["flash_v2", "qmm_w8a8", "flash_fwd.", None,
                                 ""])
def test_a_name_the_reduction_would_cut_is_rejected(bad):
    assert not name_survives(bad)
    if bad:     # what the table would print instead
        assert trace_reduce.base_name(f"%{bad}.12") != bad
