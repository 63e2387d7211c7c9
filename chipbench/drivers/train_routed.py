"""``kind: train_routed`` — ``train_steps``' loop (optimizer steps through
``initialize`` / ``DeepSpeedEngine.train_batch`` on fresh seeded
random-token batches made by a host generator inside the window; one step
dispatched while the last one's loss is fetched; the window closed on a
fetched loss) for a model whose ``loss_fn`` returns a RECORD beside its
loss: the step's ``metrics["model"]`` (``runtime/engine.py``) is fetched
one step late, with the loss, into ``counters`` (``expert_rows``,
``experts_touched``, ``expert_rows_max``, ``expert_rows_absent``,
``router_aux``, ``lm_loss``: per-step means).

**What decides ``correct``** (set-up, at the published widths and the timed
sequence length, on two seeded ``seq_len + 1``-token rows and the engine's
own weights; ``check`` below):

(a) the program's logits — its uncached forward, the body and kernels the
    step differentiates, a row at a time, the final hidden state times the
    head at the compared positions — against the plain reference's at the
    first ``EDGE``, ``EDGE`` around the window's edge and the last ``EDGE``
    positions of each row: relative RMSE within
    ``serve_closed.LOGIT_REL_RMSE``.  A bf16 router breaks near-ties
    otherwise than a float32 one, so the reference takes the program's
    expert sets; of its own chosen experts ``EXPERT_AGREEMENT`` must be in
    them and no disagreeing expert may lie further than ``EXPERT_GAP`` of
    its token's largest score from the cut-off (PR 34's two limits, whose
    reasons ``serve_mixedattn.py`` gives; the numbers are this cell's own,
    below);
(b) the first step's loss (its mean over the micro-batches, the balance
    term included) against the reference's float32 loss on the same rows,
    within ``train_steps.LOSS_ABS_TOL``;
(c) the program's ``expert_rows`` for that batch against the pairs the
    reference's OWN sets put on held experts, within ``EXPERT_ROWS_SHARE``
    of the batch's pairs;
(d) **the first update itself.**  The first step runs on the two rows, each
    filling half of the global batch (``[r0, .., r0, r1, .., r1]``: under
    gradient accumulation the micro-batches' halves differ).  The gradient
    its optimizer was handed — read back exactly from Adam's first moment
    after that one step, ``mu / (1 - b1)``, so it is the accumulator's
    result and not a second computation — is held, LEAF BY LEAF (token
    table, q/k/v/o, router, the three expert stacks, norms, head), to the
    float32 reference's ``jax.grad`` of the same loss on the same rows at
    the weights before the update: ``|g - g_ref| / |g_ref|`` within
    ``GRAD_REL_ERR`` in every leaf.  A state left unchanged and a leaf whose
    gradient is zeroed read 1, half of the micro-batches dropped reads 0.6
    or more (``tests/chipbench/test_smallthinker.py`` plants the three).
    The weights' change is held to the stated optimizer: in the norm scales
    and the routers (the leaves small enough to keep a copy of) ``w1 - w0``
    against ``-lr g / (|g| + eps)``, Adam's first step at the sizing's
    ``lr``, within ``UPDATE_REL_ERR``; and the program's loss on the same
    batch is lower after the update (its second step reads it);
(e) nothing compiled in the window, no non-finite loss.

    python3 -m chipbench.drivers.train_routed --workload <cell> --seed N [--rehearse]

puts the plain reference and each shortcut of ``VARIANTS`` through (a)-(d)
on the cell's engine, a JSON line each, and exits 0 only if the plain
reference passes and every shortcut is refused by at least one limit.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import costs, traffic
from chipbench.drivers import serve_closed, train_steps
from chipbench.reference_smallthinker import VARIANTS

KIND = "train_routed"
WARM_STEPS = train_steps.WARM_STEPS
#: positions compared at each of a row's start, the window's edge, its end
EDGE = 128
#: of the reference's own chosen experts, the share the program chose too,
#: and how far a disagreeing expert may lie from the cut-off.  On the chip
#: (PR 47, 45 runs, 2 x 8,192 tokens x 4 layers x top-6 of 64 softmax
#: scores) the program reads 0.9925-0.9933 and 0.021-0.032; the reference
#: with its router's input rounded to float8 — the nearest precision below
#: the configuration's bf16 — reads 0.9714-0.9718 and 0.089-0.149 (six
#: readings), with every weight in float8 0.878 and 0.50, every other
#: shortcut under 0.95 and over 0.7.  Each limit lies between its two
#: readings: the gap at twice the program's largest and 0.73 of the
#: float8's smallest (a max over 4 M scores, so it has the more room above)
EXPERT_AGREEMENT, EXPERT_GAP = 0.985, 0.065
#: (c): of the batch's pairs, how many the two counts of held pairs may
#: differ by.  A flipped near-tie moves a pair across the held share's edge
#: or not at all; on the chip the sound runs read 1-171 of 393,216 pairs
#: (0.0004 at most), the shortcuts 60-3,323: this one tells no precision
#: and not every shortcut, which (a) and (d) do; it tells a counter that
#: counts another thing (every pair, or a micro-batch's for a step's)
EXPERT_ROWS_SHARE = 0.0015
#: (d): ``|g - g_ref| / |g_ref|`` a leaf, and the small leaves' ``w1 - w0``
#: against Adam's first step.  An unchanged state reads 1 in both.  On the
#: chip (PR 47, eleven runs) the bf16 step's largest leaf reads 0.077-0.081
#: (``experts_w1``: a ReLU gate whose bf16 pre-activation falls on the other
#: side of zero; the head 0.015, the final norm 0.007) and the update 5e-5;
#: the reference with every weight in float8 reads 0.42 (its head 0.19), the
#: architecture's shortcuts 0.42-2.9, half of the micro-batches dropped
#: 0.63-0.73 (the rehearsal's), a learning rate a tenth of the stated one
#: 0.9.  Each limit lies between the program's reading and the nearest
#: refused one, with three times of room above the reading
GRAD_REL_ERR, UPDATE_REL_ERR = 0.25, 0.02
#: leaves up to this many elements keep a host copy across the first update
SMALL_LEAF = 1 << 20
#: the record's fields the window keeps, as per-step means
RECORD = ("expert_rows", "experts_touched", "expert_rows_max",
          "expert_rows_absent", "router_aux", "lm_loss")


def positions(seq: int, window: int) -> List[int]:
    """The compared positions of a ``seq``-token row."""
    edge = min(EDGE, seq // 3)
    mid = min(max(window - edge // 2, edge), seq - 2 * edge)
    return sorted(set(range(edge)) | set(range(mid, mid + edge))
                  | set(range(seq - edge, seq)))


def build(job):
    """The cell's engine and its two seeded comparison rows."""
    import jax

    import deepspeed_tpu

    mix = job.traffic
    seq = int(mix["seq_len"])
    model = job.family.build(job.config, job.sizing.get("model"))
    vocab = costs.arch(job.config)["vocab"]
    n_dev = len(jax.devices())
    ds = dict(job.sizing["ds_config"])
    micro = int(ds["train_micro_batch_size_per_gpu"])
    per_micro = micro * n_dev * seq
    gas = max(1, int(mix["tokens_per_step"]) // per_micro)
    if gas * per_micro != int(mix["tokens_per_step"]) and not job.rehearse:
        raise ValueError(
            f"tokens_per_step {mix['tokens_per_step']} is not a whole "
            f"number of {micro} x {n_dev} x {seq}-token micro-batches")
    ds["gradient_accumulation_steps"] = gas
    # the engine keys its PRNG with a 32-bit seed and 0 means "default"
    ds["seed"] = job.seed % (2 ** 31 - 1) + 1
    with job.spans("cb.setup.initialize"):
        engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=ds)
        jax.block_until_ready(engine.state)
    rng = np.random.default_rng(traffic.seed_sequence(job.seed).spawn(1)[0])
    two = rng.integers(0, vocab, (2, seq + 1), dtype=np.int32)
    return model, engine, two, {"seq": seq, "vocab": vocab, "micro": micro,
                                "gas": gas, "chips": n_dev,
                                "bf16": bool(ds.get("bf16", {})
                                             .get("enabled"))}


def program_side(job, model, engine, two, shape) -> Dict[str, Any]:
    """The PROGRAM's logits at the compared positions and its expert sets
    on the two rows, at the weights before the first update."""
    import jax.numpy as jnp

    seq = shape["seq"]
    at = positions(seq, costs.arch(job.config)["window"])
    dtype = "bf16" if shape["bf16"] else "fp32"
    with job.spans("cb.setup.program_forward"):
        hidden, chosen = job.family.program_hidden(
            model, engine.state["params"], two[:, :-1], dtype)
        head = engine.state["params"]["lm_head"].astype(hidden.dtype)
        got = np.asarray((hidden[:, jnp.asarray(at)] @ head)
                         .astype(jnp.float32))
        chosen = np.asarray(chosen)
        del hidden
    return {"at": at, "logits": got, "experts": chosen}


def reference_before(job, engine, two, side, variant=None) -> Dict[str, Any]:
    """The reference's readings at the weights BEFORE the first update: its
    logits at the compared positions and its loss with its gradient, both
    taking the program's expert sets; the gradient on the host (the step's
    own temporaries need the room)."""
    import jax

    forced = {"experts": side["experts"]}
    with job.spans("cb.setup.reference"):
        want, report = job.family.logits(
            job.config, engine.state["params"], two[:, :-1], at=side["at"],
            forced=forced, variant=variant)
        loss, _, grads = job.family.next_token_loss(
            job.config, engine.state["params"], two, variant=variant,
            report=True, forced=forced, grad=True)
        grads = jax.device_get(grads)
    return {"logits": np.asarray(want, np.float32), "report": report,
            "loss": float(loss), "grads": grads}


def _named(tree) -> Dict[str, Any]:
    """A pytree's leaves by dotted path."""
    import jax

    return {".".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def first_steps(job, engine, two) -> Dict[str, Any]:
    """Two steps on the two rows, each filling half of the global batch:
    their losses and records and, read between them, the gradient the first
    update was made from (Adam's first moment after one step is ``(1 - b1)
    g``) and that update in the small leaves."""
    import jax
    import optax

    rows = engine.train_batch_size()
    if rows % 2:
        raise ValueError(f"a global batch of {rows} rows has no two halves")
    batch = {"input_ids": np.repeat(two, rows // 2, axis=0)}
    small = {name: np.asarray(leaf)
             for name, leaf in _named(engine.state["params"]).items()
             if leaf.size <= SMALL_LEAF}
    losses, records = [], []
    for step in range(2):
        with job.spans("cb.setup.warm_steps"):
            _, m = engine.train_batch(batch)
            losses.append(float(m["loss"]))
            records.append({k: float(v) for k, v in m["model"].items()})
        if step:
            break
        adam = [s for s in jax.tree_util.tree_leaves(
            engine.state["opt_state"],
            is_leaf=lambda s: isinstance(s, optax.ScaleByAdamState))
            if isinstance(s, optax.ScaleByAdamState)]
        b1 = job.sizing["ds_config"]["optimizer"]["params"].get(
            "betas", (0.9, 0.999))[0]
        grads = {name: np.asarray(mu) / (1.0 - b1)
                 for name, mu in _named(adam[0].mu).items()}
        moved = {name: np.asarray(leaf) - small[name]
                 for name, leaf in _named(engine.state["params"]).items()
                 if name in small}
    return {"losses": losses, "records": records, "batch": batch,
            "rows": rows, "grads": grads, "moved": moved}


def _rel_err(got, want) -> float:
    """``|got - want| / |want|`` over arrays or lists of them; 1 where
    ``want`` is all zero (a gradient of zero predicts no update to hold the
    weights' change to: an unchanged state reads 1 here too)."""
    got, want = (np.concatenate([np.ravel(a) for a in x])
                 if isinstance(x, list) else np.ravel(x)
                 for x in (got, want))
    norm = float(np.linalg.norm(want))
    return float(np.linalg.norm(got - want)) / norm if norm else 1.0


def check(job, shape, side, before, steps) -> Dict[str, Any]:
    """(a)-(d) of the module docstring from the readings -> a dict with
    ``ok`` and every number beside its limit."""
    precision = "bf16" if shape["bf16"] else "fp32"
    got, want = side["logits"], before["logits"]
    rel = float(np.sqrt(np.mean((got - want) ** 2)) / np.std(want))
    tol = serve_closed.LOGIT_REL_RMSE[precision]
    report = before["report"]
    agreed = report["experts"] >= EXPERT_AGREEMENT \
        and report["expert_gap"] <= EXPERT_GAP
    loss_tol = train_steps.LOSS_ABS_TOL[precision]
    loss0, loss1 = steps["losses"][:2]
    loss_diff = abs(loss0 - before["loss"])
    # the program counted the global batch: each of the two rows rows/2 times
    record = steps["records"][0]
    rows_prog = record["expert_rows"] * 2 / steps["rows"]
    rows_ref = report["expert_rows"]
    rows_tol = EXPERT_ROWS_SHARE * 2 / steps["rows"] \
        * (record["expert_rows"] + record["expert_rows_absent"])
    want_grads = _named(before["grads"])
    grad_err = {name: _rel_err(g, want_grads[name])
                for name, g in steps["grads"].items()}
    worst = float(np.max(list(grad_err.values())))    # a NaN leaf is the worst
    opt = job.sizing["ds_config"]["optimizer"]["params"]
    names = sorted(steps["moved"])
    step_err = _rel_err(
        [steps["moved"][n] for n in names],
        [-opt["lr"] * g / (np.abs(g) + opt.get("eps", 1e-8))
         for g in (steps["grads"][n] for n in names)])
    ok = bool(np.isfinite(got).all() and rel <= tol and agreed
              and loss_diff <= loss_tol
              and abs(rows_prog - rows_ref) <= rows_tol
              and worst <= GRAD_REL_ERR
              and step_err <= UPDATE_REL_ERR and loss1 < loss0)
    return {"ok": ok, "logit_rel_rmse": rel, "tolerance": tol,
            "experts": report["experts"],
            "expert_gap": report["expert_gap"],
            "first_loss": loss0, "reference_loss": before["loss"],
            "loss_diff": loss_diff, "loss_tolerance": loss_tol,
            "expert_rows": rows_prog, "reference_expert_rows": rows_ref,
            "expert_rows_tolerance": rows_tol,
            "grad_rel_err": worst,
            "grad_rel_err_limit": GRAD_REL_ERR,
            "grad_rel_err_by_leaf": grad_err,
            "update_rel_err": step_err,
            "update_rel_err_limit": UPDATE_REL_ERR,
            "program_fell": loss0 - loss1,
            "positions": int(got.shape[0] * got.shape[1])}


def compare(job, variants=(None,)):
    """The cell's engine, warm by two steps, and the comparison of the
    program with each of ``variants`` -> ``(engine, shape, the two steps'
    readings, {variant: verdict})``."""
    model, engine, two, shape = build(job)
    side = program_side(job, model, engine, two, shape)
    before = {v: reference_before(job, engine, two, side, v)
              for v in variants}
    steps = first_steps(job, engine, two)
    return engine, shape, steps, {
        v: check(job, shape, side, before[v], steps) for v in variants}


def run(job) -> Dict[str, Any]:
    import jax

    engine, shape, steps, verdicts = compare(job)
    verdict = verdicts[None]
    seq, micro, gas, n_dev = (shape[k] for k in ("seq", "micro", "gas",
                                                 "chips"))
    job.note("comparison " + json.dumps(verdict))
    losses = list(steps["losses"])
    with job.spans("cb.setup.warm_steps"):
        for _ in range(max(0, WARM_STEPS - len(losses))):
            _, m = engine.train_batch(steps["batch"])
            losses.append(float(m["loss"]))
    rows = steps["rows"]
    tokens_per_step = rows * seq

    batches = traffic.token_batches(job.seed, shape["vocab"], rows, seq + 1)
    step_losses: List[float] = []
    records: List[Dict[str, float]] = []
    started = 0
    compiles0 = job.compiles()
    t_open = time.perf_counter()
    job.window_opened(t_open)
    pending = None

    def fetch(m):
        with job.spans("cb.fetch_loss"):
            step_losses.append(float(m["loss"]))
            got = jax.device_get(m["model"])
            records.append({k: float(got[k]) for k in RECORD if k in got})

    while True:
        with job.spans("cb.make_batch"):
            batch = {"input_ids": next(batches)}
        with job.spans("cb.train_batch"):
            _, m = engine.train_batch(batch)
        started += 1
        if pending is not None:
            fetch(pending)
        pending = m
        since = time.perf_counter() - t_open
        job.tracer.poll(since)
        if since >= job.seconds:
            break
    fetch(pending)
    t_close = time.perf_counter()
    job.tracer.finish()
    compiles1 = job.compiles()

    window = t_close - t_open
    bad = sum(not math.isfinite(x) for x in step_losses)
    no_compile = compiles1 == compiles0 \
        and engine.sentry.retraces_observed == 0
    if not no_compile:
        job.note(f"compiled inside the window: backend compiles "
                 f"{compiles0} -> {compiles1}, retraces "
                 f"{engine.sentry.retraces_observed}")
    # the record is a sum over the step's micro-batches: counts stay sums
    # a step, the two losses become the micro-batches' mean
    means = {k: float(np.mean([r[k] for r in records])) for k in records[0]}
    for k in ("router_aux", "lm_loss"):
        if k in means:
            means[k] /= gas
    job.note(f"window {window:.3f} s: {len(step_losses)} steps of "
             f"{tokens_per_step} tokens (micro {micro} x {n_dev} chips x "
             f"gas {gas}), {bad} non-finite losses; loss first "
             f"{step_losses[0]:.4f} last {step_losses[-1]:.4f}; step "
             f"median {window / len(step_losses) * 1e3:.1f} ms; record a "
             f"step {json.dumps(means)}")
    held_pairs = means["expert_rows"] / tokens_per_step
    flops_per_step = job.family.train_flops_per_token(
        job.config, seq, held_pairs) * tokens_per_step
    return {
        "correct": bool(verdict["ok"] and no_compile and bad == 0),
        "attempted": started, "failed": int(bad),
        "end_to_end": {
            "train_tok_s": len(step_losses) * tokens_per_step / window},
        "window_s": window, "window": (t_open, t_close),
        "counters": {"steps": len(step_losses),
                     "tokens_per_step": tokens_per_step,
                     "micro_batch": micro, "gas": gas, "chips": n_dev,
                     "seq_len": seq, "rows_per_step": rows,
                     "flops_per_step": flops_per_step,
                     "warm_losses": losses, **means,
                     "comparison": verdict},
        "samples": {},
        "devices": list(jax.devices()),
    }


def controls(job) -> bool:
    """The plain reference and every shortcut of ``VARIANTS`` through
    :func:`check` on the cell's engine, a JSON line each; true if the plain
    comparison is ``ok`` and every shortcut is refused."""
    held = True
    for variant, verdict in compare(job, VARIANTS)[3].items():
        held &= verdict["ok"] == (variant is None)
        print(json.dumps({"seed": job.seed, "variant": variant, **verdict}),
              flush=True)
    return held


def main(argv: Optional[List[str]] = None) -> int:
    from deepspeed_tpu.utils.platform import enable_compile_cache

    from chipbench import run as cb

    ap = argparse.ArgumentParser(description=controls.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache(cb.ROOT)
    job = cb.Job(argparse.Namespace(
        seed=args.seed, seconds=0.0, rehearse=args.rehearse, trace=0,
        keep_trace=None), cb.load_cell(args.workload, args.rehearse))
    held = controls(job)
    print(json.dumps({"controls_held": held}), flush=True)
    return 0 if held else 1


if __name__ == "__main__":
    sys.exit(main())
