"""``kind: serve_closed`` — N callers, each waiting for its reply before it
sends the next request, against one ``ServingEngine`` driven by hand
(``submit`` / ``step``), on one chip.

Set-up: weights on the device from the seed in one jitted call, in the
dtype they are served in; ``init_serving`` with the cell's sizing and the
program's defaults for everything else; the teacher-forced comparison with
the plain reference (``correct``); then every caller's first request, its
output cut to a seeded fraction so that callers are out of step.  The
window opens when every caller has had its first token: both programs have
then compiled and run.

Window: ``srv.step()`` in a loop.  Tokens only appear inside ``step()``,
so after each step the driver reads every live handle and stamps its new
tokens with ``time.perf_counter()`` — what a streaming client would see.
A finished request is checked and its caller submits the next.  When the
window has lasted ``--seconds`` no more is submitted; stepping goes on
(unmeasured) until every request submitted inside the window has its first
token, so the TTFT tail is the tail of all of them.
"""

from __future__ import annotations

import math
import statistics
import time
from typing import Any, Dict, List

import numpy as np

from chipbench import costs, traffic

KIND = "serve_closed"

#: bf16 engine vs float32 reference on the same weights: 24 layers of bf16
#: matmul/residual rounding (2^-9 relative each, accumulating like a random
#: walk) come to 1-3 % of the logit scale (1.16 % measured, PR 21); int8
#: anywhere on the path, or one wrong cache block, lands far above 5 %.
#: A float32 engine (rehearsal) must agree to rounding.
LOGIT_REL_RMSE = {"bf16": 5e-2, "fp32": 1e-4}
SCORE_DECODE_STEPS = 16
#: stepping after the window, for first tokens of requests sent inside it
DRAIN_LIMIT_S = 60.0


def paged_logits(srv, tokens: np.ndarray, n_decode: int) -> np.ndarray:
    """Teacher-forced logits through the engine's paged path (copied from
    ``chip_smoke.paged_logits``): chunked prefill of
    ``tokens[:, :-n_decode]``, then one decode step per remaining token, on
    the engine's own weights, cache layout and decode hooks.  Float32
    ``[B, n_chunks + n_decode, V]``: the logits after each prefill chunk's
    last token and after every decode token."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import paged_kv

    hooks = srv.engine.module.decode_hooks
    fwd, prepare = hooks["forward_cached"], srv.engine._prepare
    b, s = tokens.shape
    bs, chunk = srv.block_size, srv.prefill_chunk
    nbper = paged_kv.blocks_for(s, bs)
    cache = jax.eval_shape(lambda: hooks["init_cache"](
        1 + b * nbper, bs, srv.engine._config.jnp_dtype))
    cache = jax.tree_util.tree_map(
        lambda a: jax.device_put(jnp.zeros(a.shape, a.dtype),
                                 srv._pool_sharding), cache)
    bt = jnp.asarray(1 + np.arange(b * nbper).reshape(b, nbper), jnp.int32)

    @jax.jit
    def prefill(params, cache, ids, base, valid):
        return fwd(prepare(params), ids, cache, base, lengths=valid,
                   block_tables=bt)

    @jax.jit
    def decode(params, cache, tok, lengths):
        return fwd(prepare(params), tok, cache, 0, lengths=lengths,
                   block_tables=bt)

    params, rows = srv.engine.params, []
    n_prefill = s - n_decode
    with srv._tp_ctx():
        for base in range(0, n_prefill, chunk):
            valid = min(chunk, n_prefill - base)
            ids = np.zeros((b, chunk), np.int32)
            ids[:, :valid] = tokens[:, base:base + valid]
            logits, cache = prefill(
                params, cache, jnp.asarray(ids),
                jnp.full((b,), base, jnp.int32),
                jnp.full((b,), valid, jnp.int32))
            rows.append(np.asarray(logits, np.float32))
        for p in range(n_prefill, s):
            logits, cache = decode(params, cache,
                                   jnp.asarray(tokens[:, p:p + 1]),
                                   jnp.full((b,), p, jnp.int32))
            rows.append(np.asarray(logits, np.float32))
    return np.stack(rows, axis=1)


def check_logits(job, srv) -> Dict[str, Any]:
    """Engine vs the family's plain reference on ``slots`` seeded sequences
    of two prefill chunks plus ``SCORE_DECODE_STEPS`` decode steps."""
    vocab = costs.arch(job.config)["vocab"]
    chunk = srv.prefill_chunk
    s = 2 * chunk + SCORE_DECODE_STEPS
    rng = np.random.default_rng(traffic.seed_sequence(job.seed).spawn(1)[0])
    tokens = rng.integers(0, vocab, (srv.slots, s)).astype(np.int32)
    got = paged_logits(srv, tokens, SCORE_DECODE_STEPS)
    n_prefill = s - SCORE_DECODE_STEPS
    at = [min(base + chunk, n_prefill) - 1
          for base in range(0, n_prefill, chunk)]
    at += list(range(n_prefill, s))
    want = np.asarray(job.family.logits(job.config, srv.engine.params,
                                        tokens, at=at), np.float32)
    rmse = float(np.sqrt(np.mean((got - want) ** 2)))
    rel = rmse / float(np.std(want))
    tol = LOGIT_REL_RMSE[job.config["dtype"]]
    return {"ok": bool(np.isfinite(got).all() and rel <= tol),
            "logit_rel_rmse": rel, "tolerance": tol,
            "positions": int(got.shape[0] * got.shape[1])}


class _Client:
    """One caller: the request it waits on, and the stamps of that
    request's tokens."""

    def __init__(self):
        self.handle = None
        self.submitted = 0.0
        self.seen = 0
        self.stamps: List[float] = []
        self.in_window = False


def run(job) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.inference.serving import Request

    mix, sizing = job.traffic, job.sizing["serving"]
    clients_n = int(mix["clients"])
    if clients_n > int(sizing["slots"]):
        raise ValueError(
            f"{clients_n} callers over {sizing['slots']} slots: a closed "
            "loop with more callers than slots queues at admission, which "
            "this driver does not stamp")
    model = job.family.build(job.config, job.sizing.get("model"))
    dtype = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[job.config["dtype"]]

    with job.spans("cb.setup.weights"):
        params = jax.jit(lambda key: jax.tree_util.tree_map(
            lambda a: a.astype(dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a,
            model.init_fn(key)))(jax.random.PRNGKey(job.seed))
        jax.block_until_ready(params)
    with job.spans("cb.setup.init_serving"):
        srv = deepspeed_tpu.init_serving(
            model, config={"dtype": job.config["dtype"]}, params=params,
            **sizing)
        del params
        jax.block_until_ready((srv.engine.params, srv._cache))
    with job.spans("cb.setup.check_logits"):
        check = check_logits(job, srv)
    job.note(f"teacher-forced logits vs float32 reference: relative RMSE "
             f"{check['logit_rel_rmse']:.5f} (tolerance "
             f"{check['tolerance']}) over {check['positions']} positions")

    vocab = costs.arch(job.config)["vocab"]
    stream = traffic.RequestStream(mix, vocab, job.seed)
    clients = [_Client() for _ in range(clients_n)]
    failed = 0
    ttfts: List[float] = []          # of requests submitted in the window
    gaps: List[float] = []           # between tokens stamped in the window
    state = {"open": None, "close": None, "tokens": 0, "attempted": 0,
             "first_tokens": 0, "kv_tokens": 0, "decode_samples": 0}
    pool_used: List[float] = []

    def submit(c: _Client, cut: float = 1.0) -> None:
        nonlocal failed
        r = next(stream)
        r["max_new_tokens"] = max(1, math.ceil(r["max_new_tokens"] * cut))
        c.seen, c.stamps = 0, []
        c.in_window = state["open"] is not None and state["close"] is None
        if c.in_window:
            state["attempted"] += 1
        c.submitted = time.perf_counter()
        try:
            c.handle = srv.submit(Request(**r))
        except Exception as e:  # refused: counted, the caller retries next
            job.note(f"submit refused: {type(e).__name__}: {e}")
            c.handle = None
            failed += c.in_window

    def harvest(now: float) -> None:
        """Stamp what the last step emitted; finish and resubmit."""
        nonlocal failed
        measuring = state["open"] is not None and state["close"] is None
        for c in clients:
            h = c.handle
            if h is None:
                if state["close"] is None:
                    submit(c)
                continue
            toks = h.tokens()
            new = len(toks) - c.seen
            if new:
                if c.seen == 0 and c.in_window:
                    ttfts.append(now - c.submitted)
                if measuring:
                    state["tokens"] += new
                    state["first_tokens"] += c.seen == 0
                    if c.stamps and c.stamps[-1] >= state["open"]:
                        gaps.append(now - c.stamps[-1])
                        gaps.extend([0.0] * (new - 1))
                c.stamps.extend([now] * new)
                c.seen = len(toks)
            if h.done:
                want = h.request.max_new_tokens
                good = (h.status == "finished" and len(toks) == want
                        and all(0 <= t < vocab for t in toks))
                if not good:
                    failed += 1
                    job.note(f"request {h.uid}: status {h.status}, "
                             f"{len(toks)} of {want} tokens")
                c.handle = None
                if state["close"] is None:
                    submit(c)

    def step() -> None:
        with job.spans("cb.step"):
            srv.step()
        now = time.perf_counter()
        with job.spans("cb.harvest"):
            harvest(now)
        if state["open"] is not None and state["close"] is None:
            pool_used.append(srv._alloc.blocks_in_use)
            live = [c for c in clients if c.handle is not None and c.seen]
            if live:
                state["kv_tokens"] += sum(
                    c.handle.request.prompt.size + c.seen for c in live)
                state["decode_samples"] += 1

    # warm-in: every caller's first request, cut to a seeded fraction
    with job.spans("cb.setup.warm_in"):
        first_done = [False] * clients_n
        for c, frac in zip(clients, stream.warm_in_fractions(clients_n)):
            submit(c, cut=frac)
        firsts = [c.handle for c in clients]
        t_warm = time.perf_counter()
        while not all(first_done):
            step()
            for i, h in enumerate(firsts):
                first_done[i] = first_done[i] or h is None \
                    or len(h.tokens()) > 0
            if time.perf_counter() - t_warm > 900:
                raise RuntimeError("warm-in did not finish in 900 s")

    before = srv.stats()
    compiles0 = job.compiles()
    state["open"] = t_open = time.perf_counter()
    job.window_opened(t_open)
    while True:
        step()
        since = time.perf_counter() - t_open
        job.tracer.poll(since)
        if since >= job.seconds:
            break
    state["close"] = t_close = time.perf_counter()
    job.tracer.finish()
    after = srv.stats()
    compiles1 = job.compiles()

    # unmeasured: first tokens of what was submitted inside the window
    t_drain = time.perf_counter()
    while any(c.handle is not None and c.in_window and c.seen == 0
              for c in clients):
        if time.perf_counter() - t_drain > DRAIN_LIMIT_S:
            late = sum(c.handle is not None and c.in_window and c.seen == 0
                       for c in clients)
            failed += late
            job.note(f"{late} requests had no first token {DRAIN_LIMIT_S} s "
                     "after the window: counted as failed")
            break
        step()

    window = t_close - t_open
    delta = {k: after[k] - before[k] for k in (
        "iterations", "decode_steps", "prefill_calls", "generated_tokens",
        "prompt_tokens", "prefix_hit_tokens", "evicted", "admitted",
        "compile_count")}
    no_compile = compiles1 == compiles0 and delta["compile_count"] == 0
    if not no_compile:
        job.note(f"compiled inside the window: backend compiles "
                 f"{compiles0} -> {compiles1}, engine compile_count "
                 f"+{delta['compile_count']}")
    job.note(f"window {window:.3f} s: {state['attempted']} requests "
             f"submitted, {len(ttfts)} first tokens, {len(gaps)} gaps, "
             f"{state['tokens']} tokens, {failed} failed; TTFT median "
             f"{_ms(statistics.median(ttfts)) if ttfts else None} ms over "
             f"{len(ttfts)} samples, ITL median "
             f"{_ms(statistics.median(gaps)) if gaps else None} ms over "
             f"{len(gaps)} samples; evicted {delta['evicted']}")

    e2e = {"serve_tok_s": state["tokens"] / window}
    if ttfts:
        e2e["ttft_p95_ms"] = _ms(_p95(ttfts))
    if gaps:
        e2e["itl_p95_ms"] = _ms(_p95(gaps))
    srv.close()
    return {
        "correct": bool(check["ok"] and no_compile),
        "attempted": state["attempted"], "failed": int(failed),
        "end_to_end": e2e, "window_s": window, "window": (t_open, t_close),
        "counters": {**delta, "slots": srv.slots,
                     "num_blocks": after["num_blocks"],
                     "block_size": after["block_size"],
                     "tokens_in_window": state["tokens"],
                     "first_tokens_in_window": state["first_tokens"],
                     "ttft_samples": len(ttfts), "itl_samples": len(gaps),
                     "ttft_median_ms": _ms(statistics.median(ttfts))
                     if ttfts else None,
                     "itl_median_ms": _ms(statistics.median(gaps))
                     if gaps else None,
                     "mean_valid_kv_tokens": state["kv_tokens"]
                     / max(1, state["decode_samples"]),
                     "logit_rel_rmse": check["logit_rel_rmse"]},
        "samples": {"blocks_in_use": pool_used},
        "devices": list(srv.engine.mesh.devices.flat),
    }


def _ms(seconds: float) -> float:
    return seconds * 1e3


def _p95(values: List[float]) -> float:
    """95th percentile, linear between order statistics."""
    return float(np.percentile(np.asarray(values, np.float64), 95))
