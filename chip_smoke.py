"""First-light smoke for the TPU: the main path, once, through the entry
points a user calls — and the quickest proof that the system still starts
on the chip.

    python chip_smoke.py            # on a machine with a TPU (chiprun)
    python chip_smoke.py --phases kernels,train   # a subset, while debugging

One process (it spawns nothing: a chip belongs to one process), six phases,
each printing ``ok`` or its exception; any failure makes the exit code
non-zero and suppresses the result line:

 - ``device``   what JAX found; that ``block_until_ready`` really waits.
 - ``kernels``  every Pallas kernel the two main phases use (and the
                speculative-verify kernel), Mosaic-compiled at the smoke's
                shapes, against the reference the repo already has.
 - ``serve``    ``init_serving`` of OPT-1.3B at its published widths, bf16,
                seeded random weights initialised on device, defaults for
                slots / block_size / prefill_chunk / prefix caching /
                sampling; a handful of requests through ``srv.serve``;
                teacher-forced logits through the paged cache against a
                float32 plain ``forward``.
 - ``serve-q``  the same widths with ``quantize="w8a8+kv8"`` (int8 KV pool
                + s8-MXU decode matmuls), judged by the repo's
                bounded-divergence contract.
 - ``serving-memory``  the decode and prefill programs compiled at the
                benchmark's chat-cell shapes (24 slots x 1,024): their
                ``memory_analysis().temp_size_in_bytes`` and the number of
                pool-slice-sized ``copy`` instructions, failing on a
                pool-sized temporary (the pool is updated in place).
 - ``train``    ``initialize`` of GPT-2 125M (flash v2 at 1024x1024 blocks,
                ``dots_flash`` remat, unrolled layers), three ``train_batch`` steps on one seeded
                batch; loss finite and falling.

On more than one chip the same phases run with the batch over ``dp=n``
(ZeRO-1 from two chips on, ZeRO-0 on one) and the serving engines at
``topology=n``, and the script checks that pool shards and bytes in use
are spread over every chip.

It refuses to run without a TPU — there is no CPU branch here.  The tier-1
tests import the phases and run them at tiny widths on the forced-CPU
platform so the script cannot rot (``tests/unit/test_tpu_smoke.py``).

The last line of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import time
import traceback
from typing import Any, Callable, Dict, List, Tuple

import numpy as np

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))

# ------------------------------------------------------------- tolerances
#: bf16 attention kernels vs a float32 reference over the SAME bf16 inputs:
#: the kernels accumulate in f32 and round once on the way out (flash also
#: rounds p to bf16 before p@v), so the gap is about one bf16 ulp of the
#: output — 2^-7 relative, absolute below 1.0.  A wrong mask, scale or
#: block walk shows up as O(0.1..1)
ATTN_TOL = 2.0 ** -7
#: flash backward: ds and p are rounded to bf16 before their matmuls (as in
#: every flash kernel), so gradients carry ~2^-8 relative noise per term;
#: bound the worst element against the gradient's own scale
FLASH_GRAD_REL = 2e-2
#: w8a8 kernel vs dequantize+matmul: the kernel also rounds ACTIVATIONS to
#: int8 per k-group (step = rowmax/127, error uniform in half a step ->
#: ~0.4% of the row scale per element, averaging down over K); 2% of the
#: output norm bounds it with room, a wrong layer/scale/group gives ~100%
W8A8_REL_FRO = 2e-2
#: bf16 engine vs float32 plain forward on the same weights: 24 layers of
#: bf16 matmul/residual rounding (2^-9 relative each, accumulating like a
#: random walk) is ~1-3% of the logit scale; int8 anywhere on the path or
#: a wrong cache read lands far above 5%
BF16_LOGIT_REL_RMSE = 5e-2
#: quantized engine: the repo's bounded-divergence contract
#: (tests/unit/quant_divergence.py, README "Quantized serving")
QUANT_LOGIT_RMSE = 0.15


@dataclasses.dataclass
class Sizes:
    """Everything that differs between the chip run and the CPU test."""
    opt: Any                       # OPTConfig served
    gpt2: Any                      # GPT2Config trained
    moe: Any = None                # MixtralConfig: a second head shape for
                                   # the paged kernels + the routed FFN
    dtype: str = "bf16"
    prompt_lens: Tuple[int, ...] = (5, 40, 130, 300)
    shared_prefix: int = 96        # block-aligned, shared by two requests
    new_tokens: Tuple[int, ...] = (16, 24, 32)
    score_len: int = 272           # teacher-forced: 2 chunks + decode tail
    score_decode: int = 16
    micro_bs: int = 32
    seq: int = 1024
    gas: int = 2
    sync_dim: int = 4096
    sync_iters: int = 100
    sampler_vocab: int = 262272    # the widest cell's row (ZAYA1-8B)
    serving_kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)


def full_sizes() -> Sizes:
    from deepspeed_tpu.models import gpt2, opt

    g = gpt2.GPT2Config.gpt2_125m()
    # the TPU kernel configuration of the training cells
    g.remat, g.use_flash, g.remat_policy = True, True, "dots_flash"
    g.scan_layers = False
    g.flash_block_q, g.flash_block_k = 1024, 1024
    from deepspeed_tpu.models import mixtral

    return Sizes(opt=opt.OPTConfig.opt_1_3b(), gpt2=g,
                 moe=mixtral.MixtralConfig.olmoe_1b_7b())


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ device
def phase_device(sz: Sizes, report: Dict[str, Any]) -> None:
    import jax
    import jax.numpy as jnp

    devs = jax.devices()
    report["device"] = {"platform": devs[0].platform,
                        "kind": devs[0].device_kind, "count": len(devs)}
    log(f"platform={devs[0].platform} device_kind={devs[0].device_kind} "
        f"count={len(devs)} jax={jax.__version__} "
        f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}")

    # does block_until_ready wait?  A long dependent chain: if the call
    # returned at enqueue time, the value fetch AFTER it would pay for the
    # whole computation instead of for one scalar's copy
    n, iters = sz.sync_dim, sz.sync_iters
    x = jnp.ones((n, n), jnp.bfloat16)

    @jax.jit
    def chain(x):
        return jax.lax.fori_loop(
            0, iters, lambda i, y: (y @ x) * jnp.bfloat16(1.0 / n), x)

    float(chain(x)[0, 0])                      # compile + warm
    t0 = time.perf_counter()
    y = chain(x)
    t_enq = time.perf_counter() - t0
    y.block_until_ready()
    t_blk = time.perf_counter() - t0
    v = float(y[0, 0])
    t_fetch = time.perf_counter() - t0
    log(f"sync: enqueue {t_enq * 1e3:.2f} ms, block_until_ready "
        f"{t_blk * 1e3:.2f} ms, value fetched {t_fetch * 1e3:.2f} ms")
    assert v == 1.0, v
    assert t_fetch - t_blk < 0.25 * t_fetch + 5e-3, (
        "block_until_ready returned before the device finished: "
        f"{t_blk:.4f}s vs {t_fetch:.4f}s to the fetched value")
    report["sync_ms"] = {"enqueue": round(t_enq * 1e3, 3),
                         "block": round(t_blk * 1e3, 3),
                         "fetch": round(t_fetch * 1e3, 3)}


# ----------------------------------------------------------------- kernels
def _close(name: str, got, want, tol: float) -> float:
    """max |got - want| / max(1, |want|) must stay within ``tol``."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want) / np.maximum(1.0, np.abs(want))))
    assert np.isfinite(err) and err <= tol, \
        f"{name}: max |kernel - reference| = {err:.5f} > {tol:.5f}"
    return err


def _mosaic(text: str, what: str, require: bool) -> int:
    """Count Mosaic custom calls in a lowered program; on a TPU a program
    that was meant to hold a Pallas kernel and holds none is a failure."""
    n = text.count("tpu_custom_call")
    if require:
        assert n > 0, f"{what}: no Mosaic tpu_custom_call in the lowered " \
                      "program — it took an XLA fallback"
    return n


def phase_kernels(sz: Sizes, report: Dict[str, Any]) -> None:
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import decode_attention as da
    from deepspeed_tpu.ops import flash_attention as fa
    from deepspeed_tpu.ops import paged_kv
    from deepspeed_tpu.ops import quantization as quant
    from deepspeed_tpu.ops import quantized_matmul as qmm
    from deepspeed_tpu.utils.platform import on_tpu

    require = on_tpu()
    out: Dict[str, Any] = {}
    cfg = sz.opt
    slots = 8                                      # init_serving defaults
    bs = sz.serving_kwargs.get("block_size", 32)
    keys = jax.random.split(jax.random.PRNGKey(0), 8)

    def f32_pool(p):
        if paged_kv.is_quantized_pool(p):
            return quant.dequantize_kv(p["qp"], p["ps"], jnp.float32)
        return p.astype(jnp.float32)

    def exact(fn, *args):
        """A float32 reference: full-precision matmuls.  Only references
        are traced under this — inside it the kernels' own dots would be
        lowered at another precision than the programs being checked."""
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)

    checks: List[Tuple[str, Callable[[], Dict[str, float]]]] = []

    def as_engine_holds_it(p):
        """A stacked pool (layer 0 zeros, ``p`` at layer 1), lane-packed
        (``g = 128 // hd`` spans a row: ``g = 1`` at head_dim 128)."""
        return paged_kv.pack_pool(jax.tree_util.tree_map(
            lambda a: jnp.stack([jnp.zeros_like(a), a]), p))

    def paged_checks(tag, h, hd, ctx, kinds):
        """Paged attention at one head shape: the decode (T=1) and verify
        (T=4) kernels, and the prefill kernel a chunk takes through the
        dispatcher, each against the float32 reference."""
        nbper = paged_kv.blocks_for(ctx, bs)
        nb = 1 + slots * nbper
        rng = np.random.default_rng(0)
        # every row owns a shuffled set of physical blocks (0 = scratch)
        bt = jnp.asarray(
            1 + rng.permutation(slots * nbper).reshape(slots, nbper),
            jnp.int32)
        # first token, a block edge, mid-block, the last position, the rest
        pos = jnp.asarray(([0, bs - 1, bs, ctx - 4]
                           + list(rng.integers(1, ctx - 4, slots)))[:slots],
                          jnp.int32)
        kf = jax.random.normal(keys[0], (nb, h, bs, hd), jnp.float32)
        vf = jax.random.normal(keys[1], (nb, h, bs, hd), jnp.float32)
        pools = {"bf16": (kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16))}
        if "kv8" in kinds:
            qk, sk = quant.quantize_kv(kf, paged_kv.SCALE_DTYPE)
            qv, sv = quant.quantize_kv(vf, paged_kv.SCALE_DTYPE)
            pools["kv8"] = ({"qp": qk, "ps": sk}, {"qp": qv, "ps": sv})

        def paged(t, kernel, kind, q_pos):
            kp, vp = pools[kind]
            q = jax.random.normal(keys[2], (slots, h, t, hd), jnp.bfloat16)
            # the kernel reads the whole pool at a (non-zero) layer index;
            # the reference reads that layer's pool alone, unpacked, float32
            fn = jax.jit(lambda q, kp, vp, bt, pos: kernel(
                q, kp, vp, bt, pos, layer=1))
            kps, vps = as_engine_holds_it(kp), as_engine_holds_it(vp)
            _mosaic(fn.lower(q, kps, vps, bt, q_pos).as_text(),
                    f"paged T={t} {kind} {tag}", require)
            want = exact(
                lambda q, kp, vp: da.paged_decode_attention_reference(
                    q.astype(jnp.float32), f32_pool(kp), f32_pool(vp), bt,
                    q_pos), q, kp, vp)
            return {f"paged_T{t}_{kind}{tag}": _close(
                f"paged attention T={t} {kind} {tag}",
                fn(q, kps, vps, bt, q_pos), want, ATTN_TOL)}

        for t, kernel in ((1, da.paged_decode_attention_pallas),
                          (4, da.paged_verify_attention_pallas)):
            for kind in pools:
                checks.append((f"paged T={t} {kind} {tag}",
                               lambda t=t, kernel=kernel, kind=kind:
                               paged(t, kernel, kind, pos)))
        # a prefill chunk: on a TPU the dispatcher sends T > VERIFY_T_MAX
        # to the prefill kernel, every row walking its own valid blocks
        t = sz.serving_kwargs.get("prefill_chunk", 128)
        base = jnp.minimum(pos, ctx - t)
        checks.append((f"paged prefill T={t} {tag}", lambda: paged(
            t, da.paged_decode_attention, "bf16", base)))

    paged_checks("", cfg.num_heads, cfg.head_dim, cfg.max_seq_len,
                 ("bf16", "kv8"))
    if sz.moe is not None:
        m = sz.moe
        paged_checks(f"_h{m.num_kv_heads}x{m.head_dim}", m.num_kv_heads,
                     m.head_dim, min(m.max_seq_len, 1024), ("bf16",))

        def routed():
            """The dropless routed FFN at one decode step's shape (64 rows,
            top-k of E) against a dense float32 loop over experts."""
            from deepspeed_tpu.moe import routed as moe_routed

            d, f, e, k = m.hidden_size, m.ffn_size, m.num_experts, m.top_k
            ks = jax.random.split(keys[3], 5)
            y = jax.random.normal(ks[0], (64, 1, d), jnp.bfloat16)
            gate_w = (jax.random.normal(ks[1], (d, e)) * 0.02).astype(
                jnp.bfloat16)
            w1, w3 = ((jax.random.normal(kk, (e, d, f)) * 0.02).astype(
                jnp.bfloat16) for kk in ks[2:4])
            w2 = (jax.random.normal(ks[4], (e, f, d)) * 0.02).astype(
                jnp.bfloat16)
            fn = jax.jit(lambda *a: moe_routed.routed_ffn(
                *a, k, m.norm_topk_prob))
            got, record = fn(y, gate_w, w1, w3, w2)

            def dense(y, gate_w, w1, w3, w2):
                x = y.reshape(-1, d).astype(jnp.float32)
                p, idx = moe_routed.route(y.reshape(-1, d), gate_w, k,
                                          m.norm_topk_prob)
                weight = jnp.zeros((x.shape[0], e)).at[
                    jnp.arange(x.shape[0])[:, None], idx].set(p)

                def one(i, acc):
                    a, b, c = (t[i].astype(jnp.float32)
                               for t in (w1, w3, w2))
                    return acc + ((jax.nn.silu(x @ a) * (x @ b)) @ c) \
                        * weight[:, i, None]

                return jax.lax.fori_loop(0, e, one, jnp.zeros_like(x))

            want = np.asarray(exact(dense, y, gate_w, w1, w3, w2))
            got = np.asarray(got, np.float32).reshape(want.shape)
            rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
            # bf16 rounding of gate*up and of the output: 2^-8 each
            assert rel <= 2e-2, f"routed FFN: relative error {rel:.4f}"
            assert int(record[1]) == 64 * k, record
            return {"routed_ffn_rel": rel}

        checks.append(("routed FFN", routed))

    def flash(tag, batch, heads, hd, seq, **blocks):
        """flash forward + backward against the einsum reference: the
        GPT-2 train step's call (the blocks its config names) and a call
        that names none at the serving model's longest sequence (OPT-1.3B:
        S = 2048, hd 64 — the blocks ``flash_attention`` chooses)"""
        q, k, v = (jax.random.normal(kk, (batch, heads, seq, hd),
                                     jnp.bfloat16) for kk in keys[3:6])
        w = jax.random.normal(keys[6], q.shape, jnp.float32)

        def flash_loss(q, k, v):
            o = fa.flash_attention(q, k, v, causal=True, **blocks)
            return (o.astype(jnp.float32) * w).sum(), o

        def ref_loss(q, k, v):
            o = fa.mha_reference(q.astype(jnp.float32),
                                 k.astype(jnp.float32),
                                 v.astype(jnp.float32), causal=True)
            return (o * w).sum(), o

        fgrad = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2),
                                 has_aux=True))
        n_calls = _mosaic(fgrad.lower(q, k, v).as_text(),
                          f"flash fwd+bwd{tag}", require)
        assert not require or n_calls >= 2, n_calls
        (dq, dk, dv), o = fgrad(q, k, v)
        (rq, rk, rv), ro = exact(
            jax.grad(ref_loss, argnums=(0, 1, 2), has_aux=True), q, k, v)
        res = {f"flash{tag}_fwd": _close(f"flash forward{tag}", o, ro,
                                         ATTN_TOL)}
        for name, a, b in (("dq", dq, rq), ("dk", dk, rk), ("dv", dv, rv)):
            scale = float(jnp.max(jnp.abs(b)))
            res[f"flash{tag}_{name}_rel"] = _close(
                f"flash {name}{tag}", a / scale, b / scale, FLASH_GRAD_REL)
        return res

    g = sz.gpt2
    checks.append(("flash fwd+bwd", lambda: flash(
        "", 2, g.num_heads, g.head_dim, sz.seq, block_q=g.flash_block_q,
        block_k=g.flash_block_k)))
    checks.append((f"flash fwd+bwd S={cfg.max_seq_len} default blocks",
                   lambda: flash(f"_s{cfg.max_seq_len}", 1, cfg.num_heads,
                                 cfg.head_dim, cfg.max_seq_len)))

    def w8a8(k_dim, n_dim):
        """2-D kernel vs dequantize+matmul; stacked == 2-D exactly"""
        wk = jax.random.normal(keys[7], (2, k_dim, n_dim),
                               jnp.float32) * 0.02
        rec = quant.quantize_k_grouped(wk, k_group=min(128, k_dim))
        x = jax.random.normal(keys[2], (slots, k_dim), jnp.bfloat16)
        layer1 = {"qk": rec["qk"][1], "kscale": rec["kscale"][1]}
        one = jax.jit(lambda x, r: qmm.w8a8_matmul(
            x, r, out_dtype=jnp.float32))
        stk = jax.jit(lambda x, r, l: qmm.w8a8_matmul_stacked(
            x, r, l, out_dtype=jnp.float32))
        _mosaic(one.lower(x, layer1).as_text(), f"w8a8 {k_dim}x{n_dim}",
                require)
        _mosaic(stk.lower(x, rec, jnp.int32(1)).as_text(),
                f"w8a8 stacked {k_dim}x{n_dim}", require)
        got = np.asarray(one(x, layer1))
        want = np.asarray(exact(
            lambda x, r: x.astype(jnp.float32)
            @ quant.dequantize_k(r, jnp.float32), x, layer1))
        rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        assert rel <= W8A8_REL_FRO, \
            f"w8a8 {k_dim}x{n_dim}: relative error {rel:.4f}"
        np.testing.assert_array_equal(
            got, np.asarray(stk(x, rec, jnp.int32(1))))
        return {f"w8a8_{k_dim}x{n_dim}_rel": rel}

    d, f = cfg.hidden_size, cfg.ffn_size
    for k_dim, n_dim in ((d, 3 * d), (d, d), (d, f), (f, d)):
        checks.append((f"w8a8 {k_dim}x{n_dim}",
                       lambda k=k_dim, n=n_dim: w8a8(k, n)))

    def nucleus():
        """The sampler's tiled nucleus search (``ops/sampling.py``) at the
        widest cell's row, 12 rows (no multiple of a tile), against the
        plain loop: where the two differ the upper set's float64 mass is
        within 1e-6 of ``top_p`` (the reduction's order decides)."""
        from deepspeed_tpu.ops import sampling

        rows, vocab = 12, sz.sampler_vocab
        probs = jax.nn.softmax(jax.random.normal(
            keys[3], (rows, vocab), jnp.float32) * 0.7, axis=-1)
        p = jnp.linspace(0.3, 0.95, rows, dtype=jnp.float32)[:, None]
        tiled = jax.jit(sampling._nucleus_threshold_tiled)
        _mosaic(tiled.lower(probs, p).as_text(), "nucleus_search", require)
        plain = jax.jit(sampling._nucleus_threshold)
        got, want = (np.asarray(f(probs, p))[:, 0] for f in (tiled, plain))
        pr = np.asarray(probs, np.float64)
        for r in np.flatnonzero(got != want):
            upper = pr[r][pr[r] >= max(got[r], want[r])].sum()
            assert abs(upper - float(p[r, 0])) < 1e-6, (r, upper)
        return {"nucleus_search_rows_equal": float(np.mean(got == want))}

    checks.append(("nucleus search", nucleus))

    # every kernel is tried even after one fails: a Mosaic refusal is the
    # thing this phase exists to find, and each costs a chip call to see
    failed = []
    for name, check in checks:
        try:
            out.update(check())
        except Exception as e:  # collected and re-raised below
            traceback.print_exc()
            failed.append(f"{name}: {type(e).__name__}: {str(e)[:400]}")
    report["kernels"] = {k: round(v, 6) for k, v in out.items()}
    log("kernels vs references: " + json.dumps(report["kernels"]))
    if failed:
        raise AssertionError(f"{len(failed)} of {len(checks)} kernel checks "
                             "failed:\n" + "\n".join(failed))


# ------------------------------------------------------------------- serve
def _requests(sz: Sizes, vocab: int):
    """A handful of seeded requests: a few tokens up to more than two
    prefill chunks, two sharing a block-aligned prefix, one sampled."""
    from deepspeed_tpu.inference.serving import Request

    rng = np.random.default_rng(1)
    shared = rng.integers(0, vocab, sz.shared_prefix)
    reqs, nt = [], sz.new_tokens
    for i, n in enumerate(sz.prompt_lens):
        reqs.append(Request(f"len{n}", rng.integers(0, vocab, n),
                            max_new_tokens=nt[i % len(nt)]))
    for i in range(2):
        tail = rng.integers(0, vocab, 9 + 7 * i)
        reqs.append(Request(f"shared{i}", np.concatenate([shared, tail]),
                            max_new_tokens=nt[i % len(nt)]))
    reqs.append(Request("sampled", rng.integers(0, vocab, 21),
                        max_new_tokens=nt[-1], temperature=0.8, top_p=0.9,
                        seed=7))
    return reqs


def paged_logits(srv, tokens: np.ndarray, n_decode: int) -> np.ndarray:
    """Teacher-forced logits through the engine's paged path: chunked
    prefill of ``tokens[:, :-n_decode]`` then one decode step per remaining
    token, on the engine's own weights, cache layout, mesh and decode
    hooks.  Returns f32 ``[B, n_chunks + n_decode, V]`` — the logits after
    each prefill chunk's last token and after every decode token."""
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
    if srv.kv_quant:
        cache = paged_kv.quantize_pool(cache)
    else:
        cache = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, a.dtype), cache)
    # lane-packed, as the engine holds its own pool (the benchmark's copy
    # of this function passes the hook's shape: the same code, unpacked)
    cache = jax.tree_util.tree_map(
        lambda a: jax.device_put(a, srv._pool_sharding),
        paged_kv.pack_pool(cache))
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


def reference_logits(model, params, tokens: np.ndarray, n_decode: int,
                     chunk: int) -> np.ndarray:
    """The same positions from the model's plain float32 ``forward`` (XLA
    attention, no kernels, no cache), weights upcast from ``params``."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import opt

    cfg = dataclasses.replace(model.model_config, use_flash=False)
    p32 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, ids: opt.forward(cfg, p, ids))(
            p32, jnp.asarray(tokens))
    s = tokens.shape[1]
    n_prefill = s - n_decode
    at = [min(base + chunk, n_prefill) - 1
          for base in range(0, n_prefill, chunk)]
    at += list(range(n_prefill, s))
    return np.asarray(logits[:, np.asarray(at)], np.float32)


def _rmse(a, b) -> Tuple[float, float]:
    rmse = float(np.sqrt(np.mean((a - b) ** 2)))
    return rmse, rmse / float(np.std(b))


def _per_device_bytes() -> List[Any]:
    import jax

    return [(d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()]


def _assert_spread(used: List[Any]) -> None:
    """On several chips "everything on the first chip" must not pass."""
    import jax

    if len(used) > 1 and all(u is not None for u in used) \
            and min(used) <= 0.25 * max(used):
        live = sorted(jax.live_arrays(), key=lambda a: -a.nbytes)[:8]
        raise AssertionError(
            f"device memory is not spread over the chips: {used}; largest "
            f"live arrays (shape, dtype, devices): "
            + str([(a.shape, str(a.dtype), len(a.sharding.device_set))
                   for a in live]))


def _check_spread(srv) -> Dict[str, Any]:
    """Pool shard shape and bytes in use per chip; on several chips the
    pool must be head-sharded over all of them."""
    import jax

    n = len(jax.devices())
    leaf = jax.tree_util.tree_leaves(srv._cache)[0]
    shard = tuple(leaf.addressable_shards[0].data.shape)
    used = _per_device_bytes()
    log(f"pool shard per chip {shard} of {tuple(leaf.shape)}; bytes in use "
        f"per device {used}")
    if n > 1:
        assert srv.tp_degree == n and srv.kv_sharded, (srv.tp_degree, n)
        assert len(leaf.addressable_shards) == n
        assert shard[2] == leaf.shape[2] // n, (shard, leaf.shape)
        _assert_spread(used)
    return {"pool_shape": tuple(leaf.shape), "pool_shard_shape": shard,
            "bytes_in_use": used}


def phase_serve(sz: Sizes, report: Dict[str, Any], quantize=None) -> None:
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import opt
    from deepspeed_tpu.telemetry.flops import ServingFlopsProfiler
    from deepspeed_tpu.utils.platform import on_tpu

    n = len(jax.devices())
    model = opt.build(sz.opt)
    t0 = time.perf_counter()
    srv = deepspeed_tpu.init_serving(
        model, config={"dtype": sz.dtype}, quantize=quantize,
        topology=n if n > 1 else None,
        debug_checks=True, **sz.serving_kwargs)
    jax.block_until_ready((srv.engine.params, srv._cache))
    t_setup = time.perf_counter() - t0

    reqs = _requests(sz, sz.opt.vocab_size)
    times = []
    for label in ("cold", "warm"):
        t0 = time.perf_counter()
        res = srv.serve(reqs)
        times.append(time.perf_counter() - t0)
        assert set(res) == {r.uid for r in reqs}
        for r in reqs:
            toks = np.asarray(res[r.uid])
            assert toks.shape == (r.prompt.size + r.max_new_tokens,), \
                (r.uid, toks.shape)
            assert np.array_equal(toks[:r.prompt.size], r.prompt), r.uid
            assert ((toks >= 0) & (toks < sz.opt.vocab_size)).all(), r.uid
        st = srv.stats()
        assert st["compile_count"] <= st["compile_budget"], st
        assert st["retraces_observed"] == 0, st
        assert st["generated_tokens"] >= sum(r.max_new_tokens for r in reqs)
        if label == "cold":
            compiled, cold_hits = st["compile_count"], st["prefix_hit_tokens"]
    assert st["compile_count"] == compiled, "warm pass compiled a program"
    # the second pass re-sends every prompt, so the shared prefix (and
    # more) must come back from the block trie whatever the first pass's
    # admission order was
    assert st["prefix_hit_tokens"] >= cold_hits + sz.shared_prefix, st
    assert st["sampled_requests"] >= 1, st
    log(f"serve[{quantize or sz.dtype}]: set-up {t_setup:.1f}s, first pass "
        f"(compiles {compiled} programs) {times[0]:.1f}s, second pass "
        f"{times[1]:.2f}s; {len(reqs)} requests, "
        f"{st['generated_tokens']} tokens, prefix hits {cold_hits} then "
        f"{st['prefix_hit_tokens'] - cold_hits} tokens, "
        f"{st['iterations']} iterations")

    spread = _check_spread(srv)     # after serving: set-up transients gone

    # which implementation did the compiled decode program take?
    prof = ServingFlopsProfiler(srv)
    n_mosaic = _mosaic(prof.lower("decode").as_text(),
                       f"decode program [{quantize or sz.dtype}]", on_tpu())
    assert srv.compile_count == compiled      # lowering compiled nothing

    # teacher-forced logits through the paged cache vs plain f32 forward
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, sz.opt.vocab_size,
                          (srv.slots, sz.score_len)).astype(np.int32)
    got = paged_logits(srv, tokens, sz.score_decode)
    assert np.isfinite(got).all()
    if quantize is None:
        want = reference_logits(model, srv.engine.params, tokens,
                                sz.score_decode, srv.prefill_chunk)
        report["_ref_logits"] = want
        rmse, rel = _rmse(got, want)
        assert rel <= BF16_LOGIT_REL_RMSE, \
            f"paged {sz.dtype} logits vs f32 forward: relative RMSE {rel:.4f}"
    else:
        want = report.pop("_ref_logits", None)
        if want is None:
            raise RuntimeError("no float32 reference logits: the "
                               "full-precision serve phase did not finish")
        rmse, rel = _rmse(got, want)
        assert rmse <= QUANT_LOGIT_RMSE, \
            f"{quantize} logits vs f32 forward: RMSE {rmse:.4f}"
    log(f"teacher-forced logits [{quantize or sz.dtype}] vs f32 forward: "
        f"RMSE {rmse:.4f} ({rel * 100:.2f}% of logit std) over "
        f"{got.shape[0]}x{got.shape[1]} positions")
    report[f"serve_{quantize or sz.dtype}"] = {
        "setup_s": round(t_setup, 2), "first_pass_s": round(times[0], 2),
        "second_pass_s": round(times[1], 3), "compile_count": compiled,
        "compile_budget": st["compile_budget"], "mosaic_calls": n_mosaic,
        "logit_rmse": round(rmse, 5), "logit_rel_rmse": round(rel, 5),
        "generated_tokens": st["generated_tokens"],
        "prefix_hit_tokens": st["prefix_hit_tokens"], **spread}
    deepspeed_tpu.comm.reset_topology()


def phase_serve_quant(sz: Sizes, report: Dict[str, Any]) -> None:
    import jax

    from deepspeed_tpu.utils.platform import on_tpu

    if on_tpu() and len(jax.devices()) > 1:
        # w8a8 x tp does not compile on a TPU yet (InferenceEngine raises
        # with the compiler's message); the int8 KV pool does shard
        log("several chips: serving kv8 only — w8a8 under tensor "
            "parallelism is refused on a TPU")
        return phase_serve(sz, report, quantize="kv8")
    phase_serve(sz, report, quantize="w8a8+kv8")


# ------------------------------------------------- serving programs' memory
#: the benchmark's chat cell (chipbench/workloads/opt13b-chat-closed.json):
#: 24 slots x 1,024 tokens in 32-token blocks, [4, 128] prefill chunks
CHAT_SLOTS, CHAT_CTX, CHAT_BLOCK, CHAT_CHUNK = 24, 1024, 32, (4, 128)


def serving_programs(cfg, sharding, *, slots=CHAT_SLOTS, ctx=CHAT_CTX,
                     block=CHAT_BLOCK, chunk=CHAT_CHUNK, kv8=False,
                     verify_t=4):
    """The paged serving programs of an OPT ``cfg`` as ``{name: (fn,
    abstract args)}`` — the bodies ``ServingEngine`` jits
    (``forward_cached`` over the donated pool, argument 1, + a token rule),
    at the shapes of a ``slots`` x ``ctx`` engine with the pool lane-packed
    as the engine holds it (``paged_kv.pack_pool``), on ShapeDtypeStructs
    only: nothing is allocated.  ``sharding=None`` leaves the arguments
    plain shapes (``jax.export`` from the CPU)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import opt
    from deepspeed_tpu.ops import paged_kv

    spec = opt.build(cfg)
    fwd = spec.decode_hooks["forward_cached"]
    nbper = paged_kv.blocks_for(ctx, block)

    def sds(a, where=sharding):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=where)

    def i32(*shape):
        return sds(jax.ShapeDtypeStruct(shape, jnp.int32))

    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16),
            spec.init_fn(jax.random.PRNGKey(0)))))
    pool = jax.eval_shape(lambda: spec.decode_hooks["init_cache"](
        1 + slots * nbper, block, jnp.bfloat16))
    if kv8:
        pool = jax.eval_shape(paged_kv.quantize_pool, pool)
    pool = jax.tree_util.tree_map(sds, jax.eval_shape(paged_kv.pack_pool,
                                                      pool))

    def decode_step(params, cache, tokens, lengths, bt):
        logits, cache = fwd(params, tokens[:, None], cache, 0,
                            lengths=lengths, block_tables=bt)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache

    def prefill(params, cache, ids, bt, base, valid, all_positions=False):
        logits, cache = fwd(params, ids, cache, base, lengths=valid,
                            block_tables=bt, all_positions=all_positions)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache

    def verify(*args):
        return prefill(*args, all_positions=True)

    j, w = chunk
    return {
        "decode_step": (decode_step, (params, pool, i32(slots), i32(slots),
                                      i32(slots, nbper))),
        "prefill": (prefill, (params, pool, i32(j, w), i32(j, nbper),
                              i32(j), i32(j))),
        "verify": (verify, (params, pool, i32(slots, verify_t),
                            i32(slots, nbper), i32(slots), i32(slots))),
    }


def pool_payload_struct(cache):
    """The K pool's payload array (the int8 codes of a kv8 record) of a
    ``{"k", "v"}`` cache tree."""
    from deepspeed_tpu.ops import paged_kv

    return paged_kv.pool_payload(cache["k"])


def compile_serving_program(fn, args):
    """Compile one of :func:`serving_programs` with the pool donated;
    ``(compiled, pool-slice-sized copy instructions in its text)``."""
    import jax

    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    payload = pool_payload_struct(args[1]).shape            # [L, NB, ...]
    slice_dims = ",".join(str(d) for d in payload[1:])
    copies = [line for line in compiled.as_text().splitlines()
              if " copy(" in line and slice_dims in line.split(" copy(")[0]]
    return compiled, copies


def phase_serving_memory(sz: Sizes, report: Dict[str, Any]) -> None:
    """The decode and prefill programs at the chat cell's shapes must hold
    no temporary of the pool's size: the pool is carried whole and updated
    in place (one layer's slice of it is the yardstick: 100 MB)."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.utils.platform import on_tpu

    if not on_tpu():
        # XLA:CPU's buffers and layouts say nothing about the chip's
        log("serving-memory: a TPU-only check, skipped")
        return
    progs = serving_programs(sz.opt, SingleDeviceSharding(jax.devices()[0]))
    out, failed = {}, []
    for name in ("decode_step", "prefill"):
        fn, args = progs[name]
        compiled, copies = compile_serving_program(fn, args)
        payload = pool_payload_struct(args[1])
        layer_slice = int(np.prod(payload.shape[1:])) * payload.dtype.itemsize
        temp = int(compiled.memory_analysis().temp_size_in_bytes)
        out[name] = {"temp_bytes": temp, "pool_slice_copies": len(copies),
                     "layer_slice_bytes": layer_slice}
        log(f"serving program {name} at {CHAT_SLOTS} slots x {CHAT_CTX}: "
            f"temporaries {temp / 1e6:.2f} MB, {len(copies)} copies of a "
            f"pool slice (one layer's slice: {layer_slice / 1e6:.1f} MB)")
        if temp >= layer_slice or copies:
            failed.append(name)
    report["serving_memory"] = out
    assert not failed, f"pool-sized temporaries or copies in {failed}: {out}"


# ------------------------------------------------------------------- train
def phase_train(sz: Sizes, report: Dict[str, Any]) -> None:
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.utils.platform import on_tpu

    n = len(jax.devices())
    cfg = sz.gpt2
    t0 = time.perf_counter()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=gpt2.build(cfg),
        config={"train_micro_batch_size_per_gpu": sz.micro_bs,
                "gradient_accumulation_steps": sz.gas,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                "bf16": {"enabled": True},
                "zero_optimization": {"stage": 1 if n > 1 else 0}})
    jax.block_until_ready(engine.state)
    t_setup = time.perf_counter() - t0
    rng = np.random.default_rng(3)
    batch = {"input_ids": rng.integers(
        0, cfg.vocab_size,
        (engine.train_batch_size(), sz.seq + 1)).astype(np.int32)}

    # which attention did the train step take?  (lowering, no compile)
    sharded = engine._shard_batch(engine._reshape_global_batch(batch),
                                  leading_gas_dim=True)
    n_mosaic = _mosaic(
        engine._train_step_fn.lower(engine.state, sharded,
                                    engine._dropout_rng).as_text(),
        "train step", on_tpu())

    losses, times = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        _, m = engine.train_batch(batch)
        losses.append(float(m["loss"]))        # value fetch: waits
        times.append(time.perf_counter() - t0)
    log(f"train: set-up {t_setup:.1f}s, first step (compile) "
        f"{times[0]:.1f}s, steps {times[1]:.3f}s {times[2]:.3f}s; "
        f"losses {losses}; global batch {engine.train_batch_size()} x "
        f"{sz.seq} over {n} device(s); bytes in use per device "
        f"{_per_device_bytes()}")
    assert all(np.isfinite(l) for l in losses), losses
    # seeded N(0, 0.02) weights: the first loss sits at ln(vocab)
    assert abs(losses[0] - np.log(cfg.vocab_size)) < 0.5, losses
    assert losses[2] < losses[1] < losses[0], f"loss not falling: {losses}"
    assert engine.sentry.retraces_observed == 0
    _assert_spread(_per_device_bytes())
    report["train"] = {
        "setup_s": round(t_setup, 2), "first_step_s": round(times[0], 2),
        "step_s": [round(t, 4) for t in times[1:]], "losses": losses,
        "mosaic_calls": n_mosaic, "devices": n,
        "bytes_in_use": _per_device_bytes()}
    deepspeed_tpu.comm.reset_topology()


PHASES: List[Tuple[str, Callable[[Sizes, Dict[str, Any]], None]]] = [
    ("device", phase_device),
    ("kernels", phase_kernels),
    ("serve", phase_serve),
    ("serve-q", phase_serve_quant),
    ("serving-memory", phase_serving_memory),
    ("train", phase_train),
]


def run_phases(sz: Sizes, phases=None) -> Tuple[bool, Dict[str, Any]]:
    """Run every phase; a phase that raises is reported with its traceback
    and fails the run — later phases still run so one call on the chip
    reports everything that is broken."""
    report: Dict[str, Any] = {"phases": {}}
    for name, fn in (phases or PHASES):
        t0 = time.perf_counter()
        try:
            fn(sz, report)
            status = "ok"
        except Exception as e:  # report, keep going, fail at the end
            traceback.print_exc()
            status = f"{type(e).__name__}: {e}"[:2000]
        dt = time.perf_counter() - t0
        report["phases"][name] = status
        log(f"== phase {name}: {status.splitlines()[0][:300]} ({dt:.1f}s)")
        gc.collect()
    report.pop("_ref_logits", None)
    return all(s == "ok" for s in report["phases"].values()), report


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phases", default=None,
                    help="comma-separated subset of "
                         + ",".join(n for n, _ in PHASES) + " (debugging "
                         "on a chip budget; the result line needs them all)")
    args = ap.parse_args(argv)
    phases = PHASES
    if args.phases:
        want = args.phases.split(",")
        unknown = set(want) - {n for n, _ in PHASES}
        if unknown:
            ap.error(f"unknown phases {sorted(unknown)}")
        phases = [(n, f) for n, f in PHASES if n in want]

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{dev.platform!r} — refusing to run", file=sys.stderr)
        return 2
    from deepspeed_tpu.utils.platform import enable_compile_cache

    t0 = time.perf_counter()
    cache_dir = enable_compile_cache(REPO_ROOT)
    before = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"compile cache: {cache_dir} ({before} entries at start)")
    ok, report = run_phases(full_sizes(), phases)
    after = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    report["compile_cache"] = {"dir": cache_dir, "entries_before": before,
                               "entries_after": after}
    report["total_s"] = round(time.perf_counter() - t0, 1)
    os.makedirs(os.path.join(REPO_ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO_ROOT, "chiprun_out",
                           "chip_smoke_report.json"), "w") as f:
        json.dump(report, f, indent=1, default=str)
    log(f"total {report['total_s']}s; cache entries {before} -> {after}; "
        f"phases {json.dumps(report['phases'])}")
    if not ok:
        return 1
    if len(phases) != len(PHASES):
        log("partial run: no result line")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
