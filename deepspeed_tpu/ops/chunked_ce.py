"""Chunked cross-entropy over a head: the loss AND both cotangents in one
pass over ``[T / n_chunks, V]`` logits at a time — the head's float32
logits are never whole (GPT-2's ``fused_ce``; every family whose sequence x
vocabulary would not fit shares it)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def whole_chunks(n: int, most: int) -> int:
    """The largest chunk count up to ``most`` that divides ``n`` tokens."""
    n_chunks = max(1, min(most, n))
    while n % n_chunks:
        n_chunks -= 1
    return n_chunks


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def chunked_ce(w, x2d, targets, n_chunks):
    loss, _ = _chunked_ce_fwd(w, x2d, targets, n_chunks)
    return loss


def _chunked_ce_fwd(w, x2d, targets, n_chunks):
    """Chunked CE over a tied head: computes loss AND the (unscaled) input /
    weight cotangents during the forward pass.

    The checkpointed head (``loss_from_batch``) runs 4 full [T,D]x[D,V]
    matmuls per train step (fwd logits, bwd recompute, dx, dW); computing
    ``dlogits = softmax - onehot`` while the chunk's logits are live needs
    only 3 and never materializes more than [T/n_chunks, V] of logits.  The
    softmax/one-hot trick is textbook CE backward (cf. the reference's fused
    logits kernels, ``csrc/transformer/softmax_kernels.cu``); loss scaling
    happens in the vjp by the (linear) upstream cotangent.
    """
    n, d = x2d.shape
    v = w.shape[1]
    assert n % n_chunks == 0, (n, n_chunks)
    c = n // n_chunks
    xs = x2d.reshape(n_chunks, c, d)
    ts = targets.reshape(n_chunks, c)
    valid_all = targets >= 0
    denom = jnp.maximum(valid_all.sum(), 1).astype(jnp.float32)

    def chunk(xc, tc):
        with jax.named_scope("head"):
            logits = (xc @ w).astype(jnp.float32)        # [c, V]
        valid = tc >= 0
        safe = jnp.where(valid, tc, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)           # [c]
        picked = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
        loss = jnp.where(valid, lse - picked, 0.0).sum() / denom
        # dlogits of mean-NLL (unscaled by upstream cotangent).  gc is cast
        # to the param dtype for the MXU matmuls: fine for bf16 (f32
        # exponent range), lossy for fp16 where tiny unscaled entries land
        # in the subnormal range — prefer bf16 training with fused_ce.
        p = jnp.exp(logits - lse[:, None])
        g = p.at[jnp.arange(c), safe].add(-1.0)
        g = jnp.where(valid[:, None], g, 0.0) / denom     # [c, V] f32
        gc = g.astype(w.dtype)
        # MXU inputs stay in param dtype; outputs come out f32 so unscaled
        # fp16 grads don't flush to subnormals before the bwd ct multiply
        with jax.named_scope("head"):
            dxi = jax.lax.dot_general(gc, w, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            dwi = jax.lax.dot_general(xc, gc, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return loss, dxi, dwi                             # loss, [c,D], [D,V]

    # unrolled chunk loop (a scan's dw carry would copy [D, V] f32 per
    # iteration and serialize; unrolled, XLA overlaps chunk i+1's logits
    # with chunk i's grad matmuls)
    loss = jnp.zeros((), jnp.float32)
    dw = jnp.zeros((d, v), jnp.float32)
    dxs = []
    with jax.named_scope("loss"):
        for i in range(n_chunks):
            li, dxi, dwi = chunk(xs[i], ts[i])
            loss += li
            dw += dwi
            dxs.append(dxi)
        dx = jnp.concatenate(dxs, axis=0) if n_chunks > 1 else dxs[0]
    # Residuals stay f32: under fp16 loss scaling the upstream cotangent
    # (the scale) is applied in _chunked_ce_bwd, and casting the UNSCALED
    # grads to fp16 here would underflow exactly the small values the
    # scaler exists to preserve.  One f32 [D,V] + [N,D] residual is the
    # price; the cast to param dtype happens after the ct multiply.  The
    # target dtypes ride as zero-size arrays (a dtype object is not a
    # valid jax residual leaf).
    return loss, (jnp.zeros((0,), w.dtype), jnp.zeros((0,), x2d.dtype),
                  dw, dx)


def _chunked_ce_bwd(n_chunks, res, ct):
    w_proto, x_proto, dw, dx = res
    # (a backward rule is a function of its own: the forward's scope does
    # not reach it)
    with jax.named_scope("loss"):
        ct = ct.astype(jnp.float32)
        return ((ct * dw).astype(w_proto.dtype),
                (ct * dx).astype(x_proto.dtype), None)


chunked_ce.defvjp(_chunked_ce_fwd, _chunked_ce_bwd)
