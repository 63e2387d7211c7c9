"""Block-sparse self-attention execution.

Reference ``ops/sparse_attention/sparse_self_attention.py:11
SparseSelfAttention`` runs Triton block-sparse sddmm/softmax/dsd kernels
over a ``SparsityConfig`` layout; here the same layouts gate the Pallas
flash kernel's (q-block, k-block) grid (``ops/flash_attention.py``
``_sparse_attention_bh``): gated-off blocks are skipped in forward *and*
both backward kernels, so compute scales with layout density.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...utils.platform import interpret_kernels
from ..flash_attention import _sparse_attention_bh
from .sparsity_config import SparsityConfig


def sparse_attention(q, k, v, layout, block: int,
                     sm_scale: Optional[float] = None,
                     causal: bool = False,
                     interpret: Optional[bool] = None):
    """q: [B, H, S, D]; k/v: [B, Hkv, S, D] (GQA: Hkv | H); layout:
    [H, S/block, S/block] 0/1.

    Returns [B, H, S, D].  ``causal=True`` additionally lower-triangularizes
    inside diagonal blocks (configs built with ``attention="unidirectional"``
    already gate strictly-upper blocks off)."""
    b, h, s, d = q.shape
    hkv = k.shape[1]
    assert h % hkv == 0, f"GQA needs num_heads {h} % kv_heads {hkv} == 0"
    rep = h // hkv
    n = layout.shape[-1]
    assert s % block == 0 and s // block == n, (
        f"seq {s} != layout blocks {n} x block {block}")
    assert layout.shape[0] in (1, h)
    if interpret is None:
        interpret = interpret_kernels()
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    layout = jnp.asarray(layout, jnp.float32)
    if layout.shape[0] == 1:
        layout = jnp.broadcast_to(layout, (h,) + layout.shape[1:])

    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * hkv, s, d)
    vf = v.reshape(b * hkv, s, d)
    o = _sparse_attention_bh(qf, kf, vf, layout, sm_scale, causal, block,
                             block, interpret, rep)
    return o.reshape(b, h, s, d)


class SparseSelfAttention:
    """Config-driven wrapper (reference ``sparse_self_attention.py:11``)."""

    def __init__(self, sparsity_config: SparsityConfig,
                 attn_mask_mode: str = "mul", max_seq_length: int = 2048):
        self.sparsity_config = sparsity_config
        self._layouts = {}

    def get_layout(self, seq_len: int):
        if seq_len not in self._layouts:
            self._layouts[seq_len] = self.sparsity_config.make_layout(seq_len)
        return self._layouts[seq_len]

    def __call__(self, q, k, v, rpe=None, key_padding_mask=None,
                 attn_mask=None):
        assert rpe is None and key_padding_mask is None and attn_mask is None, (
            "rpe/masks not supported yet (reference supports them via "
            "kernel arguments)")
        s = q.shape[2]
        layout = self.get_layout(s)
        causal = getattr(self.sparsity_config, "attention",
                         "bidirectional") == "unidirectional"
        return sparse_attention(q, k, v, layout,
                                self.sparsity_config.block, causal=causal)


def sparse_attention_reference(q, k, v, layout, block: int,
                               causal: bool = False):
    """Dense einsum reference honoring the block layout (for tests)."""
    b, h, s, d = q.shape
    if k.shape[1] != h:  # GQA: repeat KV heads for the dense math
        rep = h // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    mask = np.kron(np.asarray(layout, bool),
                   np.ones((block, block), bool))  # [H, S, S]
    if causal:
        mask = mask & np.tril(np.ones((s, s), bool))[None]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(d)
    scores = jnp.where(jnp.asarray(mask)[None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
