"""Megatron-LM GPT checkpoint ingestion onto the GPT-2 family.

Reference parity: ``runtime/state_dict_factory.py`` ``MegatronSDLoader``
(merge/split of Megatron TP shards, qkv layout per checkpoint version,
``:214``) and the Megatron injection policy
(``module_inject/replace_policy.py`` MegatronLayerPolicy,
``containers/megatron_gpt.py``).

Megatron GPT uses the GPT-2 block (pre-LN, fused qkv, learned positions,
tied lm head), so ingestion targets :mod:`deepspeed_tpu.models.gpt2`'s
param pytree directly.  The three qkv row layouts the reference recognizes:

 - version 0:   rows = (3, np, hn)  — q | k | v contiguous
 - version 1.0: rows = (np, hn, 3) — per-head, dim-fastest interleave
 - version 2.0: rows = (np, 3, hn) — per-head q|k|v interleave
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from . import gpt2
from .gpt2 import GPT2Config

PyTree = Any


def _np(t) -> np.ndarray:
    return np.asarray(t.detach().cpu().numpy() if hasattr(t, "detach") else t,
                      dtype=np.float32)


def _deinterleave_qkv(qkv_rows: np.ndarray, num_heads: int,
                      ckpt_version: float) -> np.ndarray:
    """[3h, h] Megatron rows (any supported version) -> q|k|v contiguous."""
    three_h, h = qkv_rows.shape
    hn = three_h // (3 * num_heads)
    if ckpt_version == 0:
        return qkv_rows                                    # already q|k|v
    if ckpt_version == 1.0:
        x = qkv_rows.reshape(num_heads, hn, 3, h)
        return x.transpose(2, 0, 1, 3).reshape(three_h, h)
    if ckpt_version == 2.0:
        x = qkv_rows.reshape(num_heads, 3, hn, h)
        return x.transpose(1, 0, 2, 3).reshape(three_h, h)
    raise ValueError(f"unsupported Megatron checkpoint version {ckpt_version}")


def _deinterleave_qkv_bias(b: np.ndarray, num_heads: int,
                           ckpt_version: float) -> np.ndarray:
    three_h = b.shape[0]
    hn = three_h // (3 * num_heads)
    if ckpt_version == 0:
        return b
    if ckpt_version == 1.0:
        return b.reshape(num_heads, hn, 3).transpose(2, 0, 1).reshape(three_h)
    if ckpt_version == 2.0:
        return b.reshape(num_heads, 3, hn).transpose(1, 0, 2).reshape(three_h)
    raise ValueError(f"unsupported Megatron checkpoint version {ckpt_version}")


def merge_tp_qkv(shards: Sequence[np.ndarray], num_heads: int,
                 ckpt_version: float) -> np.ndarray:
    """Merge per-TP-rank qkv row shards (reference
    ``merge_query_key_value``): version 0 concatenates per-projection;
    1.0/2.0 concatenate whole shards (head-interleaved rows)."""
    if ckpt_version == 0:
        per = [np.split(s, 3, axis=0) for s in shards]
        return np.concatenate([np.concatenate([p[i] for p in per], axis=0)
                               for i in range(3)], axis=0)
    return np.concatenate(list(shards), axis=0)


_EMB_PREFIXES = ("", "embedding.", "model.", "model.language_model.",
                 "model.language_model.embedding.", "transformer.",
                 "encoder.", "model.language_model.transformer.",
                 "model.language_model.encoder.")


def _get_any(sd, name):
    for p in _EMB_PREFIXES:
        if p + name in sd:
            return _np(sd[p + name])
    raise KeyError(f"{name} (have: {sorted(sd)[:8]}...)")


def config_from_state_dicts(shards: Sequence[Dict[str, Any]],
                            max_seq_len: Optional[int] = None,
                            num_heads: Optional[int] = None) -> GPT2Config:
    """Infer a GPT2Config from Megatron GPT TP-rank state dicts (the vocab
    is split over ranks, so all shards are consulted)."""
    sd = shards[0]
    vocab = sum(_get_any(s, "word_embeddings.weight").shape[0]
                for s in shards)
    wpe = _get_any(sd, "position_embeddings.weight")
    n_layers = 1 + max(
        int(k.split("layers.")[1].split(".")[0])
        for k in sd if ".layers." in k or k.startswith("layers."))
    d = wpe.shape[1]
    # Megatron does not store the head count; pass ``num_heads`` when the
    # standard 64-dim-head assumption is wrong.
    return GPT2Config(vocab_size=vocab,
                      max_seq_len=max_seq_len or wpe.shape[0],
                      num_layers=n_layers,
                      num_heads=num_heads or max(1, d // 64),
                      hidden_size=d)


def config_from_state_dict(sd: Dict[str, Any],
                           max_seq_len: Optional[int] = None,
                           num_heads: Optional[int] = None) -> GPT2Config:
    """Single (merged) state-dict convenience wrapper."""
    return config_from_state_dicts([sd], max_seq_len=max_seq_len,
                                   num_heads=num_heads)


def from_megatron_state_dicts(cfg: GPT2Config,
                              shards: List[Dict[str, Any]],
                              ckpt_version: float = 0) -> PyTree:
    """Merge Megatron TP-rank state dicts into the gpt2 param pytree.

    ``shards``: one state dict per TP rank (a single-element list for an
    unpartitioned checkpoint).  Column-parallel weights (qkv, h_to_4h)
    concatenate on rows; row-parallel (dense, 4h_to_h) on columns —
    mirroring the reference's merge table (``state_dict_factory.py:330+``).
    """
    def get(sd, name):
        return _get_any(sd, name)

    def layer(name, i):
        # prefix resolution handles transformer./encoder./nested variants
        return f"layers.{i}.{name}"

    l = cfg.num_layers

    def merged(name, i, axis=None, qkv=False):
        parts = [get(sd, layer(name, i)) for sd in shards]
        if qkv:
            return merge_tp_qkv(parts, cfg.num_heads, ckpt_version)
        if axis is None or len(parts) == 1:
            return parts[0]
        return np.concatenate(parts, axis=axis)

    def stack(fn):
        return jnp.asarray(np.stack([fn(i) for i in range(l)]))

    wte = np.concatenate([get(sd, "word_embeddings.weight")
                          for sd in shards], axis=0) if len(shards) > 1 \
        else get(shards[0], "word_embeddings.weight")

    return {
        "wte": jnp.asarray(wte[:cfg.vocab_size]),
        "wpe": jnp.asarray(get(shards[0], "position_embeddings.weight")),
        "blocks": {
            "ln1_scale": stack(lambda i: merged("input_layernorm.weight", i)),
            "ln1_bias": stack(lambda i: merged("input_layernorm.bias", i)),
            # torch [out, in] -> ours [in, out]
            "qkv_w": stack(lambda i: _deinterleave_qkv(
                merged("attention.query_key_value.weight", i, qkv=True),
                cfg.num_heads, ckpt_version).T),
            "qkv_b": stack(lambda i: _deinterleave_qkv_bias(
                merge_tp_qkv([get(sd, layer(
                    "attention.query_key_value.bias", i))[:, None]
                    for sd in shards], cfg.num_heads, ckpt_version)[:, 0],
                cfg.num_heads, ckpt_version)),
            "o_w": stack(lambda i: merged("attention.dense.weight", i,
                                          axis=1).T),
            "o_b": stack(lambda i: merged("attention.dense.bias", i)),
            "ln2_scale": stack(
                lambda i: merged("post_attention_layernorm.weight", i)),
            "ln2_bias": stack(
                lambda i: merged("post_attention_layernorm.bias", i)),
            "fc_w": stack(lambda i: merged("mlp.dense_h_to_4h.weight", i,
                                           axis=0).T),
            "fc_b": stack(lambda i: merged("mlp.dense_h_to_4h.bias", i,
                                           axis=0)),
            "proj_w": stack(lambda i: merged("mlp.dense_4h_to_h.weight", i,
                                             axis=1).T),
            "proj_b": stack(lambda i: merged("mlp.dense_4h_to_h.bias", i)),
        },
        "lnf_scale": jnp.asarray(
            get(shards[0], "final_layernorm.weight")),
        "lnf_bias": jnp.asarray(
            get(shards[0], "final_layernorm.bias")),
    }


def load(ckpt_files: List[str], cfg: Optional[GPT2Config] = None,
         ckpt_version: Optional[float] = None):
    """Load Megatron GPT checkpoint file(s) (one per TP rank) into
    ``(ModelSpec, params)``.  Accepts raw state dicts or the Megatron
    wrapper dict ({'model': ..., 'checkpoint_version': ...})."""
    import torch

    raw = [torch.load(f, map_location="cpu", weights_only=False)
           for f in ckpt_files]
    sds = []
    ver = ckpt_version
    for r in raw:
        if isinstance(r, dict) and "model" in r and isinstance(
                r["model"], dict):
            if ver is None and "checkpoint_version" in r:
                ver = float(r["checkpoint_version"])
            sd = r["model"]
            if "language_model" in sd:
                sd = sd["language_model"]
            flat = {}

            def _flatten(prefix, d):
                for k, v in d.items():
                    if isinstance(v, dict):
                        _flatten(f"{prefix}{k}.", v)
                    else:
                        flat[f"{prefix}{k}"] = v

            _flatten("", sd)
            sds.append(flat)
        else:
            sds.append(r)
    ver = 0 if ver is None else ver
    cfg = cfg or config_from_state_dicts(sds)
    params = from_megatron_state_dicts(cfg, sds, ckpt_version=ver)
    return gpt2.build(cfg), params
