"""Per-architecture injection policies (reference
``module_inject/replace_policy.py:4-28`` + ``containers/*``).

A :class:`HFPolicy` maps a HuggingFace architecture to:
 - a config translation (HF config -> our model config),
 - a weight conversion (HF state dict -> scan-stacked param pytree),
 - a ModelSpec builder.

``replace_module(hf_model)`` is the ``replace_transformer_layer`` analog
(``replace_module.py:308``): given a torch HF model (or its config + state
dict), returns ``(ModelSpec, params)`` ready for ``init_inference``.  TP
sharding is applied by the InferenceEngine from the spec's ``tp_rules`` —
for architectures without a policy, ``auto_tp.infer_tp_specs`` provides the
generic fallback.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax.numpy as jnp
import numpy as np

PyTree = Any


@dataclasses.dataclass
class HFPolicy:
    arch: str                                  # HF `architectures[0]` name
    translate_config: Callable[[Any], Any]     # hf config -> our config
    convert_weights: Callable[[Any, Dict], PyTree]  # (cfg, state_dict) -> params
    build: Callable[[Any], Any]                # cfg -> ModelSpec


def _np(t) -> np.ndarray:
    return np.asarray(t.detach().cpu().numpy() if hasattr(t, "detach") else t,
                      dtype=np.float32)


# ----------------------------------------------------------------- GPT-2
def _gpt2_translate(hf):
    from ..models.gpt2 import GPT2Config
    return GPT2Config(vocab_size=hf.vocab_size, max_seq_len=hf.n_positions,
                      num_layers=hf.n_layer, num_heads=hf.n_head,
                      hidden_size=hf.n_embd)


def _gpt2_convert(cfg, sd) -> PyTree:
    def get(name):
        for prefix in ("transformer.", ""):
            if prefix + name in sd:
                return _np(sd[prefix + name])
        raise KeyError(name)

    l = cfg.num_layers

    def stack(fmt):
        return jnp.asarray(np.stack([get(fmt.format(i=i)) for i in range(l)]))

    # HF GPT-2 uses Conv1D: weights already [in, out] — no transpose
    return {
        "wte": jnp.asarray(get("wte.weight")),
        "wpe": jnp.asarray(get("wpe.weight")),
        "blocks": {
            "ln1_scale": stack("h.{i}.ln_1.weight"),
            "ln1_bias": stack("h.{i}.ln_1.bias"),
            "qkv_w": stack("h.{i}.attn.c_attn.weight"),
            "qkv_b": stack("h.{i}.attn.c_attn.bias"),
            "o_w": stack("h.{i}.attn.c_proj.weight"),
            "o_b": stack("h.{i}.attn.c_proj.bias"),
            "ln2_scale": stack("h.{i}.ln_2.weight"),
            "ln2_bias": stack("h.{i}.ln_2.bias"),
            "fc_w": stack("h.{i}.mlp.c_fc.weight"),
            "fc_b": stack("h.{i}.mlp.c_fc.bias"),
            "proj_w": stack("h.{i}.mlp.c_proj.weight"),
            "proj_b": stack("h.{i}.mlp.c_proj.bias"),
        },
        "lnf_scale": jnp.asarray(get("ln_f.weight")),
        "lnf_bias": jnp.asarray(get("ln_f.bias")),
    }


def _gpt2_build(cfg):
    from ..models import gpt2
    return gpt2.build(cfg)


# ------------------------------------------------------------------- OPT
def _opt_translate(hf):
    from ..models.opt import OPTConfig
    return OPTConfig.from_hf(hf)


def _opt_convert(cfg, sd) -> PyTree:
    from ..models.opt import from_hf_state_dict
    return from_hf_state_dict(cfg, sd)


def _opt_build(cfg):
    from ..models import opt
    return opt.build(cfg)


# ----------------------------------------------------------------- Llama
def _llama_translate(hf):
    from ..models.llama import LlamaConfig
    return LlamaConfig(
        vocab_size=hf.vocab_size, max_seq_len=hf.max_position_embeddings,
        num_layers=hf.num_hidden_layers, num_heads=hf.num_attention_heads,
        num_kv_heads=hf.num_key_value_heads, hidden_size=hf.hidden_size,
        ffn_size=hf.intermediate_size,
        rope_theta=getattr(hf, "rope_theta", 10000.0))


def _llama_convert(cfg, sd, include_mlp: bool = True) -> PyTree:
    """Llama-family trunk (embed/attention/norms/head); ``include_mlp=False``
    for Mixtral, whose FFN keys live under block_sparse_moe instead."""
    def get(name):
        for prefix in ("model.", ""):
            if prefix + name in sd:
                return _np(sd[prefix + name])
        raise KeyError(name)

    l = cfg.num_layers

    def stack(fmt, transpose=True):
        rows = [get(fmt.format(i=i)) for i in range(l)]
        return jnp.asarray(np.stack([r.T if transpose else r for r in rows]))

    if "lm_head.weight" in sd:
        lm_head = jnp.asarray(_np(sd["lm_head.weight"]).T)
    else:  # tied
        lm_head = jnp.asarray(get("embed_tokens.weight").T)
    blocks = {
        "attn_norm": stack("layers.{i}.input_layernorm.weight",
                           transpose=False),
        "q_w": stack("layers.{i}.self_attn.q_proj.weight"),
        "k_w": stack("layers.{i}.self_attn.k_proj.weight"),
        "v_w": stack("layers.{i}.self_attn.v_proj.weight"),
        "o_w": stack("layers.{i}.self_attn.o_proj.weight"),
        "mlp_norm": stack("layers.{i}.post_attention_layernorm.weight",
                          transpose=False),
    }
    if include_mlp:
        blocks["w1"] = stack("layers.{i}.mlp.gate_proj.weight")
        blocks["w3"] = stack("layers.{i}.mlp.up_proj.weight")
        blocks["w2"] = stack("layers.{i}.mlp.down_proj.weight")
    return {
        "embed": jnp.asarray(get("embed_tokens.weight")),
        "blocks": blocks,
        "final_norm": jnp.asarray(get("norm.weight")),
        "lm_head": lm_head,
    }


def _llama_build(cfg):
    from ..models import llama
    return llama.build(cfg)


# ----------------------------------------------------------------- Mixtral
def _mixtral_translate(hf):
    from ..models.mixtral import MixtralConfig
    return MixtralConfig(
        vocab_size=hf.vocab_size, max_seq_len=hf.max_position_embeddings,
        num_layers=hf.num_hidden_layers, num_heads=hf.num_attention_heads,
        num_kv_heads=hf.num_key_value_heads, hidden_size=hf.hidden_size,
        ffn_size=hf.intermediate_size,
        rope_theta=getattr(hf, "rope_theta", 1e6),
        # inference is dropless (moe/routed.py): HF's routing semantics
        num_experts=hf.num_local_experts, top_k=hf.num_experts_per_tok)


def _mixtral_convert(cfg, sd) -> PyTree:
    base = _llama_convert(cfg, sd, include_mlp=False)
    blocks = base["blocks"]

    def get(name):
        for prefix in ("model.", ""):
            if prefix + name in sd:
                return _np(sd[prefix + name])
        raise KeyError(name)

    l, e = cfg.num_layers, cfg.num_experts

    def stack_experts(w_name):
        # HF expert Linear stores [out, in]; ours is [l, e, in, out]
        return jnp.asarray(np.stack([
            np.stack([get(f"layers.{i}.block_sparse_moe.experts.{j}."
                          f"{w_name}.weight").T for j in range(e)])
            for i in range(l)]))

    blocks["gate_w"] = jnp.asarray(np.stack(
        [get(f"layers.{i}.block_sparse_moe.gate.weight").T
         for i in range(l)]))
    blocks["experts_w1"] = stack_experts("w1")
    blocks["experts_w2"] = stack_experts("w2")
    blocks["experts_w3"] = stack_experts("w3")
    return base


def _mixtral_build(cfg):
    from ..models import mixtral
    return mixtral.build(cfg)


_POLICIES: Dict[str, HFPolicy] = {}


def _register(arch, translate, convert, build):
    _POLICIES[arch.lower()] = HFPolicy(arch, translate, convert, build)


def _bloom_translate(hf):
    from ..models.bloom import BloomConfig
    return BloomConfig.from_hf(hf)


def _bloom_convert(cfg, sd):
    from ..models.bloom import from_hf_state_dict
    return from_hf_state_dict(cfg, sd)


def _bloom_build(cfg):
    from ..models import bloom
    return bloom.build(cfg)


def _neox_translate(hf):
    from ..models.gptneox import GPTNeoXConfig
    return GPTNeoXConfig.from_hf(hf)


def _neox_convert(cfg, sd):
    from ..models.gptneox import from_hf_state_dict
    return from_hf_state_dict(cfg, sd)


def _neox_build(cfg):
    from ..models import gptneox
    return gptneox.build(cfg)


def _gptj_translate(hf):
    from ..models.gptj import GPTJConfig
    return GPTJConfig.from_hf(hf)


def _gptj_convert(cfg, sd):
    from ..models.gptj import from_hf_state_dict
    return from_hf_state_dict(cfg, sd)


def _gptj_build(cfg):
    from ..models import gptj
    return gptj.build(cfg)


def _gptneo_translate(hf):
    from ..models.gptneo import GPTNeoConfig
    return GPTNeoConfig.from_hf(hf)


def _gptneo_convert(cfg, sd):
    from ..models.gptneo import from_hf_state_dict
    return from_hf_state_dict(cfg, sd)


def _gptneo_build(cfg):
    from ..models import gptneo
    return gptneo.build(cfg)


def _bert_translate(hf):
    from ..models.bert import BertConfig
    return BertConfig.from_hf(hf)


def _bert_convert(cfg, sd):
    from ..models.bert import from_hf_state_dict
    return from_hf_state_dict(cfg, sd)


def _bert_build(cfg):
    from ..models import bert
    return bert.build(cfg)


def _distilbert_translate(hf):
    """DistilBERT is a 6-layer post-LN BERT without token-type embeddings
    or pooler (reference ``containers/distil_bert.py``); it reuses the BERT
    encoder with a 1-row zero token-type table."""
    from ..models.bert import BertConfig
    act = getattr(hf, "activation", "gelu")
    if act not in ("gelu", "gelu_new"):
        raise NotImplementedError(f"distilbert: activation={act!r}")
    return BertConfig(
        vocab_size=hf.vocab_size,
        max_seq_len=hf.max_position_embeddings,
        type_vocab_size=1,
        num_layers=hf.n_layers,
        num_heads=hf.n_heads,
        hidden_size=hf.dim,
        intermediate_size=hf.hidden_dim,
        layer_norm_eps=1e-12)


def _distilbert_convert(cfg, sd):
    def get(name):
        for prefix in ("distilbert.", ""):
            if prefix + name in sd:
                return _np(sd[prefix + name])
        raise KeyError(name)

    l, d = cfg.num_layers, cfg.hidden_size

    def stack(fmt, fn=lambda x: x):
        return jnp.asarray(np.stack([fn(get(fmt.format(i=i)))
                                     for i in range(l)]))

    def fuse_qkv(i):
        ws = [get(f"transformer.layer.{i}.attention.{p}_lin.weight").T
              for p in ("q", "k", "v")]
        return np.concatenate(ws, axis=1)

    def fuse_qkv_b(i):
        return np.concatenate(
            [get(f"transformer.layer.{i}.attention.{p}_lin.bias")
             for p in ("q", "k", "v")])

    t = lambda w: w.T
    # our BERT mlm head decodes through the (tied) word embeddings; verify
    # the projector really is tied before dropping its weight
    try:
        proj = get("vocab_projector.weight")
        if not np.allclose(proj, get("embeddings.word_embeddings.weight")):
            raise NotImplementedError(
                "distilbert: untied vocab_projector is unsupported "
                "(tie_word_embeddings=False)")
    except KeyError:
        pass  # tied weights may be absent from the serialized dict
    return {
        "word_embeddings": jnp.asarray(get("embeddings.word_embeddings.weight")),
        "position_embeddings": jnp.asarray(
            get("embeddings.position_embeddings.weight")),
        "token_type_embeddings": jnp.zeros((1, d), jnp.float32),
        "emb_ln_scale": jnp.asarray(get("embeddings.LayerNorm.weight")),
        "emb_ln_bias": jnp.asarray(get("embeddings.LayerNorm.bias")),
        "blocks": {
            "qkv_w": jnp.asarray(np.stack([fuse_qkv(i) for i in range(l)])),
            "qkv_b": jnp.asarray(np.stack([fuse_qkv_b(i) for i in range(l)])),
            "attn_out_w": stack("transformer.layer.{i}.attention.out_lin.weight", t),
            "attn_out_b": stack("transformer.layer.{i}.attention.out_lin.bias"),
            "attn_ln_scale": stack("transformer.layer.{i}.sa_layer_norm.weight"),
            "attn_ln_bias": stack("transformer.layer.{i}.sa_layer_norm.bias"),
            "inter_w": stack("transformer.layer.{i}.ffn.lin1.weight", t),
            "inter_b": stack("transformer.layer.{i}.ffn.lin1.bias"),
            "out_w": stack("transformer.layer.{i}.ffn.lin2.weight", t),
            "out_b": stack("transformer.layer.{i}.ffn.lin2.bias"),
            "out_ln_scale": stack("transformer.layer.{i}.output_layer_norm.weight"),
            "out_ln_bias": stack("transformer.layer.{i}.output_layer_norm.bias"),
        },
        "mlm_dense_w": jnp.asarray(get("vocab_transform.weight").T),
        "mlm_dense_b": jnp.asarray(get("vocab_transform.bias")),
        "mlm_ln_scale": jnp.asarray(get("vocab_layer_norm.weight")),
        "mlm_ln_bias": jnp.asarray(get("vocab_layer_norm.bias")),
        "mlm_bias": jnp.asarray(get("vocab_projector.bias")),
    }


_register("BertForMaskedLM", _bert_translate, _bert_convert, _bert_build)
_register("DistilBertForMaskedLM", _distilbert_translate,
          _distilbert_convert, _bert_build)
_register("GPT2LMHeadModel", _gpt2_translate, _gpt2_convert, _gpt2_build)
_register("OPTForCausalLM", _opt_translate, _opt_convert, _opt_build)
_register("LlamaForCausalLM", _llama_translate, _llama_convert, _llama_build)
_register("MixtralForCausalLM", _mixtral_translate, _mixtral_convert,
          _mixtral_build)
_register("BloomForCausalLM", _bloom_translate, _bloom_convert, _bloom_build)
_register("GPTNeoXForCausalLM", _neox_translate, _neox_convert, _neox_build)
_register("GPTJForCausalLM", _gptj_translate, _gptj_convert, _gptj_build)
_register("GPTNeoForCausalLM", _gptneo_translate, _gptneo_convert,
          _gptneo_build)


def _clip_translate(hf):
    from ..models.clip import CLIPConfig
    return CLIPConfig.from_hf(hf)


def _clip_convert(cfg, sd):
    from ..models.clip import from_hf_state_dict
    return from_hf_state_dict(cfg, sd)


def _clip_build(cfg):
    from ..models import clip
    return clip.build(cfg)


_register("CLIPModel", _clip_translate, _clip_convert, _clip_build)


def generic_policies():
    return list(_POLICIES.values())


def policy_for(model_or_config) -> Optional[HFPolicy]:
    """Look up the policy for a HF model/config by its architecture name."""
    cfg = getattr(model_or_config, "config", model_or_config)
    archs = getattr(cfg, "architectures", None) or []
    cls_name = type(model_or_config).__name__
    for name in list(archs) + [cls_name]:
        pol = _POLICIES.get(str(name).lower())
        if pol is not None:
            return pol
    return None


def replace_module(hf_model=None, config=None, state_dict=None):
    """HF model -> (ModelSpec, params) (reference ``replace_module.py:308``).

    Pass either a torch HF model, or its ``config`` + ``state_dict``.
    """
    if hf_model is not None:
        config = hf_model.config
        state_dict = hf_model.state_dict()
    assert config is not None and state_dict is not None
    pol = policy_for(hf_model if hf_model is not None else config)
    if pol is None:
        archs = getattr(config, "architectures", None)
        raise ValueError(
            f"no injection policy for architecture {archs}; supported: "
            f"{sorted(p.arch for p in _POLICIES.values())}")
    cfg = pol.translate_config(config)
    params = pol.convert_weights(cfg, dict(state_dict))
    return pol.build(cfg), params
