"""Stable-Diffusion UNet (UNet2DConditionModel), TPU-native.

Reference parity: the diffusers UNet injection policy
(``module_inject/replace_policy.py`` UNetPolicy, ``containers/unet.py``) and
the diffusers attention path (``ops/transformer/inference/
diffusers_attention.py``); the spatial bias-add kernels
(``csrc/spatial/csrc/opt_bias_add.cu``) are XLA fusions on TPU.

Architecture (SD 1.x UNet2DConditionModel):
 - sinusoidal timestep embedding -> 2-layer silu MLP
 - conv_in -> down path: CrossAttnDownBlock2D x3 (resnet+transformer pairs,
   stride-2 downsample) + DownBlock2D
 - mid: resnet, transformer, resnet
 - up path: mirrored with skip-connection concat into every resnet
 - GroupNorm/silu/conv_out
 - the transformer block is the diffusers BasicTransformerBlock: self-attn,
   cross-attn over the text-encoder context, GEGLU feed-forward, pre-LN

No diffusers package exists in this image, so parity is structural and
tests are self-consistent (shapes incl. the ~860M SD-1.x param count,
conditioning sensitivity, denoising training) and the checkpoint
converter (``from_hf_state_dict``) follows the published diffusers naming,
validated by a fabricated-dict roundtrip test like the VAE sibling's.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime.model import ModelSpec
from .vae import (conv_init, gn_init, conv2d, group_norm)

PyTree = Any


@dataclasses.dataclass
class UNetConfig:
    in_channels: int = 4
    out_channels: int = 4
    block_channels: Sequence[int] = (320, 640, 1280, 1280)
    #: True for blocks with transformer (cross-attention) layers; SD 1.x
    #: uses attention in all but the last down block
    block_has_attn: Sequence[bool] = (True, True, True, False)
    layers_per_block: int = 2
    norm_groups: int = 32
    #: head COUNT per attention layer (diffusers SD 1.x attention_head_dim=8
    #: is historically the head count: 8 heads with dims 40/80/160 per block)
    attn_heads: int = 8
    cross_attention_dim: int = 768
    sample_size: int = 64

    @staticmethod
    def sd_unet() -> "UNetConfig":
        return UNetConfig()

    @staticmethod
    def tiny() -> "UNetConfig":
        return UNetConfig(block_channels=(16, 32), block_has_attn=(True, False),
                          layers_per_block=1, norm_groups=4, attn_heads=2,
                          cross_attention_dim=24, sample_size=16)

    @property
    def time_embed_dim(self) -> int:
        return self.block_channels[0] * 4

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: init_params(self, jax.random.PRNGKey(0)))))


# ----------------------------------------------------------------- primitives
def _dense_init(key, din, dout, bias=True):
    p = {"w": (jax.random.normal(key, (din, dout)) /
               np.sqrt(din)).astype(jnp.float32)}
    if bias:
        p["b"] = jnp.zeros((dout,))
    return p


def _dense(p, x):
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def _ln_init(d):
    return {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))}


def _ln(p, x, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps) * p["scale"] +
            p["bias"]).astype(x.dtype)


def timestep_embedding(timesteps, dim: int, max_period: float = 10000.0):
    """diffusers get_timestep_embedding (flip_sin_to_cos=True, scale=1)."""
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) *
                    jnp.arange(half, dtype=jnp.float32) / half)
    ang = timesteps.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


def _resnet_init(key, cin, cout, temb_dim):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {"norm1": gn_init(cin), "conv1": conv_init(k1, cin, cout, 3),
         "time_emb": _dense_init(k2, temb_dim, cout),
         "norm2": gn_init(cout), "conv2": conv_init(k3, cout, cout, 3)}
    if cin != cout:
        p["shortcut"] = conv_init(k4, cin, cout, 1)
    return p


def resnet_block(p, x, temb, groups: int):
    h = conv2d(p["conv1"], jax.nn.silu(group_norm(p["norm1"], x, groups)))
    h = h + _dense(p["time_emb"], jax.nn.silu(temb))[:, :, None, None]
    h = conv2d(p["conv2"], jax.nn.silu(group_norm(p["norm2"], h, groups)))
    if "shortcut" in p:
        x = conv2d(p["shortcut"], x, padding=0)
    return x + h


def _mha_init(key, q_dim, kv_dim, heads, head_dim):
    inner = heads * head_dim
    ks = jax.random.split(key, 4)
    return {"q": _dense_init(ks[0], q_dim, inner, bias=False),
            "k": _dense_init(ks[1], kv_dim, inner, bias=False),
            "v": _dense_init(ks[2], kv_dim, inner, bias=False),
            "out": _dense_init(ks[3], inner, q_dim)}


def _mha(p, x, context, heads: int):
    b, n, _ = x.shape
    q = _dense(p["q"], x)
    k = _dense(p["k"], context)
    v = _dense(p["v"], context)
    hd = q.shape[-1] // heads
    q = q.reshape(b, n, heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, -1, heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, -1, heads, hd).transpose(0, 2, 1, 3)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / \
        np.sqrt(hd)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    o = o.transpose(0, 2, 1, 3).reshape(b, n, -1)
    return _dense(p["out"], o)


def _tx_block_init(key, dim, ctx_dim, heads, head_dim):
    ks = jax.random.split(key, 5)
    return {"ln1": _ln_init(dim),
            "attn1": _mha_init(ks[0], dim, dim, heads, head_dim),
            "ln2": _ln_init(dim),
            "attn2": _mha_init(ks[1], dim, ctx_dim, heads, head_dim),
            "ln3": _ln_init(dim),
            "geglu": _dense_init(ks[2], dim, 8 * dim),
            "ff_out": _dense_init(ks[3], 4 * dim, dim)}


def _tx_block(p, x, context, heads: int):
    """diffusers BasicTransformerBlock: self-attn, cross-attn, GEGLU FF."""
    y = _ln(p["ln1"], x)
    x = x + _mha(p["attn1"], y, y, heads)
    x = x + _mha(p["attn2"], _ln(p["ln2"], x), context, heads)
    h = _dense(p["geglu"], _ln(p["ln3"], x))
    a, gate = jnp.split(h, 2, axis=-1)
    return x + _dense(p["ff_out"], a * jax.nn.gelu(gate))


def _transformer_init(key, c, ctx_dim, heads, head_dim):
    ks = jax.random.split(key, 3)
    return {"norm": gn_init(c),
            "proj_in": conv_init(ks[0], c, c, 1),
            "block": _tx_block_init(ks[1], c, ctx_dim, heads, head_dim),
            "proj_out": conv_init(ks[2], c, c, 1)}


def transformer_2d(p, x, context, groups: int, heads: int):
    """diffusers Transformer2DModel with one BasicTransformerBlock."""
    b, c, h, w = x.shape
    res = x
    y = group_norm(p["norm"], x, groups)
    y = conv2d(p["proj_in"], y, padding=0)
    y = y.reshape(b, c, h * w).transpose(0, 2, 1)
    y = _tx_block(p["block"], y, context, heads)
    y = y.transpose(0, 2, 1).reshape(b, c, h, w)
    return res + conv2d(p["proj_out"], y, padding=0)


# ----------------------------------------------------------------- init
def init_params(cfg: UNetConfig, rng) -> PyTree:
    chans = list(cfg.block_channels)
    temb = cfg.time_embed_dim
    keys = iter(jax.random.split(rng, 400))
    heads = cfg.attn_heads

    p: Dict[str, Any] = {
        "time_mlp1": _dense_init(next(keys), chans[0], temb),
        "time_mlp2": _dense_init(next(keys), temb, temb),
        "conv_in": conv_init(next(keys), cfg.in_channels, chans[0], 3),
    }
    down = []
    c = chans[0]
    for i, ch in enumerate(chans):
        blk = {"resnets": []}
        if cfg.block_has_attn[i]:
            blk["attns"] = []
        for j in range(cfg.layers_per_block):
            blk["resnets"].append(_resnet_init(next(keys),
                                               c if j == 0 else ch, ch, temb))
            if cfg.block_has_attn[i]:
                blk["attns"].append(_transformer_init(
                    next(keys), ch, cfg.cross_attention_dim, heads,
                    ch // heads))
        c = ch
        if i < len(chans) - 1:
            blk["down"] = conv_init(next(keys), ch, ch, 3)
        down.append(blk)
    p["down"] = down
    p["mid"] = {"res1": _resnet_init(next(keys), c, c, temb),
                "attn": _transformer_init(next(keys), c,
                                          cfg.cross_attention_dim, heads,
                                          c // heads),
                "res2": _resnet_init(next(keys), c, c, temb)}
    up = []
    rev = list(reversed(chans))
    for i, ch in enumerate(rev):
        prev_out = c
        has_attn = list(reversed(cfg.block_has_attn))[i]
        blk = {"resnets": []}
        if has_attn:
            blk["attns"] = []
        for j in range(cfg.layers_per_block + 1):
            # skip channels: reversed down-path outputs, incl. conv_in's
            skip_ch = rev[min(i + 1, len(rev) - 1)] \
                if j == cfg.layers_per_block else ch
            if i == len(rev) - 1 and j == cfg.layers_per_block:
                skip_ch = chans[0]
            blk["resnets"].append(_resnet_init(
                next(keys), prev_out + skip_ch, ch, temb))
            prev_out = ch
            if has_attn:
                blk["attns"].append(_transformer_init(
                    next(keys), ch, cfg.cross_attention_dim, heads,
                    ch // heads))
        c = ch
        if i < len(rev) - 1:
            blk["up"] = conv_init(next(keys), ch, ch, 3)
        up.append(blk)
    p["up"] = up
    p["norm_out"] = gn_init(chans[0])
    p["conv_out"] = conv_init(next(keys), chans[0], cfg.out_channels, 3)
    return p


# ----------------------------------------------------------------- forward
def forward(cfg: UNetConfig, params, sample, timesteps, encoder_hidden_states,
            rng=None, train: bool = True):
    """sample: [B, 4, H, W]; timesteps: [B]; context: [B, T, ctx_dim]."""
    g = cfg.norm_groups
    chans = list(cfg.block_channels)
    heads = cfg.attn_heads
    ctx = encoder_hidden_states

    temb = timestep_embedding(timesteps, chans[0])
    temb = _dense(params["time_mlp2"],
                  jax.nn.silu(_dense(params["time_mlp1"], temb)))

    h = conv2d(params["conv_in"], sample)
    skips = [h]
    for i, blk in enumerate(params["down"]):
        for j, r in enumerate(blk["resnets"]):
            h = resnet_block(r, h, temb, g)
            if "attns" in blk:
                h = transformer_2d(blk["attns"][j], h, ctx, g, heads)
            skips.append(h)
        if "down" in blk:
            hpad = jnp.pad(h, ((0, 0), (0, 0), (0, 1), (0, 1)))
            h = jax.lax.conv_general_dilated(
                hpad, blk["down"]["w"].astype(h.dtype), (2, 2),
                padding=[(0, 0), (0, 0)],
                dimension_numbers=("NCHW", "OIHW", "NCHW")) + \
                blk["down"]["b"].astype(h.dtype)[None, :, None, None]
            skips.append(h)

    h = resnet_block(params["mid"]["res1"], h, temb, g)
    h = transformer_2d(params["mid"]["attn"], h, ctx, g, heads)
    h = resnet_block(params["mid"]["res2"], h, temb, g)

    for i, blk in enumerate(params["up"]):
        for j, r in enumerate(blk["resnets"]):
            skip = skips.pop()
            h = jnp.concatenate([h, skip], axis=1)
            h = resnet_block(r, h, temb, g)
            if "attns" in blk:
                h = transformer_2d(blk["attns"][j], h, ctx, g, heads)
        if "up" in blk:
            b, c, hh, ww = h.shape
            h = jax.image.resize(h, (b, c, 2 * hh, 2 * ww), "nearest")
            h = conv2d(blk["up"], h)

    h = jax.nn.silu(group_norm(params["norm_out"], h, g))
    return conv2d(params["conv_out"], h)


def loss_from_batch(cfg: UNetConfig, params, batch, rng=None,
                    train: bool = True):
    """Denoising MSE: predict the noise added to the latents (the DDPM /
    SD training objective)."""
    eps = batch["noise"]
    noisy = batch["noisy_latents"]
    pred = forward(cfg, params, noisy, batch["timesteps"],
                   batch["encoder_hidden_states"], rng=rng, train=train)
    return jnp.mean((pred.astype(jnp.float32) - eps.astype(jnp.float32)) ** 2)


def build(cfg: Optional[UNetConfig] = None, **overrides) -> ModelSpec:
    cfg = cfg or UNetConfig(**overrides)

    def init_fn(rng):
        return init_params(cfg, rng)

    def loss_fn(params, batch, rng=None, train=True):
        return loss_from_batch(cfg, params, batch, rng=rng, train=train)

    def apply_fn(params, batch, rng=None):
        return forward(cfg, params, batch["sample"], batch["timesteps"],
                       batch["encoder_hidden_states"], train=False)

    return ModelSpec(
        init_fn=init_fn, model_config=cfg, loss_fn=loss_fn, apply_fn=apply_fn,
                     name=f"unet-{cfg.block_channels[0]}c")


# --------------------------------------------------------------------- HF I/O
def _np(t) -> np.ndarray:
    return np.asarray(t.detach().cpu().numpy() if hasattr(t, "detach") else t,
                      dtype=np.float32)


def from_hf_state_dict(cfg: UNetConfig, sd: Dict[str, Any]) -> PyTree:
    """diffusers ``UNet2DConditionModel`` state dict -> param pytree
    (published naming: time_embedding.linear_{1,2}, down_blocks.N.resnets.M
    .{norm1,conv1,time_emb_proj,...}, .attentions.M.transformer_blocks.0
    .{attn1,attn2,ff.net.0.proj,ff.net.2}, mid_block, up_blocks,
    conv_norm_out/conv_out).  Validated by a fabricated-naming roundtrip
    test (no diffusers package in this image to diff against)."""
    def get(name):
        return _np(sd[name])

    def conv(name):
        return {"w": jnp.asarray(get(name + ".weight")),
                "b": jnp.asarray(get(name + ".bias"))}

    def gn(name):
        return {"scale": jnp.asarray(get(name + ".weight")),
                "bias": jnp.asarray(get(name + ".bias"))}

    def dense(name, bias=True):
        p = {"w": jnp.asarray(get(name + ".weight").T)}
        if bias:
            p["b"] = jnp.asarray(get(name + ".bias"))
        return p

    def resnet(prefix):
        p = {"norm1": gn(prefix + ".norm1"), "conv1": conv(prefix + ".conv1"),
             "time_emb": dense(prefix + ".time_emb_proj"),
             "norm2": gn(prefix + ".norm2"), "conv2": conv(prefix + ".conv2")}
        if prefix + ".conv_shortcut.weight" in sd:
            p["shortcut"] = conv(prefix + ".conv_shortcut")
        return p

    def tx(prefix):
        b = prefix + ".transformer_blocks.0"
        return {
            "norm": gn(prefix + ".norm"),
            "proj_in": conv(prefix + ".proj_in"),
            "block": {
                "ln1": {"scale": jnp.asarray(get(b + ".norm1.weight")),
                        "bias": jnp.asarray(get(b + ".norm1.bias"))},
                "attn1": {"q": dense(b + ".attn1.to_q", bias=False),
                          "k": dense(b + ".attn1.to_k", bias=False),
                          "v": dense(b + ".attn1.to_v", bias=False),
                          "out": dense(b + ".attn1.to_out.0")},
                "ln2": {"scale": jnp.asarray(get(b + ".norm2.weight")),
                        "bias": jnp.asarray(get(b + ".norm2.bias"))},
                "attn2": {"q": dense(b + ".attn2.to_q", bias=False),
                          "k": dense(b + ".attn2.to_k", bias=False),
                          "v": dense(b + ".attn2.to_v", bias=False),
                          "out": dense(b + ".attn2.to_out.0")},
                "ln3": {"scale": jnp.asarray(get(b + ".norm3.weight")),
                        "bias": jnp.asarray(get(b + ".norm3.bias"))},
                "geglu": dense(b + ".ff.net.0.proj"),
                "ff_out": dense(b + ".ff.net.2"),
            },
            "proj_out": conv(prefix + ".proj_out"),
        }

    chans = list(cfg.block_channels)
    p: Dict[str, Any] = {
        "time_mlp1": dense("time_embedding.linear_1"),
        "time_mlp2": dense("time_embedding.linear_2"),
        "conv_in": conv("conv_in"),
    }
    down = []
    for i in range(len(chans)):
        blk: Dict[str, Any] = {"resnets": [
            resnet(f"down_blocks.{i}.resnets.{j}")
            for j in range(cfg.layers_per_block)]}
        if cfg.block_has_attn[i]:
            blk["attns"] = [tx(f"down_blocks.{i}.attentions.{j}")
                            for j in range(cfg.layers_per_block)]
        if f"down_blocks.{i}.downsamplers.0.conv.weight" in sd:
            blk["down"] = conv(f"down_blocks.{i}.downsamplers.0.conv")
        down.append(blk)
    p["down"] = down
    p["mid"] = {"res1": resnet("mid_block.resnets.0"),
                "attn": tx("mid_block.attentions.0"),
                "res2": resnet("mid_block.resnets.1")}
    up = []
    for i in range(len(chans)):
        has_attn = list(reversed(cfg.block_has_attn))[i]
        blk = {"resnets": [resnet(f"up_blocks.{i}.resnets.{j}")
                           for j in range(cfg.layers_per_block + 1)]}
        if has_attn:
            blk["attns"] = [tx(f"up_blocks.{i}.attentions.{j}")
                            for j in range(cfg.layers_per_block + 1)]
        if f"up_blocks.{i}.upsamplers.0.conv.weight" in sd:
            blk["up"] = conv(f"up_blocks.{i}.upsamplers.0.conv")
        up.append(blk)
    p["up"] = up
    p["norm_out"] = gn("conv_norm_out")
    p["conv_out"] = conv("conv_out")
    return p
