"""Stable-Diffusion VAE (AutoencoderKL), TPU-native.

Reference parity: the diffusers VAE injection policy
(``module_inject/replace_policy.py`` VAEPolicy, ``containers/vae.py``) and
the spatial inference ops (``csrc/spatial/csrc/opt_bias_add.cu`` — bias-add
fusions XLA performs natively on TPU).

Architecture (SD 1.x/2.x AutoencoderKL):
 - encoder: conv_in -> 4 down blocks (2 resnets each, stride-2 downsample
   between) -> mid (resnet, single-head spatial attention, resnet) ->
   GroupNorm/silu/conv_out -> 2*latent channels (mean, logvar)
 - decoder: mirrored with 3-resnet up blocks and nearest-2x upsampling
 - quant_conv / post_quant_conv 1x1 around the latent

Layout: NCHW at the API (diffusers convention); convs run through
``lax.conv_general_dilated`` which XLA lays out for the MXU.  No diffusers
package exists in this image, so HF parity is structural: the weight
converter follows the published diffusers state-dict naming and tests are
self-consistent (shapes, KL stats, encode/decode roundtrip, gradients).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime.model import ModelSpec

PyTree = Any


@dataclasses.dataclass
class VAEConfig:
    in_channels: int = 3
    latent_channels: int = 4
    base_channels: int = 128
    channel_mults: Sequence[int] = (1, 2, 4, 4)
    layers_per_block: int = 2
    norm_groups: int = 32
    sample_size: int = 256
    scaling_factor: float = 0.18215

    @staticmethod
    def sd_vae() -> "VAEConfig":
        return VAEConfig()

    @staticmethod
    def tiny() -> "VAEConfig":
        return VAEConfig(base_channels=16, channel_mults=(1, 2),
                         layers_per_block=1, norm_groups=4, sample_size=32,
                         latent_channels=4)

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: init_params(self, jax.random.PRNGKey(0)))))


# ----------------------------------------------------------------- primitives
def conv_init(key, cin, cout, k):
    fan_in = cin * k * k
    w = jax.random.normal(key, (cout, cin, k, k)) / np.sqrt(fan_in)
    return {"w": w.astype(jnp.float32), "b": jnp.zeros((cout,))}


def conv2d(p, x, stride: int = 1, padding: int = 1):
    """x: [B, C, H, W]; weight [O, I, kh, kw] (torch layout)."""
    out = jax.lax.conv_general_dilated(
        x, p["w"].astype(x.dtype), window_strides=(stride, stride),
        padding=[(padding, padding), (padding, padding)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return out + p["b"].astype(x.dtype)[None, :, None, None]


def group_norm(p, x, groups: int, eps: float = 1e-6):
    b, c, h, w = x.shape
    xg = x.astype(jnp.float32).reshape(b, groups, c // groups, h, w)
    mean = xg.mean(axis=(2, 3, 4), keepdims=True)
    var = xg.var(axis=(2, 3, 4), keepdims=True)
    xg = (xg - mean) * jax.lax.rsqrt(var + eps)
    xn = xg.reshape(b, c, h, w)
    return (xn * p["scale"][None, :, None, None] +
            p["bias"][None, :, None, None]).astype(x.dtype)


def gn_init(c):
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}


def _resnet_init(key, cin, cout):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"norm1": gn_init(cin), "conv1": conv_init(k1, cin, cout, 3),
         "norm2": gn_init(cout), "conv2": conv_init(k2, cout, cout, 3)}
    if cin != cout:
        p["shortcut"] = conv_init(k3, cin, cout, 1)
    return p


def resnet_block(p, x, groups: int):
    h = group_norm(p["norm1"], x, groups)
    h = conv2d(p["conv1"], jax.nn.silu(h))
    h = group_norm(p["norm2"], h, groups)
    h = conv2d(p["conv2"], jax.nn.silu(h))
    if "shortcut" in p:
        x = conv2d(p["shortcut"], x, padding=0)
    return x + h


def _attn_init(key, c):
    ks = jax.random.split(key, 4)
    dense = lambda k: {"w": (jax.random.normal(k, (c, c)) /
                             np.sqrt(c)).astype(jnp.float32),
                       "b": jnp.zeros((c,))}
    return {"norm": gn_init(c), "q": dense(ks[0]), "k": dense(ks[1]),
            "v": dense(ks[2]), "proj": dense(ks[3])}


def attention_block(p, x, groups: int):
    """Single-head spatial self-attention over H*W positions."""
    b, c, hh, ww = x.shape
    h = group_norm(p["norm"], x, groups)
    flat = h.reshape(b, c, hh * ww).transpose(0, 2, 1)      # [B, HW, C]
    q = flat @ p["q"]["w"].astype(flat.dtype) + p["q"]["b"].astype(flat.dtype)
    k = flat @ p["k"]["w"].astype(flat.dtype) + p["k"]["b"].astype(flat.dtype)
    v = flat @ p["v"]["w"].astype(flat.dtype) + p["v"]["b"].astype(flat.dtype)
    scores = (q @ k.transpose(0, 2, 1)).astype(jnp.float32) / np.sqrt(c)
    probs = jax.nn.softmax(scores, axis=-1).astype(flat.dtype)
    o = probs @ v
    o = o @ p["proj"]["w"].astype(o.dtype) + p["proj"]["b"].astype(o.dtype)
    return x + o.transpose(0, 2, 1).reshape(b, c, hh, ww)


# ----------------------------------------------------------------- init
def init_params(cfg: VAEConfig, rng) -> PyTree:
    mults = list(cfg.channel_mults)
    chans = [cfg.base_channels * m for m in mults]
    keys = iter(jax.random.split(rng, 200))

    # encoder
    enc: Dict[str, Any] = {"conv_in": conv_init(next(keys), cfg.in_channels,
                                                chans[0], 3)}
    down = []
    c = chans[0]
    for i, ch in enumerate(chans):
        blk = {"resnets": [_resnet_init(next(keys), c if j == 0 else ch, ch)
                           for j in range(cfg.layers_per_block)]}
        c = ch
        if i < len(chans) - 1:
            blk["down"] = conv_init(next(keys), ch, ch, 3)
        down.append(blk)
    enc["down"] = down
    enc["mid"] = {"res1": _resnet_init(next(keys), c, c),
                  "attn": _attn_init(next(keys), c),
                  "res2": _resnet_init(next(keys), c, c)}
    enc["norm_out"] = gn_init(c)
    enc["conv_out"] = conv_init(next(keys), c, 2 * cfg.latent_channels, 3)

    # decoder (mirrored)
    dec: Dict[str, Any] = {"conv_in": conv_init(next(keys),
                                                cfg.latent_channels, c, 3)}
    dec["mid"] = {"res1": _resnet_init(next(keys), c, c),
                  "attn": _attn_init(next(keys), c),
                  "res2": _resnet_init(next(keys), c, c)}
    up = []
    for i, ch in enumerate(reversed(chans)):
        blk = {"resnets": [_resnet_init(next(keys), c if j == 0 else ch, ch)
                           for j in range(cfg.layers_per_block + 1)]}
        c = ch
        if i < len(chans) - 1:
            blk["up"] = conv_init(next(keys), ch, ch, 3)
        up.append(blk)
    dec["up"] = up
    dec["norm_out"] = gn_init(c)
    dec["conv_out"] = conv_init(next(keys), c, cfg.in_channels, 3)

    return {"encoder": enc, "decoder": dec,
            "quant_conv": conv_init(next(keys), 2 * cfg.latent_channels,
                                    2 * cfg.latent_channels, 1),
            "post_quant_conv": conv_init(next(keys), cfg.latent_channels,
                                         cfg.latent_channels, 1)}


# ----------------------------------------------------------------- forward
def encode(cfg: VAEConfig, params, x):
    """x: [B, 3, H, W] -> (mean, logvar) each [B, latent, H/2^d, W/2^d]."""
    p = params["encoder"]
    g = cfg.norm_groups
    h = conv2d(p["conv_in"], x)
    for i, blk in enumerate(p["down"]):
        for r in blk["resnets"]:
            h = resnet_block(r, h, g)
        if "down" in blk:
            # diffusers pads (0,1,0,1) then stride-2 valid conv
            h = jnp.pad(h, ((0, 0), (0, 0), (0, 1), (0, 1)))
            h = jax.lax.conv_general_dilated(
                h, blk["down"]["w"].astype(h.dtype), (2, 2),
                padding=[(0, 0), (0, 0)],
                dimension_numbers=("NCHW", "OIHW", "NCHW")) + \
                blk["down"]["b"].astype(h.dtype)[None, :, None, None]
    h = resnet_block(p["mid"]["res1"], h, g)
    h = attention_block(p["mid"]["attn"], h, g)
    h = resnet_block(p["mid"]["res2"], h, g)
    h = conv2d(p["conv_out"], jax.nn.silu(group_norm(p["norm_out"], h, g)))
    h = conv2d(params["quant_conv"], h, padding=0)
    mean, logvar = jnp.split(h, 2, axis=1)
    return mean, jnp.clip(logvar, -30.0, 20.0)


def decode(cfg: VAEConfig, params, z):
    p = params["decoder"]
    g = cfg.norm_groups
    h = conv2d(params["post_quant_conv"], z, padding=0)
    h = conv2d(p["conv_in"], h)
    h = resnet_block(p["mid"]["res1"], h, g)
    h = attention_block(p["mid"]["attn"], h, g)
    h = resnet_block(p["mid"]["res2"], h, g)
    for blk in p["up"]:
        for r in blk["resnets"]:
            h = resnet_block(r, h, g)
        if "up" in blk:
            b, c, hh, ww = h.shape
            h = jax.image.resize(h, (b, c, 2 * hh, 2 * ww), "nearest")
            h = conv2d(blk["up"], h)
    h = conv2d(p["conv_out"], jax.nn.silu(group_norm(p["norm_out"], h, g)))
    return h


def sample_latent(mean, logvar, rng):
    return mean + jnp.exp(0.5 * logvar) * jax.random.normal(rng, mean.shape)


def loss_from_batch(cfg: VAEConfig, params, batch, rng=None,
                    train: bool = True, kl_weight: float = 1e-6):
    """VAE objective: reconstruction MSE + KL (the SD-VAE training loss
    minus the adversarial/perceptual terms)."""
    x = batch["pixel_values"] if isinstance(batch, dict) else batch
    mean, logvar = encode(cfg, params, x)
    z = sample_latent(mean, logvar, rng) if (train and rng is not None) \
        else mean
    recon = decode(cfg, params, z)
    rec = jnp.mean((recon.astype(jnp.float32) - x.astype(jnp.float32)) ** 2)
    kl = 0.5 * jnp.mean(mean.astype(jnp.float32) ** 2 +
                        jnp.exp(logvar.astype(jnp.float32)) -
                        1.0 - logvar.astype(jnp.float32))
    return rec + kl_weight * kl


# ----------------------------------------------------------------- HF I/O
def _np(t) -> np.ndarray:
    return np.asarray(t.detach().cpu().numpy() if hasattr(t, "detach") else t,
                      dtype=np.float32)


def from_hf_state_dict(cfg: VAEConfig, sd: Dict[str, Any]) -> PyTree:
    """diffusers AutoencoderKL state dict -> param pytree (published naming:
    encoder.down_blocks.N.resnets.M.{norm1,conv1,...}, mid_block.attentions.0
    .to_{q,k,v,out.0}, decoder.up_blocks..., quant_conv/post_quant_conv)."""
    def conv(name):
        return {"w": jnp.asarray(_np(sd[name + ".weight"])),
                "b": jnp.asarray(_np(sd[name + ".bias"]))}

    def gn(name):
        return {"scale": jnp.asarray(_np(sd[name + ".weight"])),
                "bias": jnp.asarray(_np(sd[name + ".bias"]))}

    def dense(name):
        w = _np(sd[name + ".weight"])
        if w.ndim == 4:  # old checkpoints store attention projs as 1x1 convs
            w = w[:, :, 0, 0]
        return {"w": jnp.asarray(w.T), "b": jnp.asarray(_np(sd[name + ".bias"]))}

    def resnet(prefix):
        p = {"norm1": gn(prefix + ".norm1"), "conv1": conv(prefix + ".conv1"),
             "norm2": gn(prefix + ".norm2"), "conv2": conv(prefix + ".conv2")}
        if prefix + ".conv_shortcut.weight" in sd:
            p["shortcut"] = conv(prefix + ".conv_shortcut")
        return p

    def attn(prefix):
        return {"norm": gn(prefix + ".group_norm"),
                "q": dense(prefix + ".to_q"), "k": dense(prefix + ".to_k"),
                "v": dense(prefix + ".to_v"),
                "proj": dense(prefix + ".to_out.0")}

    def mid(prefix):
        return {"res1": resnet(prefix + ".resnets.0"),
                "attn": attn(prefix + ".attentions.0"),
                "res2": resnet(prefix + ".resnets.1")}

    n_blocks = len(cfg.channel_mults)
    enc = {"conv_in": conv("encoder.conv_in"),
           "down": [], "mid": mid("encoder.mid_block"),
           "norm_out": gn("encoder.conv_norm_out"),
           "conv_out": conv("encoder.conv_out")}
    for i in range(n_blocks):
        blk = {"resnets": [
            resnet(f"encoder.down_blocks.{i}.resnets.{j}")
            for j in range(cfg.layers_per_block)]}
        key = f"encoder.down_blocks.{i}.downsamplers.0.conv.weight"
        if key in sd:
            blk["down"] = conv(f"encoder.down_blocks.{i}.downsamplers.0.conv")
        enc["down"].append(blk)

    dec = {"conv_in": conv("decoder.conv_in"),
           "mid": mid("decoder.mid_block"),
           "up": [], "norm_out": gn("decoder.conv_norm_out"),
           "conv_out": conv("decoder.conv_out")}
    for i in range(n_blocks):
        blk = {"resnets": [
            resnet(f"decoder.up_blocks.{i}.resnets.{j}")
            for j in range(cfg.layers_per_block + 1)]}
        key = f"decoder.up_blocks.{i}.upsamplers.0.conv.weight"
        if key in sd:
            blk["up"] = conv(f"decoder.up_blocks.{i}.upsamplers.0.conv")
        dec["up"].append(blk)

    return {"encoder": enc, "decoder": dec,
            "quant_conv": conv("quant_conv"),
            "post_quant_conv": conv("post_quant_conv")}


def build(cfg: Optional[VAEConfig] = None, **overrides) -> ModelSpec:
    cfg = cfg or VAEConfig(**overrides)

    def init_fn(rng):
        return init_params(cfg, rng)

    def loss_fn(params, batch, rng=None, train=True):
        return loss_from_batch(cfg, params, batch, rng=rng, train=train)

    def apply_fn(params, batch, rng=None):
        x = batch["pixel_values"] if isinstance(batch, dict) else batch
        mean, logvar = encode(cfg, params, x)
        return decode(cfg, params, mean)

    return ModelSpec(
        init_fn=init_fn, model_config=cfg, loss_fn=loss_fn, apply_fn=apply_fn,
                     name=f"vae-{cfg.base_channels}c")
