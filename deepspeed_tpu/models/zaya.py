"""ZAYA1 (``Zyphra/ZAYA1-8B``, ``model_type: zaya``): compressed
convolutional attention over a paged K/V pool with per-slot convolution
TAILS, and a top-1 expert layer behind an MLP router that carries its state
from layer to layer — served through ``init_serving`` / ``ServingEngine``.

``x0 = E[ids]``; each of the ``L`` layers is an attention sublayer then an
expert sublayer, each merged as ``x <- (x + b_r) * s_r + (f(RMSNorm(x)) +
b_f) * s_f`` (four learned vectors of width ``d`` a sublayer); ``logits =
RMSNorm(x) E^T`` (a tied head).

* **attention** (grouped: ``H`` query heads, ``G`` KV heads of ``hd``; all
  of it at width ``(H + G) hd``, well under ``d``).  With ``h`` the normed
  input: ``q~ = h W_q``, ``k~ = h W_k``; their channels side by side, ``c =
  [q~ | k~]``, pass two causal convolutions of two taps — depthwise ``c1[t]
  = w0[0] c[t] + w0[1] c[t-1]``, then grouped by head ``c2[t] = W1[0] c1[t]
  + W1[1] c1[t-1]`` (a ``hd x hd`` block a head a tap) — and the q-k mean
  taken BEFORE them is added back: ``[q | k] = c2 + [(q~ + rep(k~)) / 2 |
  (mean(q~) + k~) / 2]`` (``rep`` / ``mean`` over a KV head's ``H / G``
  query heads).  ``q`` and ``k`` are L2-normalised a head to ``sqrt(hd)``
  (``k`` times one learned temperature a KV head), rotated over the first
  ``partial_rotary`` of their channels (rotate-half), and ``v[t] = [h[t]
  W_va | h[t-1] W_vb]``: half of a KV head's value is the projection of the
  token BEFORE.  Softmax attention at ``1 / sqrt(hd)`` and ``W_o`` follow.
* **experts** (``E`` SwiGLU experts, top-1, none shared).  With ``y`` the
  normed input and ``r`` the router stream the layer BEFORE left (zero
  into layer 0): ``r <- y W_down + gamma r`` (one learned scalar a layer),
  ``s = softmax_fp32(W_c GELU(W_b GELU(W_a r)))``, ``e = argmax s``, ``out =
  s_e expert_e(y)`` — the router's OUTPUT goes to ``moe/routed.py
  routed_ffn(routed=)``; the layer loop carries ``(x, r)``.  The router
  stream and its three small products are float32 (``precision=HIGHEST``):
  one expert a token, so a flipped near-tie replaces the whole FFN output.

**The cache.**  The FINISHED ``k`` and ``v`` are cached a token in the paged
pool's ``full`` kind like any K/V (``ops/paged_kv.py``; temperature, norm
and rotation are folded in before the write) and read by the paged decode
kernel and the flash prefill that exist.  But a key is not a function of its
own token: the writer of token ``t`` needs, a ROW and a layer, the TAILS
``conv [L, rows, 1, 2, (H + G) hd]`` — ``c[t-1]`` and ``c1[t-1]`` — and
``shift [L, rows, 1, 1, G hd / 2]`` — ``h[t-1] W_vb`` — (``ops/paged_kv.py``
"Tails": leaves indexed by ROW beside the paged ones, with no ``state``
leaf).  ``block_tables`` is ``{"full": the paged table}`` in a decode step
(row ``b`` IS row ``b``) and ``{"full": ..., "slot": int32 [B]}`` in a
prefill window; a window whose base is 0 starts from ZERO tails inside the
program, a chunk reads the tails its predecessor left and leaves its own
last real token's, a pad or an idle row moves none.  Served on one shard;
what else such a model is refused is ``inference/options.py``'s to say.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..moe.routed import routed_ffn
from ..runtime.model import ModelSpec
from . import cached
from .cached import live_tokens, qmm
from .llama import apply_rope, rms_norm

PyTree = Any
_EXPERT_LEAVES = ("experts_w1", "experts_w3", "experts_w2")
#: the four vectors of a sublayer's merge, by the sublayer's prefix
_MERGE = ("res_b", "res_s", "out_b", "out_s")
_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass
class ZayaConfig:
    vocab_size: int = 262272
    max_seq_len: int = 131072
    num_layers: int = 40
    hidden_size: int = 2048
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 128
    #: an expert's width (the published ``moe_intermediate_size``)
    ffn_size: int = 2048
    num_experts: int = 16
    top_k: int = 1
    #: width of the router's stream and of its MLP
    router_size: int = 256
    #: taps of the depthwise, then of the grouped causal convolution
    cca_time0: int = 2
    cca_time1: int = 2
    rope_theta: float = 5e6
    partial_rotary: float = 0.5
    rms_eps: float = 1e-5

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{self.num_heads} query heads over "
                             f"{self.num_kv_heads} KV heads")
        if (self.cca_time0, self.cca_time1) != (2, 2):
            raise NotImplementedError(
                f"cca_time0={self.cca_time0}, cca_time1={self.cca_time1}: "
                "the tails hold ONE earlier token of each convolution's "
                "input (two taps each)")
        if self.top_k != 1:
            raise NotImplementedError(
                f"top_k={self.top_k}: the router's weight is the chosen "
                "expert's own score, unnormalised (top-1)")
        if self.head_dim % 2 or self.rotary_dim % 2:
            raise ValueError(f"head_dim={self.head_dim}, partial_rotary="
                             f"{self.partial_rotary}: even widths")

    @property
    def conv_channels(self) -> int:
        """``[q~ | k~]``: every head's channels side by side."""
        return (self.num_heads + self.num_kv_heads) * self.head_dim

    @property
    def shift_channels(self) -> int:
        """The half of every KV head's value that comes a token late."""
        return self.num_kv_heads * self.head_dim // 2

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary)

    @staticmethod
    def zaya1_8b() -> "ZayaConfig":
        """Zyphra/ZAYA1-8B as published: every default."""
        return ZayaConfig()

    def layer_params(self) -> int:
        return sum(math.prod(s) for s in layer_shapes(self).values())

    def active_layer_params(self) -> int:
        """What one token multiplies with in a layer: one expert of E."""
        return self.layer_params() - (self.num_experts - self.top_k) \
            * 3 * self.hidden_size * self.ffn_size

    def num_params(self) -> int:
        return self.vocab_size * self.hidden_size + self.hidden_size \
            + self.num_layers * self.layer_params()


# ------------------------------------------------------------------ parameters
def layer_shapes(cfg: ZayaConfig):
    """One layer's leaves by name (a projection is stored ``[in, out]``;
    ``conv0_w [taps, channels]`` and ``conv1_w [taps, heads, hd in, hd out]``
    hold tap 0 on the CURRENT token, tap 1 on the one before)."""
    d, hd, f = cfg.hidden_size, cfg.head_dim, cfg.ffn_size
    h, g, r, e = cfg.num_heads, cfg.num_kv_heads, cfg.router_size, \
        cfg.num_experts
    shapes = {
        "attn_norm": (d,), "q_w": (d, h * hd), "k_w": (d, g * hd),
        "va_w": (d, cfg.shift_channels), "vb_w": (d, cfg.shift_channels),
        "o_w": (h * hd, d), "conv0_w": (2, cfg.conv_channels),
        "conv1_w": (2, h + g, hd, hd), "tau": (g,),
        "moe_norm": (d,), "down_w": (d, r), "ra_w": (r, r), "rb_w": (r, r),
        "rc_w": (r, e), "gamma": (),
        "experts_w1": (e, d, f), "experts_w3": (e, d, f),
        "experts_w2": (e, f, d)}
    shapes.update((f"{sub}_{name}", (d,)) for sub in ("attn", "moe")
                  for name in _MERGE)
    return shapes


def init_params(cfg: ZayaConfig, rng) -> PyTree:
    """Seeded parameters: the token table N(0, 0.02); a matrix N(0, 0.9 /
    sqrt(fan_in)) (0.02 at the published hidden size, in proportion at any
    other); the router's three matrices N(0, 1.8 / sqrt(fan_in)), so that
    the softmax over its outputs is as decided as a trained top-1 router's
    and the chosen score is not ~1 / E, its second and third with every
    output's weights summing to ZERO over its inputs — a GELU's outputs
    have a mean, the same for every token, and a matrix that does not
    cancel it sends a third to a half of all tokens to one expert, where a
    trained router's balance term leaves each about 1 / E of them (seeded at
    the published widths: the fullest expert's share 0.22-0.34 of 256 tokens
    without, 0.09-0.11 with; even is 0.0625); the depthwise taps U(-1 /
    sqrt(2), 1 / sqrt(2)) (a depthwise ``Conv1d``'s default), the grouped
    ones N(0, 0.9 / sqrt(2 hd)); ``tau = 1``; ``gamma`` U(0.25, 0.75); the
    merges' scales ``1 + U(-0.1, 0.1)`` and biases N(0, 0.02) around the
    published initialisation ``s = 1``, ``b = 0`` — so that a comparison
    with a reference reaches all four."""
    keys = iter(jax.random.split(rng, 64))
    n = cfg.num_layers

    def normal(shape, s):
        return (jax.random.normal(next(keys), shape) * s).astype(jnp.float32)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    def leaf(name, shape):
        shape = (n,) + shape
        if name.endswith("_norm") or name == "tau":
            return jnp.ones(shape)
        if name == "conv0_w":
            return uniform(shape, -math.sqrt(0.5), math.sqrt(0.5))
        if name == "conv1_w":
            return normal(shape, 0.9 / math.sqrt(2 * cfg.head_dim))
        if name == "gamma":
            return uniform(shape, 0.25, 0.75)
        if name.endswith("_s"):
            return 1.0 + uniform(shape, -0.1, 0.1)
        if name.endswith("_b"):
            return normal(shape, 0.02)
        scale = 1.8 if name in ("ra_w", "rb_w", "rc_w") else 0.9
        w = normal(shape, scale / math.sqrt(shape[-2]))
        if name in ("rb_w", "rc_w"):
            w = w - w.mean(axis=-2, keepdims=True)
        return w

    return {"embed": normal((cfg.vocab_size, cfg.hidden_size), 0.02),
            "blocks": {name: leaf(name, shape)
                       for name, shape in layer_shapes(cfg).items()},
            "final_norm": jnp.ones((cfg.hidden_size,))}


# ------------------------------------------------------------------- sublayers
def _merge(layer, sub: str, x, out):
    """``(x + b_r) * s_r + (out + b_f) * s_f`` in float32, as ``x``."""
    b_r, s_r, b_f, s_f = (layer[f"{sub}_{name}"].astype(jnp.float32)
                          for name in _MERGE)
    return ((x.astype(jnp.float32) + b_r) * s_r
            + (out.astype(jnp.float32) + b_f) * s_f).astype(x.dtype)


def _behind(seq, first):
    """``seq [B, T, C]`` a token late: ``first [B, C]`` then ``seq[:-1]``."""
    return jnp.concatenate([first[:, None].astype(seq.dtype), seq[:, :-1]],
                           axis=1)


def _rotate(cfg: ZayaConfig, x, positions):
    """Rotate-half rotary over the first ``rotary_dim`` channels of ``x [B,
    heads, T, hd]`` (float32) at ``positions [B, T]``."""
    rd = cfg.rotary_dim
    inv_freq = 1.0 / (cfg.rope_theta ** (
        jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    turned = apply_rope(x[..., :rd], jnp.cos(angles), jnp.sin(angles))
    return jnp.concatenate([turned, x[..., rd:]], axis=-1)


@jax.named_scope("layer/attn/qkv")
def _cca(cfg: ZayaConfig, layer, h, tails, positions):
    """Compressed convolutional attention's operands from the normed input
    ``h [B, T, d]`` and the row's ``tails = (c[-1], c1[-1] [B, channels],
    vb[-1] [B, shift channels])`` — zeros at position 0: ``(q [B, H, T, hd],
    k, v [B, G, T, hd], (c, c1, vb))``, the last the sequences a window's
    own tails are read out of.  The convolutions' products, the mean, the
    norm and the rotation are float32; ``c`` and ``c1`` are ``h``'s dtype, IN
    a window as ACROSS two (what a tail holds is what the next token of the
    same window would have read)."""
    b, t, _ = h.shape
    hq, g, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    heads, rep = hq + g, hq // g
    f32 = jnp.float32
    # (the barrier: the head splits below move these PRODUCTS, not the
    # weights — ``llama._attend_cached``)
    qt, kt, va, vb = jax.lax.optimization_barrier(
        (qmm(h, layer["q_w"]), qmm(h, layer["k_w"]), qmm(h, layer["va_w"]),
         qmm(h, layer["vb_w"])))
    c = jnp.concatenate([qt, kt], axis=-1)
    c_prev, c1_prev, vb_prev = tails
    with jax.named_scope("layer/state/conv"):
        w0 = layer["conv0_w"].astype(f32)
        c1 = (w0[0] * c.astype(f32)
              + w0[1] * _behind(c, c_prev).astype(f32)).astype(h.dtype)
        w1 = layer["conv1_w"].astype(h.dtype)
        split = lambda a: a.reshape(b, t, heads, hd)
        c2 = jnp.einsum("bthi,hio->btho", split(c1), w1[0],
                        preferred_element_type=f32) \
            + jnp.einsum("bthi,hio->btho", split(_behind(c1, c1_prev)),
                         w1[1], preferred_element_type=f32)
    q4 = qt.astype(f32).reshape(b, t, g, rep, hd)
    k3 = kt.astype(f32).reshape(b, t, g, hd)
    mean_q = (0.5 * (q4 + k3[:, :, :, None])).reshape(b, t, hq, hd)
    mean_k = 0.5 * (q4.mean(axis=3) + k3)
    qk = c2 + jnp.concatenate([mean_q, mean_k], axis=2)
    qk = qk * (math.sqrt(hd) * jax.lax.rsqrt(
        jnp.sum(qk * qk, axis=-1, keepdims=True) + 1e-12))
    q = qk[:, :, :hq].transpose(0, 2, 1, 3)
    k = (qk[:, :, hq:] * layer["tau"].astype(f32)[:, None]) \
        .transpose(0, 2, 1, 3)
    q, k = _rotate(cfg, q, positions), _rotate(cfg, k, positions)
    v = jnp.concatenate(
        [va.reshape(b, t, g, hd // 2),
         _behind(vb, vb_prev).reshape(b, t, g, hd // 2)], axis=-1)
    return q.astype(h.dtype), k.astype(h.dtype), v.transpose(0, 2, 1, 3), \
        (c, c1, vb)


def _route(cfg: ZayaConfig, layer, y, r):
    """The router: ``(r [B, T, R] float32, scores [B, T, E] float32)`` from
    the normed input ``y`` and the stream ``r`` the layer before left."""
    f32 = jnp.float32
    r = jnp.dot(y, layer["down_w"].astype(y.dtype),
                preferred_element_type=f32) \
        + layer["gamma"].astype(f32) * r
    mm = lambda a, w: jnp.dot(a, layer[w].astype(f32), precision=_HIGHEST)
    z = jax.nn.gelu(mm(r, "ra_w"), approximate=False)
    z = jax.nn.gelu(mm(z, "rb_w"), approximate=False)
    return r, jax.nn.softmax(mm(z, "rc_w"), axis=-1)


def _experts(cfg: ZayaConfig, layer, x, r, live, stacks=None,
             choices: bool = False):
    """The expert sublayer: ``(x, r, record [, (experts [B, T, 1], scores
    [B, T, E])])``.  ``stacks``: the whole ``[L, E, ..]`` expert leaves, read
    in place at ``layer["layer_index"]``; without them ``layer`` holds its
    own ``[E, ..]`` slices."""
    with jax.named_scope("layer/mlp"):
        with jax.named_scope("layer/norm"):
            y = rms_norm(x, layer["moe_norm"], cfg.rms_eps)
        with jax.named_scope("layer/moe/route"):
            r, scores = _route(cfg, layer, y, r)
            chosen = jnp.argmax(scores, axis=-1).astype(jnp.int32)[..., None]
            weight = jnp.take_along_axis(scores, chosen, axis=-1)
        whole = stacks is not None
        w1, w3, w2 = ((stacks if whole else layer)[k] for k in _EXPERT_LEAVES)
        out, record = routed_ffn(
            y, None, w1, w3, w2, 1, False, live=live,
            layer=layer["layer_index"] if whole else None,
            routed=(weight, chosen))
        x = _merge(layer, "moe", x, out)
    return (x, r, record) + (((chosen, scores),) if choices else ())


def _head(cfg: ZayaConfig, params, x):
    with jax.named_scope("layer/norm"):
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    with jax.named_scope("head"):
        return jnp.einsum("...d,vd->...v", x,
                          params["embed"].astype(x.dtype))


@jax.named_scope("layer/attn/out")
def _merge_heads(cfg: ZayaConfig, layer, attn, dtype):
    b, _, t, _ = attn.shape
    return qmm(attn.transpose(0, 2, 1, 3).reshape(
        b, t, cfg.num_heads * cfg.head_dim), layer["o_w"], dtype)


# --------------------------------------------------------------------- forward
def forward_cached(cfg: ZayaConfig, params, input_ids, cache, pos,
                   lengths=None, block_tables=None, all_positions=False,
                   routing: bool = False, choices: bool = False):
    """The cached forward (module docstring; ``cached.window`` has the
    contract of ``lengths`` / ``block_tables`` / ``all_positions``).
    ``routing`` adds a third result, the layers' routing records int32 ``[L,
    3]`` (``moe/routed.py RECORD``, over the live tokens); ``choices`` a last
    one, ``{"experts": int32 [L, B, T, 1], "scores": float32 [L, B, T, E]}``
    — what a comparison with a plain reference hands that reference, and
    holds to the reference's own."""
    if not isinstance(block_tables, dict):
        raise NotImplementedError(
            "a model whose keys are made by causal convolutions is served "
            "through init_serving / ServingEngine, whose cache holds the "
            "convolutions' tails a row beside the block-paged pool "
            "(block_tables {'full', 'slot'}); the contiguous cache of "
            "InferenceEngine.generate has one kind of state")
    table, slot = block_tables["full"], block_tables.get("slot")
    w = cached.window(input_ids, pos, lengths, table)
    live = live_tokens(input_ids, lengths, block_tables)
    b, t = input_ids.shape
    base = jnp.broadcast_to(jnp.asarray(w.step_pos, jnp.int32), (b,))
    positions = base[:, None] + jnp.arange(t, dtype=jnp.int32)
    fresh = (base == 0)[:, None]
    # the window's last real token, whose tails the row keeps (a row with
    # none — a pad row, an idle slot — keeps what it had)
    valid = live.sum(axis=1, dtype=jnp.int32)
    last = jnp.clip(valid - 1, 0, t - 1)[:, None, None]
    moved = (valid > 0)[:, None]

    blocks = dict(params["blocks"])
    # the expert stacks stay out of the layer scan (``mixtral.forward_cached``)
    stacks = {k: blocks.pop(k) for k in _EXPERT_LEAVES}
    blocks["layer_index"] = jnp.arange(cfg.num_layers, dtype=jnp.int32)

    def step(xr, layer, ck, cv, index):
        x, r = xr
        (ck, conv), (cv, shift) = ck, cv
        with jax.named_scope("layer/attn"):
            with jax.named_scope("layer/attn/kv_write"):
                if slot is None:
                    held = conv[index, :, 0], shift[index, :, 0, 0]
                else:
                    # (a pad row's slot is out of range: read clamped,
                    # written nowhere)
                    rows = jnp.clip(slot, 0, conv.shape[1] - 1)
                    held = conv[index, rows, 0], shift[index, rows, 0, 0]
                # a window at base 0 starts from nothing
                tails = tuple(jnp.where(fresh, 0, a) for a in (
                    held[0][:, 0], held[0][:, 1], held[1]))
            with jax.named_scope("layer/norm"):
                h = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
            q, k, v, seqs = _cca(cfg, layer, h, tails, positions)
            with jax.named_scope("layer/attn/kv_write"):
                c, c1, vb = (jnp.take_along_axis(s, last, axis=1)[:, 0]
                             for s in seqs)
                new_conv = jnp.where(
                    moved[:, :, None], jnp.stack([c, c1], 1),
                    held[0]).astype(conv.dtype)[:, None]
                new_shift = jnp.where(moved, vb, held[1]).astype(
                    shift.dtype)[:, None, None]
                if slot is None:
                    conv = jax.lax.dynamic_update_index_in_dim(
                        conv, new_conv, index, 0)
                    shift = jax.lax.dynamic_update_index_in_dim(
                        shift, new_shift, index, 0)
                else:
                    conv = conv.at[index, slot].set(new_conv, mode="drop")
                    shift = shift.at[index, slot].set(new_shift,
                                                      mode="drop")
            attn, ck, cv = cached.cached_attention(
                q, k, v, ck, cv, w.step_pos, table, w.chunk_valid, index)
            x = _merge(layer, "attn", x,
                       _merge_heads(cfg, layer, attn, x.dtype))
        x, r, *aux = _experts(cfg, layer, x, r, live, stacks, choices)
        return (x, r), (ck, conv), (cv, shift), tuple(aux)

    with jax.named_scope("embed"):
        x = params["embed"][input_ids]
    r = jnp.zeros((b, t, cfg.router_size), jnp.float32)
    (x, _), (ck, conv), (cv, shift), aux = cached.scan_layers_cached(
        step, (x, r), blocks, (cache["k"], cache["conv"]),
        (cache["v"], cache["shift"]), paged=True)
    if not all_positions:
        x = cached.gather_last(x, w.gather)
    out = (_head(cfg, params, x),
           {"k": ck, "v": cv, "conv": conv, "shift": shift})
    if routing:
        out += (aux[0],)
    if choices:
        out += ({"experts": aux[1][0], "scores": aux[1][1]},)
    return out


def forward(cfg: ZayaConfig, params, input_ids):
    """The uncached forward over whole sequences from position 0 (zero
    tails), layer by layer, by plain causal attention.  ``[B, S, V]``."""
    b, s = input_ids.shape
    x = params["embed"][input_ids]
    r = jnp.zeros((b, s, cfg.router_size), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    mask = jnp.tril(jnp.ones((s, s), bool))
    rep = cfg.num_heads // cfg.num_kv_heads
    zeros = (jnp.zeros((b, cfg.conv_channels), x.dtype),) * 2 \
        + (jnp.zeros((b, cfg.shift_channels), x.dtype),)
    for number in range(cfg.num_layers):
        layer = jax.tree_util.tree_map(lambda a: a[number], params["blocks"])
        h = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
        q, k, v, _ = _cca(cfg, layer, h, zeros, positions)
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) \
            / math.sqrt(cfg.head_dim)
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e9), axis=-1)
        attn = jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype), v)
        x = _merge(layer, "attn", x, _merge_heads(cfg, layer, attn, x.dtype))
        x, r, _ = _experts(cfg, layer, x, r, None)
    return _head(cfg, params, x)


def init_cache(cfg: ZayaConfig, num_blocks: int, block_size: int,
               dtype=jnp.bfloat16, state_rows: Optional[int] = None):
    """The cache of a serving engine (block-paged only): ``k``, ``v`` ``[L,
    num_blocks, G, block_size, hd]`` beside, for ``state_rows`` rows, the
    tails ``conv [L, rows, 1, 2, channels]`` and ``shift [L, rows, 1, 1, G
    hd / 2]`` (module docstring "The cache")."""
    if state_rows is None:
        raise NotImplementedError(
            "a model whose keys are made by causal convolutions is served "
            "through init_serving / ServingEngine (init_cache(..., "
            "state_rows=)): the contiguous cache of InferenceEngine.generate "
            "has one kind of state")
    n = cfg.num_layers
    return {**cached.init_kv_cache(n, num_blocks, cfg.num_kv_heads,
                                   block_size, cfg.head_dim, dtype),
            "conv": jnp.zeros((n, state_rows, 1, 2, cfg.conv_channels),
                              dtype),
            "shift": jnp.zeros((n, state_rows, 1, 1, cfg.shift_channels),
                               dtype)}


def build(cfg: Optional[ZayaConfig] = None, **overrides) -> ModelSpec:
    cfg = cfg or ZayaConfig(**overrides)

    def loss_fn(params, batch, rng=None, train=True):
        if train:
            raise NotImplementedError(
                "ZAYA1 is an inference path: the backward of compressed "
                "convolutional attention is not built")
        ids = batch["input_ids"] if isinstance(batch, dict) else batch
        logp = jax.nn.log_softmax(
            forward(cfg, params, ids[:, :-1]).astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1).mean()

    def apply_fn(params, batch, rng=None):
        ids = batch["input_ids"] if isinstance(batch, dict) else batch
        return forward(cfg, params, ids)

    decode_hooks = {
        "init_cache": lambda b, s, dtype=jnp.bfloat16, **kinds: init_cache(
            cfg, b, s, dtype, **kinds),
        "forward_cached": lambda params, ids, cache, pos, lengths=None,
            block_tables=None, all_positions=False, routing=False,
            choices=False:
            forward_cached(cfg, params, ids, cache, pos, lengths,
                           block_tables, all_positions, routing, choices),
        "max_seq_len": cfg.max_seq_len,
        "supports_lengths": True,
        "supports_paged": True,
        # a rejected draft token has already moved the tails: no verify
        # window (the engine refuses speculation by name)
        "supports_verify": False,
        "supports_kv_quant": False,
        "supports_sampling": True,
        "routing_record": True,
        # tails: leaves indexed by ROW beside the paged ones — what a key's
        # writer needs of the token before, a row a layer
        "tail_layers": {
            "layers": cfg.num_layers,
            "taps": {"conv": cfg.cca_time0 + cfg.cca_time1 - 2, "shift": 1}},
    }
    active = cfg.num_params() - cfg.num_layers * (
        cfg.layer_params() - cfg.active_layer_params())
    return ModelSpec(
        init_fn=lambda rng: init_params(cfg, rng), model_config=cfg,
        loss_fn=loss_fn, apply_fn=apply_fn,
        # served on one shard: every leaf whole on every chip
        tp_rules=lambda ap: jax.tree_util.tree_map(lambda _: P(), ap),
        flops_per_token=6.0 * active,
        decode_hooks=decode_hooks, quant_aware=False,
        name=f"zaya-{cfg.num_layers}l")
