"""Brumby (``manifestai/Brumby-14B-Base``, ``model_type: brumby``): the
Qwen3-14B block — sequential pre-norm RMSNorm, grouped-query projections
without bias, a per-head q/k-norm before a rotate-half rotary, SwiGLU, an
untied head — with its attention replaced by POWER RETENTION of degree 2
(``ops/power_retention.py``; Buckman, Gelada, Zhang, arXiv:2507.04239),
served through ``init_serving`` / ``ServingEngine``.

Every layer is of ONE kind and caches NO token: its whole past is one
float32 matrix a KV head a ROW, ``state [L, rows, HKV, hd / 2 + 1, hd, hd]``
(the degree-2 monomials of the key by the value's channels, ordered by
cyclic distance: ``ops/power_retention.py`` "The stored state"), beside its
normaliser ``z [L, rows, HKV, hd / 2 + 1, hd]`` (``ops/paged_kv.py`` "The
state kind") — the first family whose cache tree has no paged leaf at all.
The mixer, from the normed input ``h``:

    q, k, v = W_q h, W_k h, W_v h         q, k normed a head, then rotated
    lg = log sigmoid(W_gate h + b_gate)   ONE log-gate a KV head a token
    S <- exp(lg) S + phi(k) v^T,  z <- exp(lg) z + phi(k)
    y = phi(q)^T S / phi(q) . z           ``H / HKV`` query heads read one (S, z)

``phi(q) . phi(k) = (q . k)^2``: the layer is causal attention with the
weights ``exp(c_i - c_j) (q_i . k_j)^2`` normalised by their sum
(``chipbench/reference_brumby.py`` computes it in that form).

A cached forward takes a window of a ROW's tokens like any other
(``cached.window``); ``block_tables`` is ``{"slot": int32 [B]}`` — the row of
the leaves each row of the call owns (a decode step's row ``b`` is row ``b``;
a pad or idle row's slot is out of range) — and a prefill window whose base
is 0 starts from a ZERO state and a zero ``z``.  A pad (a token past
``lengths``, an idle decode row) moves neither.  Served on one shard; what
else such a model is refused is ``inference/serving.py``'s to say, by name.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import power_retention as pr
from ..runtime.model import ModelSpec
from . import cached
from . import llama as L
from .cached import qmm, scan_periods_cached

PyTree = Any
KIND = "power"
#: half-lives, in tokens, the seeded gate biases are drawn between
#: (log-uniform over layers x KV heads; :func:`init_params`)
GATE_HALF_LIFE = (64.0, 8192.0)


@dataclasses.dataclass
class BrumbyConfig(L.LlamaConfig):
    """``LlamaConfig``'s block (norm, GQA projections, per-head q/k-norm,
    rotary, SwiGLU, untied head) at the published Brumby-14B-Base sizes."""
    vocab_size: int = 151936
    max_seq_len: int = 32768
    num_layers: int = 40
    num_heads: int = 40
    num_kv_heads: int = 8
    hidden_size: int = 5120
    head_width: Optional[int] = 128
    ffn_size: int = 17408
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-6
    qk_norm: Any = "head"

    def __post_init__(self):
        super().__post_init__()
        if self.layer_kinds or self.latent or self.tie_embeddings:
            raise ValueError("every Brumby layer is a power-retention layer "
                             "under an untied head")
        pr.distances(self.head_dim)

    @staticmethod
    def brumby_14b_base() -> "BrumbyConfig":
        """manifestai/Brumby-14B-Base as published: every default."""
        return BrumbyConfig()

    def num_params(self) -> int:
        return super().num_params() \
            + self.num_layers * (self.hidden_size + 1) * self.num_kv_heads


# ------------------------------------------------------------------ parameters
def init_params(cfg: BrumbyConfig, rng) -> PyTree:
    """Seeded parameters: ``llama.init_params``' leaves with a matrix drawn
    N(0, 0.9 / sqrt(fan_in)) (0.0126 at the published hidden size, kept in
    proportion at any other: ``granite_hybrid.init_params``), the output
    projections a further ``1 / sqrt(2 L)``; the gate's projection ``gate_w
    [L, d, HKV]`` at a tenth of that and its bias ``gate_b [L, HKV]`` the
    logit of ``2 ** (-1 / H)``, ``H`` a half-life log-uniform in
    ``GATE_HALF_LIFE`` tokens — without a bias every seeded gate is ~0.5 and
    the state forgets in a handful of tokens, so that nothing could tell a
    carried state from a dropped one."""
    # (the counter-based default generator makes 4.86 G normals in ~30 s on
    # a v5e; the chip's own bit generator in ~3: the same key, the same
    # weights, on one backend)
    data = jax.random.key_data(rng).reshape(-1)
    rng = jax.random.wrap_key_data(jnp.resize(data, 4).astype(jnp.uint32),
                                   impl="rbg")
    params = L.init_params(cfg, rng)
    blocks = params["blocks"]
    for name in ("q_w", "k_w", "v_w", "o_w", "w1", "w3", "w2"):
        blocks[name] = blocks[name] * (
            0.9 / math.sqrt(blocks[name].shape[-2]) / 0.02)
    params["lm_head"] = params["lm_head"] * (
        0.9 / math.sqrt(cfg.hidden_size) / 0.02)
    kw, kb = jax.random.split(jax.random.fold_in(rng, 23))
    l, d, hkv = cfg.num_layers, cfg.hidden_size, cfg.num_kv_heads
    blocks["gate_w"] = jax.random.normal(kw, (l, d, hkv), jnp.float32) \
        * (0.09 / math.sqrt(d))
    lo, hi = (math.log(h) for h in GATE_HALF_LIFE)
    keep = 2.0 ** (-1.0 / jnp.exp(
        jax.random.uniform(kb, (l, hkv), jnp.float32, lo, hi)))
    blocks["gate_b"] = jnp.log(keep) - jnp.log1p(-keep)
    return params


# ------------------------------------------------------------------- the mixer
@jax.named_scope("layer/attn/qkv")
def _mixer_inputs(cfg: BrumbyConfig, layer, y, rope, live):
    """What the retention takes, from the normed input ``y [B, T, d]``: ``(q
    [B, T, H, hd], k, v [B, T, HKV, hd], lg [B, T, HKV] float32)`` — q and k
    normed a head and rotated by ``rope`` (``[B, heads, T, hd] -> same``);
    where ``live [B, T]`` is false ``k`` and ``lg`` are 0 (the token moves
    no state)."""
    bsz, t, _ = y.shape
    hd = cfg.head_dim
    # (the barrier: the head splits move these products, not the weights —
    # ``llama._attend_cached``)
    q, k, v, gate = jax.lax.optimization_barrier(
        (qmm(y, layer["q_w"]), qmm(y, layer["k_w"]), qmm(y, layer["v_w"]),
         qmm(y, layer["gate_w"])))
    q, k = L.qk_normed(cfg, q, k, layer.__getitem__)
    heads = lambda a: a.reshape(bsz, t, -1, hd)
    rotated = lambda a: rope(heads(a).transpose(0, 2, 1, 3)) \
        .transpose(0, 2, 1, 3)
    with jax.named_scope("layer/state/gate"):
        lg = jax.nn.log_sigmoid(gate.astype(jnp.float32)
                                + layer["gate_b"].astype(jnp.float32))
    return (rotated(q),
            jnp.where(live[..., None, None], rotated(k), 0), heads(v),
            jnp.where(live[..., None], lg, 0.0))


@jax.named_scope("layer/attn/out")
def _merge(cfg: BrumbyConfig, layer, o, dtype):
    bsz, t = o.shape[:2]
    return qmm(o.reshape(bsz, t, cfg.num_heads * cfg.head_dim).astype(dtype),
               layer["o_w"], dtype)


def _mixer_cached(cfg: BrumbyConfig, layer, y, state, z, index, slot, pos,
                  live):
    """A layer's window against the row-indexed leaves (module docstring):
    ``-> (mixer output [B, T, d], state, z)``."""
    t = y.shape[1]
    q, k, v, lg = _mixer_inputs(
        cfg, layer, y, lambda a: L._rope_cached(cfg, a, pos), live)
    if t == 1:
        # a decode step (one token a row): row b is row b of the leaves
        o, state, z = pr.step(q[:, 0], k[:, 0], v[:, 0], lg[:, 0], state, z,
                              index)
        return _merge(cfg, layer, o[:, None], y.dtype), state, z
    # a prefill window: the rows' leaves by ``slot`` (a pad row's is out of
    # range: read clamped, written nowhere); a window at base 0 starts from
    # nothing
    with jax.named_scope("layer/attn/kv_write"):
        rows = jnp.clip(slot, 0, state.shape[1] - 1)
        fresh = (jnp.asarray(pos, jnp.int32) == 0).reshape(-1)
        s0 = jnp.where(fresh[:, None, None, None, None], 0.0,
                       state[index, rows])
        z0 = jnp.where(fresh[:, None, None, None], 0.0, z[index, rows])
    o, s1, z1 = pr.chunked(q, k, v, lg, s0, z0)
    with jax.named_scope("layer/attn/kv_write"):
        state = state.at[index, slot].set(s1, mode="drop")
        z = z.at[index, slot].set(z1, mode="drop")
    return _merge(cfg, layer, o, y.dtype), state, z


def _ffn(cfg: BrumbyConfig, layer, x):
    """``x + SwiGLU(norm(x))``."""
    with jax.named_scope("layer/mlp"):
        with jax.named_scope("layer/norm"):
            y = L.rms_norm(x, layer["mlp_norm"], cfg.rms_eps)
        return x + qmm(jax.nn.silu(qmm(y, layer["w1"])) * qmm(y, layer["w3"]),
                       layer["w2"], x.dtype)


# --------------------------------------------------------------------- forward
def _head(cfg: BrumbyConfig, params, x):
    with jax.named_scope("layer/norm"):
        x = L.rms_norm(x, params["final_norm"], cfg.rms_eps)
    return L.head_logits(cfg, params, x)


def forward_cached(cfg: BrumbyConfig, params, input_ids, cache, pos,
                   lengths=None, block_tables=None, all_positions=False):
    """The cached forward (module docstring; ``cached.window`` has the
    contract of ``lengths`` / ``all_positions``)."""
    if not isinstance(block_tables, dict):
        raise NotImplementedError(
            "a model of power-retention layers is served through "
            "init_serving / ServingEngine, whose cache holds a recurrent "
            "state a row (block_tables {'slot'}); the contiguous cache of "
            "InferenceEngine.generate has one kind of state")
    bsz, t = input_ids.shape
    w = cached.window(input_ids, pos, lengths, block_tables)
    slot = block_tables["slot"]
    # a decode step runs every row, the idle ones' slots out of range; a
    # prefill window is right-padded to ``lengths``
    live = jnp.broadcast_to((slot < cache["state"].shape[1])[:, None],
                            (bsz, t)) if t == 1 or lengths is None \
        else jnp.arange(t)[None, :] < jnp.asarray(lengths)[:, None]
    with jax.named_scope("embed"):
        x = params["embed"][input_ids].astype(params["embed"].dtype)

    def step(x, layer, state, z, index, slot, kind):
        del kind
        index = jnp.asarray(index, jnp.int32)
        with jax.named_scope("layer/attn"):
            with jax.named_scope("layer/norm"):
                y = L.rms_norm(x, layer["attn_norm"], cfg.rms_eps)
            out, state, z = _mixer_cached(cfg, layer, y, state, z, index,
                                          slot, w.step_pos, live)
            x = x + out
        return _ffn(cfg, layer, x), state, z, jnp.zeros((), jnp.int32)

    x, cache, _ = scan_periods_cached(
        (KIND,), cfg.num_layers, step, x, params["blocks"], cache,
        block_tables)
    if not all_positions:
        x = cached.gather_last(x, w.gather)
    return _head(cfg, params, x), cache


def forward(cfg: BrumbyConfig, params, input_ids):
    """The uncached forward over whole sequences from position 0 (zero
    states), layer by layer, the retention token by token
    (``power_retention.recurrent``).  ``[B, S, V]``."""
    bsz, s = input_ids.shape
    x = params["embed"][input_ids].astype(params["embed"].dtype)
    live = jnp.ones((bsz, s), bool)
    cos, sin = L.rope_angles(cfg, s)
    hkv, hd = cfg.num_kv_heads, cfg.head_dim
    for number in range(cfg.num_layers):
        layer = jax.tree_util.tree_map(lambda a: a[number], params["blocks"])
        y = L.rms_norm(x, layer["attn_norm"], cfg.rms_eps)
        q, k, v, lg = _mixer_inputs(
            cfg, layer, y, lambda a: L.apply_rope(a, cos, sin), live)
        o, _, _ = pr.recurrent(
            q, k, v, lg, jnp.zeros((bsz, hkv, pr.monomials(hd), hd)),
            jnp.zeros((bsz, hkv, pr.monomials(hd))))
        x = _ffn(cfg, layer, x + _merge(cfg, layer, o, y.dtype))
    return _head(cfg, params, x)


def init_cache(cfg: BrumbyConfig, num_blocks: int, block_size: int,
               dtype=jnp.bfloat16, state_rows: Optional[int] = None):
    """The cache of a serving engine: for ``state_rows`` rows, ``state [L,
    rows, HKV, hd / 2 + 1, hd, hd]`` and ``z [L, rows, HKV, hd / 2 + 1, hd]``,
    float32 (``ops/power_retention.py`` "The stored state") — the state
    kind's leaves (``ops/paged_kv.py``) and NOTHING else: no block is asked
    for (``num_blocks`` / ``block_size`` / ``dtype`` size no leaf)."""
    del num_blocks, block_size, dtype
    if state_rows is None:
        raise NotImplementedError(
            "a model of power-retention layers is served through "
            "init_serving / ServingEngine (init_cache(..., state_rows=)): "
            "the contiguous cache of InferenceEngine.generate has one kind "
            "of state")
    shape = (cfg.num_layers, state_rows) + pr.stored_shape(
        cfg.num_kv_heads, cfg.head_dim)
    return {"state": jnp.zeros(shape, jnp.float32),
            "z": jnp.zeros(shape[:-2] + shape[-1:], jnp.float32)}


def build(cfg: Optional[BrumbyConfig] = None, **overrides) -> ModelSpec:
    cfg = cfg or BrumbyConfig(**overrides)

    def loss_fn(params, batch, rng=None, train=True):
        if train:
            raise NotImplementedError(
                "Brumby is an inference path: the chunked power "
                "retention's backward is not built")
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
            block_tables=None, all_positions=False:
            forward_cached(cfg, params, ids, cache, pos, lengths,
                           block_tables, all_positions),
        "max_seq_len": cfg.max_seq_len,
        "supports_lengths": True,
        "supports_paged": True,
        # a rejected draft token has already moved the state: no verify
        # window (the engine refuses speculation by name)
        "supports_verify": False,
        "supports_kv_quant": False,
        "supports_sampling": True,
        # the state kind: leaves indexed by ROW, no block ids, no table;
        # ``bodies``: the prefix of the names its recurrence's bodies go by
        "state_layers": {
            "layers": cfg.num_layers, "heads": cfg.num_kv_heads,
            "key_dim": pr.monomials(cfg.head_dim),
            "value_dim": cfg.head_dim, "bodies": KIND},
    }
    active = cfg.num_params()
    return ModelSpec(
        init_fn=lambda rng: init_params(cfg, rng), model_config=cfg,
        loss_fn=loss_fn, apply_fn=apply_fn,
        # served on one shard: every leaf whole on every chip
        tp_rules=lambda ap: jax.tree_util.tree_map(lambda _: P(), ap),
        flops_per_token=6.0 * active,
        decode_hooks=decode_hooks, quant_aware=False,
        name=f"brumby-{cfg.num_layers}l")
