"""dots3-note-prev's language model (``dots-studio/dots3-note-prev``,
``model_type: dots3_note``; the vision and audio towers and the multi-token
prediction module are not built: token ids in, text positions), served
through ``init_serving`` / ``ServingEngine``.

The block is the sequential pre-norm RMSNorm residual block (``x += attn(
norm(x)); x += ffn(norm(x))``, no biases) over layers of TWO attention kinds,
both LATENT attention (``models/llama.py``: low-rank queries, one joint key /
value latent ``c`` a token beside one rotated key ``k_r`` all heads share,
interleaved rotary pairs, read absorbed) at sizes of their own:

* a **full layer** (``"latent_indexed"``; ``layer_types[i] ==
  "full_attention"``) attends, in ALL its heads, the ``index_topk`` keys a
  learned indexer chose (``ops/sparse_index_attention.py``: DeepSeek-V3.2's,
  its queries ``qI = c_q W_iq`` taken from the QUERY LATENT, its one key
  ``kI = LayerNorm(x W_ik)``, the first ``qk_rope_dim`` values of both
  rotated rotate-half, ``w = x W_iw index_heads^-0.5 index_head_dim^-0.5``)
  — every key where it sees no more.  It caches the latent (``latent``) and
  the indexer's key (``idx``) under the full kind's table.
* a **sliding layer** (``"latent_sliding"``; ``"sliding_attention"``) is the
  same form at the ``swa_*`` sizes, with no indexer: a query at ``t`` attends
  ``t - sliding_window < s <= t``.  It caches ONE leaf of its own width
  (``latw``) under the window kind's ring, in blocks of its own
  (``ops/paged_kv.py`` "Layer kinds").
* both: the two latents are rescaled after their norms (``c_q = s_q
  RMSNorm(x W_qa)``, ``c = s_kv RMSNorm(c')``, ``s = (hidden_size /
  rank)^0.5``: ``lora_rescale``) and every head's output is gated by a scalar
  of the block's normed input (``o_h <- sigmoid(x W_g)_h o_h``:
  ``head_gate``) before ``W_o``.
* the **FFN** is ``models/kimi_linear.py``'s pair: the first ``first_dense``
  layers a dense SwiGLU of ``dense_ffn_size``, every other layer sigmoid
  scores over all ``num_experts``, the top-k of ``score + gate_bias``, the
  chosen scores renormalised times ``routed_scale``, beside ``shared_experts``
  shared experts; the layer may hold a share of its experts
  (``experts_held``).

The kinds' weights differ in SHAPE, so ``params["blocks"]`` holds stacks BY
KIND (``{"latent_indexed": [L_full, ...], "latent_sliding": [L_sliding, ...],
"dense": [first_dense, ...], "moe": [L - first_dense, ...]}``), and the
published ``layer_types`` is taken AS GIVEN: the first ``first_dense`` layers
stand outside the pattern (layer 0 is a full layer with the dense FFN), the
rest is whole periods of the shortest pattern that repeats (``[full, sliding,
sliding, sliding]``) and a partial closing period (one full layer) — three
stretches of ``cached.scan_periods_cached``, each counting on from the one
before.

Served on one shard through the block-paged pool only; what else such a model
is refused is ``inference/options.py KIND_REFUSES`` — the union of the
``latent``, ``indexer`` and ``window`` kinds' rows — to say, by name.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops import decode_attention, paged_kv
from ..ops import sparse_index_attention as sparse_attention
from ..runtime.model import ModelSpec
from . import cached
from . import llama as L
from . import mixtral as M
from .cached import live_tokens, qmm, scan_periods_cached

PyTree = Any
FULL, SLIDING = "latent_indexed", "latent_sliding"
#: the published ``layer_types`` names
TYPES = {"full_attention": FULL, "sliding_attention": SLIDING}


@dataclasses.dataclass
class Dots3Config(M.MixtralConfig):
    """The inherited latent and head sizes (``num_heads``, ``q_lora_rank``,
    ``kv_lora_rank``, ``qk_nope_dim``, ``qk_rope_dim``, ``v_head_dim``,
    ``rope_theta``) are the FULL layers'; ``swa_*`` the sliding layers'."""
    #: the published list, a name a layer (``num_layers`` is its length)
    layer_types: tuple = ()
    swa_num_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_dim: int = 192
    swa_qk_rope_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 50000.0
    #: leading layers whose FFN is dense, of width ``dense_ffn_size``
    first_dense: int = 1
    dense_ffn_size: int = 13824
    #: a scalar gate a head on the attention output
    head_gate: bool = True
    #: the latents times ``(hidden_size / rank)^0.5`` after their norms
    lora_rescale: bool = True
    #: the indexer rotates pairs ``(2i, 2i + 1)`` of its first ``qk_rope_dim``
    #: values (False: rotate-half, pairs ``(i, i + qk_rope_dim / 2)``)
    index_rope_interleaved: bool = False
    by_kind: ClassVar[bool] = True
    whole_periods: ClassVar[bool] = False

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        unknown = set(self.layer_types) - set(TYPES)
        if not self.layer_types or unknown:
            raise ValueError(f"layer_types names {sorted(unknown)}: a list "
                             f"of {sorted(TYPES)}, a name a layer")
        kinds = [TYPES[t] for t in self.layer_types]
        self.num_layers = len(kinds)
        if not 0 <= self.first_dense <= self.num_layers:
            raise ValueError(f"first_dense={self.first_dense} of "
                             f"{self.num_layers} layers")
        rest = kinds[self.first_dense:]
        period = next((p for p in range(1, len(rest) + 1) if all(
            k == rest[i % p] for i, k in enumerate(rest))), 0)
        #: (head, whole periods, tail): the leading layers' kinds, the
        #: number of whole periods of ``layer_kinds``, the closing ones'
        self.stretches = (tuple(kinds[:self.first_dense]),
                          len(rest) // period if period else 0,
                          tuple(rest[len(rest) // period * period:])
                          if period else ())
        self.layer_kinds = tuple(rest[:period]) or tuple(dict.fromkeys(kinds))
        super().__post_init__()
        if SLIDING in kinds and self.sliding_window < 1:
            raise ValueError("sliding layers need sliding_window >= 1")
        if FULL in kinds and not self.index_heads:
            raise ValueError("a full layer attends what its indexer chose: "
                             "index_heads > 0")
        if self.parallel_block or self.router_input != "ffn" \
                or self.tie_embeddings or self.norm != "rms" \
                or self.rope_scaling is not None \
                or self.query_temperature is not None:
            raise ValueError("dots3's block is the sequential RMSNorm block "
                             "with an untied head and plain rotary")
        self.kinds = tuple(kinds)
        self._attn = {
            FULL: self._sizes(self.num_heads, self.q_lora_rank,
                              self.kv_lora_rank, self.qk_nope_dim,
                              self.qk_rope_dim, self.v_head_dim,
                              self.rope_theta),
            SLIDING: self._sizes(self.swa_num_heads, self.swa_q_lora_rank,
                                 self.swa_kv_lora_rank, self.swa_qk_nope_dim,
                                 self.swa_qk_rope_dim, self.swa_v_head_dim,
                                 self.swa_rope_theta)}

    def _sizes(self, heads, q_rank, kv_rank, nope, rope, v, theta):
        return L.LlamaConfig(
            vocab_size=self.vocab_size, max_seq_len=self.max_seq_len,
            num_layers=1, num_heads=heads, num_kv_heads=heads,
            hidden_size=self.hidden_size, head_width=nope + rope,
            q_lora_rank=q_rank, kv_lora_rank=kv_rank, qk_nope_dim=nope,
            qk_rope_dim=rope, v_head_dim=v, rope_theta=float(theta),
            rms_eps=self.rms_eps, rope_interleaved=True)

    def attn(self, kind: str) -> L.LlamaConfig:
        """The latent attention of a layer of ``kind`` as ``models/llama.py``
        reads one: its heads, ranks, head widths and rotary base."""
        return self._attn[kind]

    def layers_of(self, kind: str) -> int:
        return self.kinds.count(kind)

    def window_block(self, block_size: int, itemsize: int) -> int:
        """Tokens a block of the sliding kind's leaf holds beside a full
        kind's of ``block_size``: what ITS bytes give
        (``paged_kv.latent_block_tokens``), never more than the other's."""
        return min(int(block_size), paged_kv.latent_block_tokens(
            self.attn(SLIDING).latent_width, itemsize, self.max_seq_len))

    @staticmethod
    def dots3_note_prev() -> "Dots3Config":
        """dots-studio/dots3-note-prev's language model at its published
        widths: 46 layers (13 full + 33 sliding), d 5,120; full layers of
        128 heads x (128 + 64) over a latent of 512 + 64, queries through
        rank 1,024, theta 8e7, an indexer of 64 heads x 128 choosing 2,048
        keys; sliding layers of 64 heads x (192 + 64) over a latent of 1,024
        + 64 under a 513-key window, theta 50,000; a leading dense SwiGLU of
        13,824, then 256 sigmoid-scored SwiGLU experts of 1,536 top-8 with a
        selection bias, renormalised, beside one shared expert; an untied
        head.  One chip's share of it (``experts_held``, fewer layers, a
        vocabulary slice) is a deployment's to state."""
        return Dots3Config(
            vocab_size=152064, max_seq_len=524288, hidden_size=5120,
            layer_types=("full_attention",) + (
                ("full_attention",) + ("sliding_attention",) * 3) * 11
            + ("full_attention",),
            num_heads=128, num_kv_heads=128, head_width=192,
            q_lora_rank=1024, kv_lora_rank=512, qk_nope_dim=128,
            qk_rope_dim=64, v_head_dim=128, rope_theta=8e7, rms_eps=1e-5,
            rope_interleaved=True, index_heads=64, index_head_dim=128,
            index_topk=2048, sliding_window=513, ffn_size=1536,
            num_experts=256, top_k=8, norm_topk_prob=True,
            router_score="sigmoid", router_bias=True, routed_scale=1.0,
            shared_experts=1, capacity_factor=None)

    def attn_params(self, kind: str) -> int:
        a = self.attn(kind)
        n = sum(math.prod(s) for s in attn_shapes(self, kind).values())
        return n + 2 * a.hidden_size

    def num_params(self) -> int:
        d, f = self.hidden_size, self.ffn_size
        moe = d * self.num_experts + self.num_experts * self.router_bias \
            + (self.experts_here + self.shared_experts) * 3 * d * f
        return 2 * self.vocab_size * d + d \
            + sum(self.layers_of(k) * self.attn_params(k)
                  for k in (FULL, SLIDING)) \
            + self.first_dense * 3 * d * self.dense_ffn_size \
            + (self.num_layers - self.first_dense) * moe

    def active_params(self) -> int:
        idle = (self.num_experts - self.top_k) * self.experts_here \
            // self.num_experts * 3 * self.hidden_size * self.ffn_size
        return self.num_params() - (self.num_layers - self.first_dense) * idle


# ------------------------------------------------------------------ parameters
def attn_shapes(cfg: Dots3Config, kind: str):
    """One attention layer's leaves of ``kind`` by name (a projection is
    stored ``[in, out]``): ``llama.latent_shapes`` at the kind's sizes, the
    head gate, and a full layer's indexer (``idx_q_w`` reads the query
    latent; ``idx_k_norm`` is a LayerNorm's scale row and bias row)."""
    a = cfg.attn(kind)
    shapes = dict(L.latent_shapes(a))
    if cfg.head_gate:
        shapes["head_gate_w"] = (a.hidden_size, a.num_heads)
    if kind == FULL:
        hi, di = cfg.index_heads, cfg.index_head_dim
        shapes.update(idx_q_w=(a.q_lora_rank, hi * di),
                      idx_k_w=(a.hidden_size, di),
                      idx_w_w=(a.hidden_size, hi), idx_k_norm=(2, di))
    return shapes


def init_params(cfg: Dots3Config, rng) -> PyTree:
    """Seeded parameters: matrices N(0, 0.02) (output projections scaled
    down with the depth, as the other families), norms at one."""
    d, n, std = cfg.hidden_size, cfg.num_layers, 0.02
    keys = iter(jax.random.split(rng, 64))

    def normal(shape, s=std):
        return (jax.random.normal(next(keys), shape) * s).astype(jnp.float32)

    out_std = std / math.sqrt(2 * n)
    stacks = {}
    for kind in (FULL, SLIDING):
        count = cfg.layers_of(kind)
        if not count:
            continue
        stack = {}
        for name, shape in attn_shapes(cfg, kind).items():
            shape = (count,) + shape
            if name == "idx_k_norm":
                stack[name] = jnp.stack([jnp.ones(shape[:1] + shape[2:]),
                                         jnp.zeros(shape[:1] + shape[2:])], 1)
            elif name.endswith("_norm"):
                stack[name] = jnp.ones(shape)
            else:
                stack[name] = normal(shape, out_std if name == "o_w" else std)
        stack["attn_norm"] = jnp.ones((count, d))
        stack["mlp_norm"] = jnp.ones((count, d))
        stacks[kind] = stack
    if cfg.first_dense:
        fd, f = cfg.first_dense, cfg.dense_ffn_size
        stacks["dense"] = {"w1": normal((fd, d, f)), "w3": normal((fd, d, f)),
                           "w2": normal((fd, f, d), out_std)}
    lr, f, e = n - cfg.first_dense, cfg.ffn_size, cfg.experts_here
    if lr:
        moe = {"gate_w": normal((lr, d, cfg.num_experts)),
               "experts_w1": normal((lr, e, d, f)),
               "experts_w3": normal((lr, e, d, f)),
               "experts_w2": normal((lr, e, f, d))}
        if cfg.router_bias:
            moe["gate_bias"] = normal((lr, cfg.num_experts))
        if cfg.shared_experts:
            sf = cfg.shared_experts * f
            moe.update(shared_w1=normal((lr, d, sf)),
                       shared_w3=normal((lr, d, sf)),
                       shared_w2=normal((lr, sf, d)))
        stacks["moe"] = moe
    return {"embed": normal((cfg.vocab_size, d)), "blocks": stacks,
            "final_norm": jnp.ones((d,)),
            "lm_head": normal((d, cfg.vocab_size))}


# ------------------------------------------------------------------- attention
def _rescale(cfg: Dots3Config, rank: int) -> float:
    return math.sqrt(cfg.hidden_size / rank) if cfg.lora_rescale else 1.0


@jax.named_scope("layer/attn/qkv")
def _project(cfg: Dots3Config, a: L.LlamaConfig, layer, y, rope):
    """A latent layer's projections of its normed input ``y [B, T, d]`` at
    the sizes ``a``: the rescaled query latent ``c_q [B, T, q_rank]``, the
    heads' unrotated and rotated query parts ``[B, H, T, *]``, the rescaled
    normed latent ``c [B, T, rank]`` and the one rotated key ``[B, 1, T,
    rope]``.  ``rope(x)`` rotates ``[B, *, T, rope]`` at the tokens'
    positions."""
    b, t, _ = y.shape
    cq = L.rms_norm(qmm(y, layer["q_a_w"]), layer["q_a_norm"], cfg.rms_eps) \
        * _rescale(cfg, a.q_lora_rank)
    q = qmm(cq, layer["q_b_w"]).reshape(b, t, a.num_heads, a.head_dim) \
        .transpose(0, 2, 1, 3)
    kv = qmm(y, layer["kv_a_w"])
    c = L.rms_norm(kv[..., :a.kv_lora_rank], layer["kv_a_norm"],
                   cfg.rms_eps) * _rescale(cfg, a.kv_lora_rank)
    kr = rope(kv[:, None, :, a.kv_lora_rank:])
    return cq.astype(y.dtype), q[..., :a.qk_nope_dim], \
        rope(q[..., a.qk_nope_dim:]), c.astype(y.dtype), kr


@jax.named_scope("layer/attn/qkv")
def _indexer(cfg: Dots3Config, layer, y, cq, rope):
    """The indexer of a full layer: queries ``[B, HI, T, DI]`` from the
    query latent ``cq``, the one key ``[B, 1, T, DI]`` (LayerNorm) and the
    head weights float32 ``[B, T, HI]`` from the block's normed input ``y``.
    ``rope(x)`` rotates the first ``qk_rope_dim`` values of ``[B, *, T, DI]``
    (:func:`_index_rope`'s pairing)."""
    b, t, _ = y.shape
    hi, di, r = cfg.index_heads, cfg.index_head_dim, cfg.qk_rope_dim
    # (the barrier: the head split moves the product, not ``idx_q_w`` —
    # ``llama._attend_cached``)
    qi = jax.lax.optimization_barrier(qmm(cq, layer["idx_q_w"]))
    qi = qi.reshape(b, t, hi, di).transpose(0, 2, 1, 3)
    ki = sparse_attention.layer_norm(qmm(y, layer["idx_k_w"]),
                                     layer["idx_k_norm"])[:, None]
    wi = qmm(y, layer["idx_w_w"]).astype(jnp.float32) \
        * (hi ** -0.5 * di ** -0.5)

    def part(x):
        return jnp.concatenate([rope(x[..., :r]), x[..., r:]], axis=-1)

    return part(qi), part(ki), wi


def _index_rope(cfg: Dots3Config, pos=None, seq_len: int = 0):
    """The indexer's rotation (the full layers' base, the pairing
    ``index_rope_interleaved`` names): at the traced offset ``pos`` of a
    cached window, or over ``0 .. seq_len - 1``."""
    a = dataclasses.replace(cfg.attn(FULL),
                            rope_interleaved=cfg.index_rope_interleaved)
    if pos is not None:
        return lambda x: L._rope_cached(a, x, pos)
    cos, sin = L.rope_angles(a, seq_len, dim=cfg.qk_rope_dim)
    return lambda x: L.apply_rope(x, cos, sin, cfg.index_rope_interleaved)


@jax.named_scope("layer/attn/out")
def _gated(cfg: Dots3Config, layer, y, out):
    """``out [B, T, H, v]`` times the head gate, ``[B, T, H * v]``."""
    if cfg.head_gate:
        gate = jax.nn.sigmoid(qmm(y, layer["head_gate_w"])
                              .astype(jnp.float32))
        out = (out * gate[..., None]).astype(out.dtype)
    return out.reshape(out.shape[:2] + (-1,))


def _attend_cached(cfg: Dots3Config, kind: str, layer, y, leaves, index,
                   table, w: cached.Window, keep: bool):
    """A layer's cache write + attention over the block-paged pool, ABSORBED
    (``llama._latent_cached``): ``-> (attention output [B, T, d], the kind's
    leaves, the selection's counts int32 [5], the chosen keys or None)``."""
    a = cfg.attn(kind)
    b, t, _ = y.shape
    pos, valid = w.step_pos, w.chunk_valid
    ring = kind == SLIDING
    cq, qn, qr, c, kr = _project(cfg, a, layer, y,
                                 lambda x: L._rope_cached(a, x, pos))
    pool = leaves[0]
    pad = pool.shape[-1] - a.latent_width
    pool = paged_kv.paged_window_update(
        pool, jnp.pad(jnp.concatenate([c[:, None], kr], axis=-1),
                      ((0, 0),) * 3 + ((0, pad),)),
        pos, table, valid=valid, layer=index, ring=ring)
    w_uk, w_uv = L._latent_up(a, layer["kv_b_w"], y.dtype)
    with jax.named_scope("layer/attn/latent_up"):
        ql = jnp.einsum("bhtn,chn->bhtc", qn, w_uk)
        q = jnp.concatenate([ql, qr], axis=-1).astype(jnp.float32) \
            * L.latent_scale(a)
        q = jnp.pad(q.astype(y.dtype), ((0, 0),) * 3 + ((0, pad),))
    counts = jnp.zeros(len(sparse_attention.COUNTS), jnp.int32)
    kept = None
    if ring:
        with jax.named_scope("layer/attn/core"):
            o = decode_attention.paged_latent_attention(
                q, pool, table, pos, rank=a.kv_lora_rank, layer=index,
                valid=valid, window=cfg.sliding_window)
        leaves = (pool,)
    else:
        qi, ki, wi = _indexer(cfg, layer, y, cq, _index_rope(cfg, pos))
        idx = paged_kv.paged_window_update(leaves[1], ki, pos, table,
                                           valid=valid, layer=index)
        o, counts, *kept = sparse_attention.paged_sparse_latent_attention(
            q, pool, idx, qi, wi, table, pos, rank=a.kv_lora_rank,
            topk=cfg.index_topk, layer=index, valid=valid, return_keep=keep)
        kept = kept[0] if kept else None
        leaves = (pool, idx)
    with jax.named_scope("layer/attn/latent_up"):
        out = jnp.einsum("bhtc,chv->bthv", o, w_uv)
    with jax.named_scope("layer/attn/out"):
        out = qmm(_gated(cfg, layer, y, out), layer["o_w"], y.dtype)
    return out, leaves, counts, kept


def _attend(cfg: Dots3Config, kind: str, layer, y):
    """A layer's UNCACHED attention over whole sequences from position 0, in
    the EXPANDED form (every head's keys and values written out from the
    latent), the selection and the window as dense masks: ``[B, S, d]``."""
    a = cfg.attn(kind)
    b, s, _ = y.shape
    cos, sin = L.rope_angles(a, s)
    cq, qn, qr, c, kr = _project(
        cfg, a, layer, y, lambda x: L.apply_rope(x, cos, sin, True))
    w_uk, w_uv = L._latent_up(a, layer["kv_b_w"], y.dtype)
    kn = jnp.einsum("bsc,chn->bhsn", c, w_uk)
    v = jnp.einsum("bsc,chv->bhsv", c, w_uv)
    scores = (jnp.einsum("bhqn,bhkn->bhqk", qn, kn)
              + jnp.einsum("bhqr,bkr->bhqk", qr, kr[:, 0])) \
        .astype(jnp.float32) * L.latent_scale(a)
    keep = jnp.tril(jnp.ones((s, s), bool))[None]
    if kind == SLIDING:
        keep = keep & ~jnp.tril(keep, -cfg.sliding_window)
    elif s > cfg.index_topk:
        qi, ki, wi = _indexer(cfg, layer, y, cq, _index_rope(cfg, seq_len=s))
        last = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        chosen = jnp.where(keep, sparse_attention.scores_of(qi, wi, ki[:, 0]),
                           -jnp.inf)
        keep = sparse_attention.chosen(
            chosen, *sparse_attention.select_threshold_reference(
                chosen, cfg.index_topk), last)
    probs = jax.nn.softmax(jnp.where(keep[:, None], scores, -1e9),
                           axis=-1).astype(y.dtype)
    out = jnp.einsum("bhqk,bhkv->bqhv", probs, v)
    return qmm(_gated(cfg, layer, y, out), layer["o_w"], y.dtype)


# ------------------------------------------------------------------------- FFN
def _at(stack, index):
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, index, keepdims=False),
        stack)


def _ffn(cfg: Dots3Config, blocks, stacks, number, y, live, choices):
    """The FFN of layer ``number`` (an ``int`` in a written-out stretch):
    ``-> (output, routing record, chosen experts or None)``; a dense layer's
    record is zeros and its choices -1."""
    if isinstance(number, int) and number < cfg.first_dense:
        layer = _at(blocks["dense"], number)
        with jax.named_scope("layer/mlp"):
            gate = jax.nn.silu(qmm(y, layer["w1"]))
            out = qmm(gate * qmm(y, layer["w3"]), layer["w2"], y.dtype)
        record = jnp.zeros(len(M.record_columns(cfg)), jnp.int32)
        chosen = jnp.full(y.shape[:-1] + (cfg.top_k,), -1, jnp.int32)
        return out, record, chosen if choices else None
    index = number - cfg.first_dense
    moe = blocks["moe"]
    if stacks is not None:
        moe = {k: v for k, v in moe.items() if k not in stacks}
    layer = _at(moe, index)
    if stacks is not None:
        layer["layer_index"] = jnp.asarray(index, jnp.int32)
    out, record = M._routed(cfg, layer, y, live, stacks, choices)
    if choices:
        return out, record[0], record[1]
    return out, record, None


# --------------------------------------------------------------------- forward
def block_cached(cfg: Dots3Config, blocks, stacks, w: cached.Window, live,
                 choices: bool, s_max: int, x, layer, ck, cv, index, table,
                 kind, number):
    """ONE block over the block-paged pool — ``scan_periods_cached``'s
    ``step`` behind its first seven arguments: the attention of ``kind`` at
    the kind's place ``index`` of its leaves, then the FFN of layer
    ``number`` read out of ``blocks`` (``stacks``: the expert leaves whole,
    for the kernel).  A family whose extra block is this block (a
    multi-token-prediction module: ``models/glm_dsa.py``) calls it with
    stacks of its own.  -> ``(x, the kind's leaves.., aux)``."""
    with jax.named_scope("layer/attn"):
        with jax.named_scope("layer/norm"):
            y = L.rms_norm(x, layer["attn_norm"], cfg.rms_eps)
        leaves = (ck,) if kind == SLIDING else (ck, cv)
        out, leaves, counts, kept = _attend_cached(
            cfg, kind, layer, y, leaves, index, table, w, choices)
        x = x + out
    with jax.named_scope("layer/norm"):
        y = L.rms_norm(x, layer["mlp_norm"], cfg.rms_eps)
    out, record, chosen = _ffn(cfg, blocks, stacks, number, y, live, choices)
    aux = {"record": record, "counts": counts}
    if choices:
        aux["experts"] = chosen
        aux["keys"] = kept if kept is not None else jnp.zeros(
            x.shape[:2] + (s_max,), bool)
    return (x + out, *leaves, aux)


def expert_stacks(blocks):
    """The expert leaves of ``blocks["moe"]`` kept whole where the kernel
    reads a layer of them in place (``mixtral.forward_cached``), else
    None."""
    if "moe" in blocks and M._expert_kernel(blocks["moe"]):
        return {k: blocks["moe"][k] for k in M._EXPERT_LEAVES}
    return None


def kind_tables(cfg: Dots3Config, block_tables):
    """``block_tables`` as a table a kind: a model with no sliding layer
    has ONE kind of block and may be handed its one table bare."""
    if isinstance(block_tables, dict):
        return block_tables
    if block_tables is None or SLIDING in cfg.kinds:
        raise NotImplementedError(
            "a model whose layers are latent attention under a learned "
            "selection and under a sliding window is served through the "
            "block-paged pool with a block table per layer kind "
            "(init_serving / ServingEngine); the contiguous cache of "
            "InferenceEngine.generate has one kind of state")
    return {"full": block_tables}


def forward_cached(cfg: Dots3Config, params, input_ids, cache, pos,
                   lengths=None, block_tables=None, all_positions=False,
                   routing: bool = False, choices: bool = False,
                   hidden: bool = False):
    """The cached forward (module docstring; ``cached.window`` has the
    contract of ``lengths`` / ``block_tables`` / ``all_positions``,
    ``mixtral.forward_cached`` that of ``routing`` and ``choices``: the
    records are ``([L, ..] with zeros for a dense layer, the full layers'
    selection counts int32 [5] summed)``, ``choices`` gives ``{"experts":
    int32 [L - first_dense, B, T, top_k], "keys": bool [L_full, B, T,
    max_seq_len]}``).  ``block_tables`` is ``{"full", "window"}`` (the one
    table bare where no layer slides).  ``hidden``: the final norm's output
    at EVERY window position, ``[B, T, d]``, as one more result behind the
    others (what a multi-token-prediction module reads)."""
    block_tables = kind_tables(cfg, block_tables)
    w = cached.window(input_ids, pos, lengths, block_tables["full"])
    live = live_tokens(input_ids, lengths, block_tables)
    with jax.named_scope("embed"):
        x = params["embed"][input_ids].astype(params["embed"].dtype)
    blocks = params["blocks"]
    stacks = expert_stacks(blocks)
    s_max = block_tables["full"].shape[1] * cache["latent"].shape[3] \
        if "latent" in cache else 0

    def step(*args):
        return block_cached(cfg, blocks, stacks, w, live, choices, s_max,
                            *args)

    head, periods, tail = cfg.stretches
    by_kind = {kind: blocks[kind] for kind in (FULL, SLIDING)
               if kind in blocks}
    auxes, before = [], {}

    def stretch(x, cache, kinds, layers, written):
        """``layers`` layers of the pattern ``kinds``, counting on from the
        stretches before (``cached.scan_periods_cached(before=)``)."""
        x, cache, aux = scan_periods_cached(
            kinds, layers, step, x, by_kind, cache, block_tables,
            head=written, before=before)
        auxes.append(aux)
        for kind in kinds:
            before[kind] = before.get(kind, 0) \
                + layers // len(kinds) * kinds.count(kind)
        return x, cache

    # the published list in three stretches: the leading layers written out
    # a layer at a time (a static layer number: the dense FFN), the whole
    # periods scanned, the closing layers written out
    for kind in head:
        x, cache = stretch(x, cache, (kind,), 1, 1)
    if periods:
        x, cache = stretch(x, cache, cfg.layer_kinds,
                           periods * len(cfg.layer_kinds), 0)
    for kind in tail:
        x, cache = stretch(x, cache, (kind,), 1, 1)
    aux = jax.tree_util.tree_map(lambda *a: jnp.concatenate(a), *auxes)
    with jax.named_scope("layer/norm"):
        x = L.rms_norm(x, params["final_norm"], cfg.rms_eps)
    last = x if all_positions else cached.gather_last(x, w.gather)
    with jax.named_scope("head"):
        logits = last @ params["lm_head"].astype(x.dtype)
    out = (logits, cache)
    if routing:
        out += ((aux["record"], aux["counts"].sum(axis=0)),)
    if choices:
        full = jnp.asarray([i for i, k in enumerate(cfg.kinds) if k == FULL],
                           jnp.int32)
        out += ({"experts": aux["experts"][cfg.first_dense:],
                 "keys": aux["keys"][full]},)
    if hidden:
        out += (x,)
    return out


def forward(cfg: Dots3Config, params, input_ids):
    """The uncached forward over whole sequences from position 0, layer by
    layer, attention EXPANDED and the selection a dense mask
    (:func:`_attend`).  ``[B, S, V]``."""
    x = params["embed"][input_ids].astype(params["embed"].dtype)
    blocks = params["blocks"]
    seen = dict.fromkeys((FULL, SLIDING), 0)
    for number, kind in enumerate(cfg.kinds):
        layer = _at(blocks[kind], seen[kind])
        seen[kind] += 1
        with jax.named_scope("layer/attn"):
            x = x + _attend(cfg, kind, layer,
                            L.rms_norm(x, layer["attn_norm"], cfg.rms_eps))
        y = L.rms_norm(x, layer["mlp_norm"], cfg.rms_eps)
        x = x + _ffn(cfg, blocks, None, number, y, None, False)[0]
    x = L.rms_norm(x, params["final_norm"], cfg.rms_eps)
    return x @ params["lm_head"].astype(x.dtype)


def init_cache(cfg: Dots3Config, num_blocks: int, block_size: int,
               dtype=jnp.bfloat16, window_blocks: Optional[int] = None,
               more_full: int = 0):
    """The cache of a serving engine (block-paged only), leaves BY LAYER KIND
    (``ops/paged_kv.py`` "Layer kinds"): ``latent [L_full, num_blocks, 1,
    block_size, W]`` and the indexer's key ``idx [L_full, num_blocks, 1,
    block_size, DI]`` under the full kind's table; ``latw [L_sliding,
    window_blocks, 1, window block, W']`` — another width, blocks of its own
    bytes (:meth:`Dots3Config.window_block`) — under the window kind's
    ring.  ``more_full``: that many more layers of the full kind's two
    leaves, behind the model's own (a module that is one more such layer)."""
    full, sliding = cfg.layers_of(FULL) + more_full, cfg.layers_of(SLIDING)
    if sliding and window_blocks is None:
        raise NotImplementedError(
            "a model with sliding-window layers is served through the "
            "block-paged pool (init_serving / ServingEngine: "
            "init_cache(..., window_blocks=)): the contiguous cache of "
            "InferenceEngine.generate has one kind of state")
    cache = {}
    if full:
        cache["latent"] = jnp.zeros(
            (full, num_blocks, 1, block_size,
             paged_kv.latent_pool_width(cfg.attn(FULL).latent_width)), dtype)
        cache["idx"] = jnp.zeros(
            (full, num_blocks, 1, block_size, cfg.index_head_dim), dtype)
    if sliding:
        cache["latw"] = jnp.zeros(
            (sliding, window_blocks, 1,
             cfg.window_block(block_size, jnp.dtype(dtype).itemsize),
             paged_kv.latent_pool_width(cfg.attn(SLIDING).latent_width)),
            dtype)
    return cache


def build(cfg: Optional[Dots3Config] = None, **overrides) -> ModelSpec:
    cfg = cfg or Dots3Config(**overrides)

    def loss_fn(params, batch, rng=None, train=True):
        if train:
            raise NotImplementedError(
                "dots3 is an inference path: a backward through the learned "
                "selection and the absorbed latent reads is not built")
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
        "routing_record": True,
        "max_seq_len": cfg.max_seq_len,
        "supports_lengths": True,
        "supports_paged": True,
        "supports_verify": False,
        "supports_kv_quant": False,
        "supports_sampling": True,
        # the full kind: a latent a token under a learned selection
        **L.latent_hook(cfg.attn(FULL)),
        "sparse_attention": {"topk": cfg.index_topk},
        # the window kind: leaves and a ring of their own, in blocks of
        # their own (the engine reads the block off the named leaves)
        "window_layers": {
            "window": cfg.sliding_window,
            "layers": {"full": cfg.layers_of(FULL),
                       "sliding": cfg.layers_of(SLIDING)},
            "leaves": ("latw",),
            "token_width": cfg.attn(SLIDING).latent_width},
    }
    if cfg.experts_held is not None:
        decode_hooks["experts_held"] = cfg.experts_held
    return ModelSpec(
        init_fn=lambda rng: init_params(cfg, rng), model_config=cfg,
        loss_fn=loss_fn, apply_fn=apply_fn,
        # served on one shard: every leaf whole on every chip
        tp_rules=lambda ap: jax.tree_util.tree_map(lambda _: P(), ap),
        flops_per_token=6.0 * cfg.active_params(),
        decode_hooks=decode_hooks, quant_aware=False,
        name=f"dots3-{cfg.num_layers}l-{cfg.num_experts}e")
