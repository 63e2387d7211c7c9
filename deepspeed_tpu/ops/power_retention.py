"""Power retention of degree 2 (Buckman, Gelada, Zhang, "Scaling Context
Requires Rethinking Attention", arXiv:2507.04239): a layer whose whole past
is one float32 matrix a KV head, ``S [D, n]`` with ``D = n (n + 1) / 2`` the
distinct degree-2 monomials of an ``n``-wide key (8,256 x 128 at ``n`` =
128), beside a normaliser ``z [D]`` — and no cache that grows with the
sequence.

    S <- g_t S + phi(k_t) v_t^T         g_t = exp(lg_t): ONE gate a KV head
    z <- g_t z + phi(k_t)
    y_t = phi(q_t)^T S / phi(q_t) . z   a group of query heads reads one (S, z)

with ``phi(x) = (x_a x_b * (1 if a == b else sqrt 2))_{a <= b}``, so that
``phi(q) . phi(k) = (q . k)^2`` exactly: the layer is attention with the
weights ``exp(c_i - c_j) (q_i . k_j)^2``, ``c`` a head's running log-gate,
normalised by their sum.  The norms, the rotation and the gate's projection
around it are the model's.  Three forms of the one recurrence:

* :func:`recurrent` — the per-token ``lax.scan`` with ``phi`` built plainly
  (the upper triangle, row-major): the oracle of the other two and of the
  tests.
* :func:`chunked` — prefill.  Over chunks of ``CHUNK`` tokens, ``c``
  restarted at the chunk's start,

      A = (Q K^T)^2 * exp(c_i - c_j)        j <= i, else 0 (exponents <= 0)
      num = A V + exp(c) * (phi(Q) S0)      den = A 1 + exp(c) * (phi(Q) z0)
      S1 = exp(c_T) S0 + sum_j exp(c_T - c_j) phi(k_j) v_j^T     z1 likewise

  no solve, no clamp, no sub-blocks.  On a TPU the WHOLE chunk — scores,
  decays, both sums and the chunk-to-chunk carry — is the Pallas kernel
  ``power_chunk_state`` (grid: row x KV head x chunk, the state resident
  over the chunks; every product at float32 precision whatever the
  caller's dtype); elsewhere :func:`_chunked_plain`.
* :func:`step` — decode, one token a row, on the WHOLE leaves at a layer
  index: the Pallas kernel ``power_step`` reads each (row, KV head) state
  once and writes it once, in place (``input_output_aliases``), all on the
  VPU; ``phi(k)`` and the group's ``phi(q)`` are built in VMEM.

**The stored state** orders the monomials by CYCLIC DISTANCE, so that
``phi`` is ``n / 2 + 1`` lane rotations of the vector and never a gather:
row ``d`` of the stored ``phi`` is ``x * roll(x, d) * w_d`` — lane ``a``
holds ``x_a x_{a - d}`` — with ``w_0 = 1`` (the squares), ``w_d = sqrt 2``
for ``0 < d < n / 2`` (each unordered pair at distance ``d`` once) and
``w_{n/2} = 1`` (each pair at distance ``n / 2`` TWICE, at lanes ``a`` and
``a + n / 2``: two ones where the triangle has one ``sqrt 2``).  The inner
product is the same ``(q . k)^2``; the stored state is ``[.., n / 2 + 1, n,
n]`` — distance, VALUE channel on the sublanes, key lane — (8,320 rows of
128 at ``n`` = 128, 64 of them duplicates: 0.8 % over the triangle's 8,256)
and ``z [.., n / 2 + 1, n]``.  :func:`pack_state` / :func:`unpack_state` move
between it and the triangle's ``[.., D, n]``.

A PAD (``k = 0``, ``lg = 0``) leaves the state as it was: its gate is one
and its ``phi`` zero.  A token whose weights are ALL exactly zero (a pad, an
idle row) reads 0, not 0 / 0.  The state, ``z``, the log-gates and the sums
over a chunk are float32 whatever the model's dtype.  An open
``ops/decode_attention.dispatch_log`` collects which body a trace was built
with (``power_step`` / ``power_chunk_state``, or ``power_*_plain``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.platform import interpret_kernels, on_tpu
from . import decode_attention as da
from .paged_kv import LANES

#: tokens of one chunk of the chunked form
CHUNK = 128
#: value channels (sublanes) of the state one pass of ``power_step``'s inner
#: loop holds in registers beside the group's accumulators
STEP_SUBLANES = 32
#: scoped VMEM of both kernels: a KV head's state in and out, each double
#: buffered (4 x 4.26 MB at n = 128), beside the small operands
VMEM_LIMIT = 48 * 1024 * 1024
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32
_SQRT2 = math.sqrt(2.0)


def _f32(*arrays):
    return tuple(jnp.asarray(a, _F32) for a in arrays)


# ------------------------------------------------------------ the stored view
def distances(head_dim: int) -> int:
    """Rows of lanes of the stored ``phi``: the cyclic distances ``0 .. n /
    2`` (module docstring)."""
    if head_dim % 2:
        raise ValueError(f"head_dim={head_dim}: the stored state pairs "
                         "lanes by cyclic distance and takes an even width")
    return head_dim // 2 + 1


def monomials(head_dim: int) -> int:
    """``D``: the distinct degree-2 monomials of a ``head_dim``-wide key."""
    return head_dim * (head_dim + 1) // 2


def stored_shape(heads: int, head_dim: int):
    """``(H, n / 2 + 1, n, n)``: a row's stored state; ``z``'s is its first
    three dims."""
    return heads, distances(head_dim), head_dim, head_dim


def _weight(d, head_dim: int):
    """``w_d`` (module docstring), ``d`` an int or traced."""
    if isinstance(d, int):
        return 1.0 if d in (0, head_dim // 2) else _SQRT2
    return jnp.where((d == 0) | (d == head_dim // 2), 1.0, _SQRT2)


def phi_stored(x):
    """``[.., n] -> [.., n / 2 + 1, n]``: the stored ``phi``, plainly."""
    n = x.shape[-1]
    return jnp.stack([x * jnp.roll(x, d, axis=-1) * _weight(d, n)
                      for d in range(distances(n))], axis=-2)


def phi(x):
    """``[.., n] -> [.., D]``: the triangle's ``phi``, row-major over ``a <=
    b``, off-diagonal monomials weighted ``sqrt 2``."""
    n = x.shape[-1]
    a, b = np.triu_indices(n)
    return x[..., a] * x[..., b] * jnp.where(a == b, 1.0, _SQRT2)


@functools.lru_cache(maxsize=None)
def _triangle_at(head_dim: int):
    """Where each monomial ``(a <= b)`` of the triangle lies in the stored
    view: ``(distance, lane, weight ratio)`` — lane ``b`` at distance ``b -
    a`` up to ``n / 2``, lane ``a`` at ``n - (b - a)`` past it; a pair at
    ``n / 2`` is stored twice with weight one and read from one of them."""
    n = head_dim
    a, b = np.triu_indices(n)
    e = b - a
    near = e <= n // 2
    return (np.where(near, e, n - e), np.where(near, b, a),
            np.where(e == n // 2, _SQRT2, 1.0).astype(np.float32))


def unpack_state(s):
    """Stored ``[.., n / 2 + 1, n (value), n (lane)] -> [.., D, n]``: the
    triangle's view of a state (tests, the reference's comparisons)."""
    d, lane, ratio = _triangle_at(s.shape[-1])
    return jnp.swapaxes(s, -1, -2)[..., d, lane, :] * ratio[:, None]


def unpack_z(z):
    """Stored ``[.., n / 2 + 1, n] -> [.., D]``."""
    d, lane, ratio = _triangle_at(z.shape[-1])
    return z[..., d, lane] * ratio


def pack_state(s):
    """:func:`unpack_state`'s inverse ``[.., D, n] -> [.., n / 2 + 1, n,
    n]`` (both copies of a pair at distance ``n / 2`` written)."""
    n = s.shape[-1]
    d, lane, ratio = _triangle_at(n)
    out = jnp.zeros(s.shape[:-2] + (distances(n), n, n), s.dtype)
    out = out.at[..., d, lane, :].set(s / ratio[:, None])
    half = d == n // 2
    out = out.at[..., d[half], (lane[half] + n // 2) % n, :].set(
        s[..., half, :] / _SQRT2)
    return jnp.swapaxes(out, -1, -2)


def pack_z(z):
    """:func:`unpack_z`'s inverse ``[.., D] -> [.., n / 2 + 1, n]``."""
    n = (math.isqrt(8 * z.shape[-1] + 1) - 1) // 2
    d, lane, ratio = _triangle_at(n)
    out = jnp.zeros(z.shape[:-1] + (distances(n), n), z.dtype)
    out = out.at[..., d, lane].set(z / ratio)
    half = d == n // 2
    return out.at[..., d[half], (lane[half] + n // 2) % n].set(
        z[..., half] / _SQRT2)


def _safe(num, den):
    """``num / den``, 0 where every weight is exactly zero (a pad)."""
    return num / jnp.where(den == 0.0, 1.0, den)


def _grouped(q, heads: int):
    """``[.., Hq, n] -> [.., H, G, n]``: query head ``m`` reads KV head ``m
    // G``."""
    return q.reshape(q.shape[:-2] + (heads, q.shape[-2] // heads,
                                     q.shape[-1]))


# ------------------------------------------------------------------ the oracle
def recurrent(q, k, v, lg, state, z):
    """The recurrence token by token, float32, on the TRIANGLE's view.  ``q
    [B, T, Hq, n]``, ``k``, ``v`` ``[B, T, H, n]``, ``lg [B, T, H]`` (a KV
    head's log-gate, <= 0), ``state [B, H, D, n]``, ``z [B, H, D]`` -> ``(y
    [B, T, Hq, n] float32, state, z)``."""
    q, k, v, lg, state, z = _f32(q, k, v, lg, state, z)
    heads = k.shape[2]

    def one(carry, xs):
        s, zz = carry
        qt, kt, vt, lt = xs
        g, pk = jnp.exp(lt), phi(kt)
        s = g[..., None, None] * s + pk[..., :, None] * vt[..., None, :]
        zz = g[..., None] * zz + pk
        pq = phi(_grouped(qt, heads))                        # [B, H, G, D]
        num = jnp.einsum("bhgm,bhmc->bhgc", pq, s, precision=_HI)
        den = jnp.einsum("bhgm,bhm->bhg", pq, zz, precision=_HI)
        return (s, zz), _safe(num, den[..., None]).reshape(qt.shape)

    (state, z), y = jax.lax.scan(one, (state, z), tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, lg)))
    return jnp.moveaxis(y, 0, 1), state, z


# ------------------------------------------------------------ the chunked form
def _chunked_plain(q, k, v, lg, state, z, chunk: int):
    """The chunked form in plain XLA, on the stored state: a scan over the
    chunks."""
    bsz, t, hq, n = q.shape
    heads = k.shape[2]
    i = jnp.arange(chunk)
    tri = (i[:, None] >= i[None, :])[None, None]

    def one(carry, xs):
        s, zz = carry
        qc, kc, vc, lc = xs
        cs = jnp.moveaxis(jnp.cumsum(lc, axis=1), 1, -1)         # [B, H, C]
        low = jnp.where(tri, jnp.exp(jnp.minimum(
            cs[..., :, None] - cs[..., None, :], 0.0)), 0.0)     # [B,H,C,C]
        qg = jnp.moveaxis(_grouped(qc, heads), 1, 3)             # [B,H,G,C,n]
        kh, vh = jnp.moveaxis(kc, 1, 2), jnp.moveaxis(vc, 1, 2)  # [B,H,C,n]
        a = jnp.einsum("bhgin,bhjn->bhgij", qg, kh, precision=_HI) ** 2 \
            * low[:, :, None]
        pq, pk = phi_stored(qg), phi_stored(kh)
        grown = jnp.exp(cs)[:, :, None, :, None]
        num = jnp.einsum("bhgij,bhjc->bhgic", a, vh, precision=_HI) \
            + grown * jnp.einsum("bhgida,bhdca->bhgic", pq, s, precision=_HI)
        den = a.sum(-1) + grown[..., 0] * jnp.einsum(
            "bhgida,bhda->bhgi", pq, zz, precision=_HI)
        last = cs[..., -1:]
        fed = jnp.exp(last - cs)                                 # [B, H, C]
        s = jnp.exp(last)[..., None, None] * s + jnp.einsum(
            "bhjc,bhjda->bhdca", vh * fed[..., None], pk, precision=_HI)
        zz = jnp.exp(last)[..., None] * zz + jnp.einsum(
            "bhj,bhjda->bhda", fed, pk, precision=_HI)
        y = _safe(num, den[..., None])                           # [B,H,G,C,n]
        return (s, zz), jnp.moveaxis(y, 3, 1).reshape(bsz, chunk, hq, n)

    def chunks(a):
        return jnp.moveaxis(
            a.reshape((bsz, t // chunk, chunk) + a.shape[2:]), 1, 0)

    (state, z), y = jax.lax.scan(one, (state, z), tuple(
        chunks(a) for a in (q, k, v, lg)))
    return jnp.moveaxis(y, 0, 1).reshape(bsz, t, hq, n), state, z


def _mm(a, b, dims=((1,), (0,))):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=_F32)


def _chunk_kernel(q_ref, k_ref, v_ref, cc_ref, cr_ref, s_ref, z_ref, y_ref,
                  s_out_ref, z_out_ref, num_ref, den_ref, *, group: int):
    """One (row, KV head, chunk): the chunk's scores, decays, both sums and
    the state through it.  ``q_ref [G, C, n]``: the group's queries;
    ``cc_ref [C, 1]`` / ``cr_ref [1, C]``: the head's running log-gate
    inside the chunk as a COLUMN over the tokens and as a ROW; ``s_out_ref``
    / ``z_out_ref`` stay resident over the chunk axis and carry the state;
    ``num_ref`` / ``den_ref [G C, n]``: what the queries read of the state
    carried in, summed over the distances."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_out_ref[...] = s_ref[...]
        z_out_ref[...] = z_ref[...]

    t, n = k_ref.shape
    nd = s_out_ref.shape[0]
    k, v, cc, cr = k_ref[...], v_ref[...], cc_ref[...], cr_ref[...]
    tri = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    low = jnp.where(tri, jnp.exp(jnp.minimum(cc - cr, 0.0)), 0.0)
    last = cc[t - 1:t, :]
    grown = jnp.exp(cc)                   # exp(c_i): what S0 has kept
    fed = jnp.exp(last - cc)              # exp(c_T - c_j)
    # (a [1, 1] value meets the state's tiles as a ROW over their lanes)
    kept = jnp.exp(jnp.broadcast_to(last, (1, n)))
    qs = q_ref[...].reshape(group * t, n)
    vf = v * fed
    num_ref[...] = jnp.zeros_like(num_ref)
    den_ref[...] = jnp.zeros_like(den_ref)

    def distance(d, carry):
        # the two products over the monomials: 2 (G + 1) D n FLOPs a
        # token, nearly all of the kernel's
        w = _weight(d, n)
        pq = qs * pltpu.roll(qs, d, 1) * w
        pk = k * pltpu.roll(k, d, 1) * w
        s, zz = s_out_ref[d], z_out_ref[pl.ds(d, 1), :]
        num_ref[...] += _mm(pq, s, ((1,), (1,)))
        den_ref[...] += pq * zz
        s_out_ref[d] = kept * s + _mm(vf, pk, ((0,), (0,)))
        z_out_ref[pl.ds(d, 1), :] = kept * zz \
            + jnp.sum(pk * fed, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, nd, distance, 0)
    for g in range(group):
        at = slice(g * t, (g + 1) * t)
        a = _mm(q_ref[g], k, ((1,), (1,)))
        a = a * a * low
        num = _mm(a, v) + grown * num_ref[at, :]
        den = jnp.sum(a, axis=1, keepdims=True) \
            + grown * jnp.sum(den_ref[at, :], axis=1, keepdims=True)
        y_ref[g] = num / jnp.where(den == 0.0, 1.0, den)


def _chunked_pallas(q, k, v, lg, state, z, chunk: int, interpret=None):
    bsz, t, hq, n = q.shape
    heads, nd = k.shape[2], state.shape[2]
    group, nc = hq // heads, t // chunk
    # by (row, KV head): the group's queries [B, H, G, T, n], keys and
    # values [B, H, T, n], the running log-gate inside each chunk as a
    # column and as a row [B, H, chunks, C, 1] / [.., 1, C]
    qg = jnp.moveaxis(_grouped(q, heads), 1, 3)
    kh, vh = jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2)
    cs = jnp.cumsum(jnp.moveaxis(lg, 1, 2).reshape(bsz, heads, nc, chunk),
                    axis=-1)
    tok = pl.BlockSpec((None, None, chunk, n), lambda i, j, ci: (i, j, ci, 0))
    grp = pl.BlockSpec((None, None, group, chunk, n),
                       lambda i, j, ci: (i, j, 0, ci, 0))
    col = pl.BlockSpec((None, None, None, chunk, 1),
                       lambda i, j, ci: (i, j, ci, 0, 0))
    row = pl.BlockSpec((None, None, None, 1, chunk),
                       lambda i, j, ci: (i, j, ci, 0, 0))
    st = pl.BlockSpec((None, None, nd, n, n), lambda i, j, ci: (i, j, 0, 0, 0))
    zs = pl.BlockSpec((None, None, nd, n), lambda i, j, ci: (i, j, 0, 0))
    y, state, z = pl.pallas_call(
        functools.partial(_chunk_kernel, group=group),
        grid=(bsz, heads, nc),
        in_specs=[grp, tok, tok, col, row, st, zs],
        out_specs=[grp, st, zs],
        out_shape=[jax.ShapeDtypeStruct(qg.shape, _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32),
                   jax.ShapeDtypeStruct(z.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((group * chunk, n), _F32),
                        pltpu.VMEM((group * chunk, n), _F32)],
        # the gathered state and normaliser are advanced where they lie
        input_output_aliases={5: 1, 6: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret_kernels() if interpret is None else interpret,
        name="power_chunk_state",
    )(qg, kh, vh, cs[..., None], cs[..., None, :], state, z)
    return jnp.moveaxis(y, 3, 1).reshape(bsz, t, hq, n), state, z


@jax.named_scope("layer/state/chunk")
def chunked(q, k, v, lg, state, z, *, kernel: Optional[bool] = None,
            interpret: Optional[bool] = None):
    """:func:`recurrent`'s contract through the chunked form, on the STORED
    state ``[B, H, n / 2 + 1, n, n]`` and ``z [B, H, n / 2 + 1, n]``; ``T`` a
    whole number of chunks (``min(CHUNK, T)`` tokens each).  ``kernel``: the
    Pallas kernel (default: on a TPU, at whole 128-lane heads)."""
    q, k, v, lg, state, z = _f32(q, k, v, lg, state, z)
    t = q.shape[1]
    chunk = min(CHUNK, t)
    if t % chunk:
        raise ValueError(f"{t} tokens are not whole chunks of {chunk}")
    if _kernel_default(q.shape[-1]) if kernel is None else kernel:
        da._took("power_chunk_state")
        return _chunked_pallas(q, k, v, lg, state, z, chunk, interpret)
    da._took("power_chunk_plain")
    return _chunked_plain(q, k, v, lg, state, z, chunk)


def _kernel_default(head_dim: int) -> bool:
    return on_tpu() and head_dim % LANES == 0


# ----------------------------------------------------------------- decode step
def _step_kernel(layer_ref, x_ref, v_ref, s_ref, z_ref, y_ref, den_ref,
                 s_out_ref, z_out_ref, p_ref, *, group: int, sublanes: int):
    """One (row, KV head): its state read once and written once.  ``x_ref
    [8, n]``: the key (row 0), the group's queries (rows 1 .. G) and the
    gate over the lanes (row 7); ``v_ref [n, 1]``: the value as a COLUMN
    over the state's sublanes; ``p_ref [n / 2 + 1, 8, n]`` (scratch): the
    stored ``phi`` of the key and of the queries, built here a distance at
    a time."""
    del layer_ref                       # consumed by the leaves' index maps
    nd, n = z_ref.shape
    x = x_ref[...]
    g = x[7:8, :]
    den = jnp.zeros_like(x)
    for d in range(nd):
        p = x * (x if d == 0 else pltpu.roll(x, d, 1)) * _weight(d, n)
        zz = g * z_ref[d:d + 1, :] + p[0:1, :]
        z_out_ref[d:d + 1, :] = zz
        den = den + p * zz
        p_ref[d] = p
    den_ref[...] = den
    for c0 in range(0, n, sublanes):
        at = pl.ds(c0, sublanes)
        vb = jnp.broadcast_to(v_ref[at, :], (sublanes, n))

        def distance(d, acc, at=at, vb=vb):
            p = p_ref[d]
            s = s_ref[d, at, :] * g + vb * p[0:1, :]
            s_out_ref[d, at, :] = s
            return tuple(a + s * p[j + 1:j + 2, :]
                         for j, a in enumerate(acc))

        acc = jax.lax.fori_loop(
            0, nd, distance,
            tuple(jnp.zeros((sublanes, n), _F32) for _ in range(group)))
        for j, a in enumerate(acc):
            y_ref[at, j:j + 1] = jnp.sum(a, axis=1, keepdims=True)


def _step_pallas(x, v, leaf, zleaf, layer, group: int, interpret=None):
    rows, heads, nd, n = zleaf.shape[1:]
    sublanes = min(STEP_SUBLANES, n)
    at = lambda *tail: pl.BlockSpec(
        (None, None) + tail, lambda i, j, layer: (i, j) + (0,) * len(tail))
    leaf_at = lambda *tail: pl.BlockSpec(
        (None, None, None) + tail,
        lambda i, j, layer: (layer[0], i, j) + (0,) * len(tail))
    y, den, leaf, zleaf = pl.pallas_call(
        functools.partial(_step_kernel, group=group, sublanes=sublanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows, heads),
            in_specs=[at(8, n), at(n, 1), leaf_at(nd, n, n), leaf_at(nd, n)],
            out_specs=[at(n, 8), at(8, n), leaf_at(nd, n, n),
                       leaf_at(nd, n)],
            scratch_shapes=[pltpu.VMEM((nd, 8, n), _F32)]),
        out_shape=[jax.ShapeDtypeStruct((rows, heads, n, 8), _F32),
                   jax.ShapeDtypeStruct((rows, heads, 8, n), _F32),
                   jax.ShapeDtypeStruct(leaf.shape, leaf.dtype),
                   jax.ShapeDtypeStruct(zleaf.shape, zleaf.dtype)],
        # both leaves are updated in place (operands 3 and 4, after the
        # scalar)
        input_output_aliases={3: 2, 4: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret_kernels() if interpret is None else interpret,
        name="power_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), x, v[..., None], leaf, zleaf)
    num = jnp.swapaxes(y, -1, -2)[:, :, :group]              # [rows,H,G,n]
    return num, den[:, :, 1:group + 1].sum(-1), leaf, zleaf


@jax.named_scope("layer/state/step")
def step(q, k, v, lg, leaf, zleaf, layer, *, kernel: Optional[bool] = None,
         interpret: Optional[bool] = None):
    """One token a row against the WHOLE stored leaves ``[L, rows, H, n / 2
    + 1, n, n]`` and ``[L, rows, H, n / 2 + 1, n]`` (float32) at ``layer``
    (traced): ``q [rows, Hq, n]``, ``k``, ``v`` ``[rows, H, n]``, ``lg [rows,
    H]`` -> ``(y [rows, Hq, n] float32, leaf, zleaf)``.  A row to be left as
    it is carries ``k = 0`` and ``lg = 0``."""
    q, k, v, lg = _f32(q, k, v, lg)
    rows, hq, n = q.shape
    heads = k.shape[1]
    group = hq // heads
    qg = _grouped(q, heads)
    if (_kernel_default(n) if kernel is None else kernel) and group <= 6:
        da._took("power_step")
        x = jnp.concatenate([
            k[:, :, None], qg, jnp.zeros((rows, heads, 6 - group, n), _F32),
            jnp.broadcast_to(jnp.exp(lg)[..., None, None],
                             (rows, heads, 1, n))], axis=2)
        num, den, leaf, zleaf = _step_pallas(x, v, leaf, zleaf, layer, group,
                                             interpret=interpret)
    else:
        da._took("power_step_plain")
        g, pk, pq = jnp.exp(lg), phi_stored(k), phi_stored(qg)
        s = g[..., None, None, None] * jax.lax.dynamic_index_in_dim(
            leaf, layer, keepdims=False) \
            + v[:, :, None, :, None] * pk[:, :, :, None, :]
        zz = g[..., None, None] * jax.lax.dynamic_index_in_dim(
            zleaf, layer, keepdims=False) + pk
        num = jnp.einsum("rhgda,rhdca->rhgc", pq, s, precision=_HI)
        den = jnp.einsum("rhgda,rhda->rhg", pq, zz, precision=_HI)
        leaf = jax.lax.dynamic_update_index_in_dim(leaf, s, layer, 0)
        zleaf = jax.lax.dynamic_update_index_in_dim(zleaf, zz, layer, 0)
    return _safe(num, den[..., None]).reshape(rows, hq, n), leaf, zleaf
