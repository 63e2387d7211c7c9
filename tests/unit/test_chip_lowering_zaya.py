"""ISSUE 66: the compile, for a described v5e (no chip), of
``zaya1-reasoning-closed``'s three serving programs whole — ZAYA1-8B at its
published widths over ten layers, 128 slots x 4,096 positions, the sampler
over 262,272 entries a row — in ``test_chip_lowering_glm5.py``'s manner: the
kernels that exist serve as they are (``moe_gmm``, ``paged_decode_attn``,
``paged_prefill_attn``), the whole cache tree (K, V AND the tails) is
aliased, and the temporaries leave room beside 10.6 GB of weights and
pool."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import decode_attention as da
from deepspeed_tpu.ops import paged_kv, sampling

ROOT = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir)
SLOTS, CTX, BLOCK = 128, 4096, 32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.limit(300)
def test_compiled_programs_fit_and_alias_the_pool_and_the_tails(
        one_chip, monkeypatch):
    from chipbench.families import zaya as family
    from deepspeed_tpu.moe import grouped_matmul
    from deepspeed_tpu.utils import platform

    for mod in (platform, da):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)
        monkeypatch.setattr(mod, "interpret_kernels", lambda: False)
    for mod in (grouped_matmul, sampling):
        monkeypatch.setattr(mod, "interpret_kernels", lambda: False)
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "ZAYA1-8B.json")) as f:
        config = json.load(f)
    config.pop("rehearse")
    spec = family.build(config)
    hooks = spec.decode_hooks
    fwd, nbper = hooks["forward_cached"], CTX // BLOCK

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def arg(dtype, *shape):
        return sds(jax.ShapeDtypeStruct(shape, dtype))

    params = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16),
            spec.init_fn(jax.random.PRNGKey(0)))))

    def pool():
        cache = hooks["init_cache"](1 + SLOTS * nbper, BLOCK, jnp.bfloat16,
                                    state_rows=SLOTS)
        return {k: v if k in paged_kv.ROW_LEAVES else paged_kv.pack_pool(v)
                for k, v in cache.items()}

    cache = jax.tree_util.tree_map(sds, jax.eval_shape(pool))
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (10, 16385, 2, 32, 128), "v": (10, 16385, 2, 32, 128),
        "conv": (10, 128, 1, 2, 1280), "shift": (10, 128, 1, 1, 128)}
    cache_bytes = sum(int(np.prod(v.shape)) * 2 for v in cache.values())
    assert round(cache_bytes / 1e9, 2) == 5.38

    def pick(logits, temps, topps, seeds, counts):
        _, logprobs = sampling.filtered_logprobs(
            logits, temps, jnp.zeros_like(counts), topps)
        return sampling.sample_tokens(
            logprobs, sampling.slot_keys(seeds, counts, 0))

    def decode(params, cache, tok, lengths, bt, *samp):
        logits, cache, rec = fwd(params, tok, cache, 0, lengths=lengths,
                                 block_tables={"full": bt}, routing=True)
        return pick(logits, *samp), cache, rec

    def prefill(params, cache, ids, bt, slot, base, valid, *samp):
        logits, cache, rec = fwd(
            params, ids, cache, base, lengths=valid,
            block_tables={"full": bt, "slot": slot}, routing=True)
        return pick(logits, *samp), cache, rec

    def samp(n):
        return (arg(jnp.float32, n), arg(jnp.float32, n),
                arg(jnp.uint32, n), arg(jnp.int32, n))

    i32 = jnp.int32
    programs = {
        "decode": (decode, (params, cache, arg(i32, SLOTS, 1),
                            arg(i32, SLOTS), arg(i32, SLOTS, nbper))
                   + samp(SLOTS), "paged_decode_attn"),
        "prefill 4x128": (prefill, (params, cache, arg(i32, 4, 128),
                                    arg(i32, 4, nbper), arg(i32, 4),
                                    arg(i32, 4), arg(i32, 4)) + samp(4),
                          "paged_prefill_attn"),
        "prefill 1x512": (prefill, (params, cache, arg(i32, 1, 512),
                                    arg(i32, 1, nbper), arg(i32, 1),
                                    arg(i32, 1), arg(i32, 1)) + samp(1),
                          "paged_prefill_attn")}
    for name, (fn, args, attention) in programs.items():
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
        text = compiled.as_text()
        for kernel in ("moe_gmm", attention, "nucleus_search"):
            assert kernel in text, (name, kernel)
        mem = compiled.memory_analysis()
        print(name, "temporaries", mem.temp_size_in_bytes / 1e6, "MB")
        # a decode step's [128, 262,272] float32 logits are 134 MB
        assert mem.temp_size_in_bytes < 600 * (1 << 20), (name, mem)
        assert mem.alias_size_in_bytes >= cache_bytes, (name, mem)
