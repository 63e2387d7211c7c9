"""ISSUE 47: Mosaic's own compile, for a described v5e (no chip), of what
the routed training cell adds to the step at the cell's shapes — the three
``moe_gmm*`` bodies over 49,152 sorted pairs of which a quarter are held,
and flash attention forward + backward under a 4,096-key window at 8,192
tokens, 28 query heads on 4 KV heads — in ``test_chip_lowering.py``'s
manner (a block shape Mosaic refuses, or a kernel over its VMEM, fails here
and not on the chip)."""

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.moe import grouped_matmul as gm
from deepspeed_tpu.ops import flash_attention as fa


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


#: (rows, K, N): the cell's gate / up projection and its down projection
GMM_SHAPES = [(49152, 2560, 768), (49152, 768, 2560)]


@pytest.mark.parametrize("rows,k,n", GMM_SHAPES)
def test_grouped_matmul_and_its_two_transposes_compile_for_a_v5e(
        rows, k, n, one_chip):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(lhs, rhs, sizes):
        out = gm.moe_gmm(lhs, rhs, sizes, interpret=False)
        return (out.astype(jnp.float32) ** 2).sum()  # the forward is needed

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        sds((rows, k), jnp.bfloat16), sds((16, k, n), jnp.bfloat16),
        sds((16,), jnp.int32)).compile().as_text()
    for kernel in ("moe_gmm", "moe_gmm_dlhs", "moe_gmm_drhs"):
        assert f'"{kernel}"' in text or f"{kernel}" in text, kernel
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("window", [0, 4096])
def test_windowed_flash_compiles_for_a_v5e_at_the_cells_shape(window,
                                                              one_chip):
    q = jax.ShapeDtypeStruct((1, 28, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4, 8192, 128), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        o = fa.flash_attention(q, k, v, causal=True, interpret=False,
                               window=window)
        return o.astype(jnp.float32).sum()

    before = fa.choices()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    (choice,) = fa.choices(since=before)
    assert (choice.generation, choice.window) == ("v3", window)
    assert min(choice.block_q, choice.block_k) >= 512, choice
    for kernel in fa.KERNELS["v3"]:
        assert kernel in text, (kernel, choice)


def test_window_zero_traces_to_the_program_without_one():
    """``window=0`` named is the program of a caller that names none: the
    same jaxpr, kernels' bodies and index maps included."""
    q = jax.ShapeDtypeStruct((1, 4, 2048, 64), jnp.bfloat16)

    def text(**kw):
        return str(jax.make_jaxpr(jax.grad(
            lambda q, k, v: fa.flash_attention(
                q, k, v, interpret=False, **kw).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))(q, q, q))

    assert text() == text(window=0)
    assert text() != text(window=512)
