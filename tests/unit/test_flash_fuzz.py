"""Property/fuzz tests for the flash kernels: random shapes, GQA ratios,
causal flags — every case must match the einsum reference in interpret
mode.  Each shape runs through BOTH the v2 fused path and (via the
DS_FLASH_V2=0 kill switch) the v1 two-kernel fallback, so padding/masking
edges are covered on both code paths.  ISSUE 62: every case also with the
edge tiles in strips of 32 rows (``_STRIP`` set here; the program reads the
strip from its shapes), where the case's blocks are multiples of that."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import flash_attention as fa
from deepspeed_tpu.ops.flash_attention import flash_attention, mha_reference

pytestmark = pytest.mark.slow

CASES = []
_rng = np.random.default_rng(20260731)
for _ in range(10):
    d = int(_rng.choice([32, 64, 128]))
    h_kv = int(_rng.choice([1, 2, 4]))
    rep = int(_rng.choice([1, 2, 4]))
    s = int(_rng.choice([64, 120, 200, 256, 384, 512]))
    causal = bool(_rng.choice([True, False]))
    CASES.append((2, h_kv * rep, h_kv, s, d, causal))


# blocks the caller names (128 x 128: several chunks a row at these lengths,
# as every case ran before flash_attention chose its own) and the chosen ones
@pytest.mark.parametrize("strip", [None, 32], ids=["strip256", "strip32"])
@pytest.mark.parametrize("blocks", [{}, {"block_q": 128, "block_k": 128}],
                         ids=["chosen", "given128"])
@pytest.mark.parametrize("kernel_ver", ["v2", "v1", "v3"])
@pytest.mark.parametrize("b,h,hkv,s,d,causal", CASES)
def test_fuzz_matches_reference(b, h, hkv, s, d, causal, kernel_ver, blocks,
                                strip, monkeypatch):
    if strip:
        monkeypatch.setattr(fa, "_STRIP", strip)
    # pin ALL branches: an ambient DS_FLASH_V2/V3 from a debugging shell
    # must not silently collapse the matrix onto one path
    monkeypatch.setenv("DS_FLASH_V2", "1" if kernel_ver == "v2" else "0")
    monkeypatch.setenv("DS_FLASH_V3", "1" if kernel_ver == "v3" else "0")
    if kernel_ver == "v3":
        # the long-sequence path: force it down to fuzz-sized shapes so the
        # chunked-grid + compact-lse logic runs with several KV chunks
        monkeypatch.setenv("DS_FLASH_V3_MIN_KV", "1")
    ks = jax.random.split(jax.random.PRNGKey(hash((b, h, s, d)) % 2**31), 3)
    q = jax.random.normal(ks[0], (b, h, s, d))
    k = jax.random.normal(ks[1], (b, hkv, s, d))
    v = jax.random.normal(ks[2], (b, hkv, s, d))
    out = flash_attention(q, k, v, causal=causal, interpret=True, **blocks)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    def loss_f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       interpret=True, **blocks) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, r, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), atol=1e-3,
                                   rtol=1e-3, err_msg=f"d{name} {(s, d)}")
