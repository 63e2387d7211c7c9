"""1-bit optimizer + compressed-collective tests (reference
``tests/onebit/test_onebit.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.runtime.comm.compressed import (CompressedBackend,
                                                   compressed_allreduce,
                                                   error_shapes)
from deepspeed_tpu.runtime.fp16.onebit import (onebit_adam, onebit_lamb,
                                               zero_one_adam)


# --------------------------------------------------------- compressed comm
def test_compressed_allreduce_error_feedback(eight_devices):
    """Per-step the reduction is lossy, but error feedback makes the
    *accumulated* sum track the true accumulated mean (the 1-bit Adam
    convergence argument)."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.parallel.topology import MeshTopology

    mesh = MeshTopology(dp=8).mesh
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 64)).astype(np.float32)
    true_mean = x.mean(axis=0)
    we_s, se_s = error_shapes((64,), 8)

    @jax.jit
    def step(xs, wes, ses):
        def body(xw, wew, sew):
            m, nwe, nse = compressed_allreduce(xw[0], wew[0], sew[0], "dp")
            return m[None], nwe[None], nse[None]

        return jax.shard_map(body, mesh=mesh, in_specs=(P("dp"),) * 3,
                         out_specs=(P("dp"),) * 3)(xs, wes, ses)

    with mesh:
        xs = jax.device_put(x)
        wes = jnp.zeros((8,) + we_s, jnp.float32)
        ses = jnp.zeros((8,) + se_s, jnp.float32)
        acc = np.zeros(64, np.float32)
        # same x re-reduced: accumulated compressed means -> k * true_mean,
        # with error decaying ~1/k (bounded error feedback)
        errs_at = {}
        for k in range(1, 101):
            mean, wes, ses = step(xs, wes, ses)
            acc += np.asarray(mean)[0]
            if k in (10, 100):
                errs_at[k] = np.abs(acc / k - true_mean).max()
    assert errs_at[100] < 0.06
    assert errs_at[100] < errs_at[10] / 2  # 1/k decay, not bias
    # single-shot error is visibly nonzero (it IS lossy)
    one, _, _ = step(xs, jnp.zeros_like(wes), jnp.zeros_like(ses))
    assert np.abs(np.asarray(one)[0] - true_mean).max() > 1e-4


def test_compressed_backend_stateful(eight_devices):
    from deepspeed_tpu.parallel.topology import MeshTopology

    deepspeed_tpu.comm.reset_topology()
    mesh = MeshTopology(dp=8).mesh
    be = CompressedBackend(mesh, "dp")
    x = np.random.default_rng(1).normal(size=(8, 32)).astype(np.float32)
    with mesh:
        acc = np.zeros(32, np.float32)
        k = 80
        for _ in range(k):
            acc += np.asarray(be.allreduce("g", jnp.asarray(x)))[0]
    np.testing.assert_allclose(acc / k, x.mean(0), atol=0.1)


# ------------------------------------------------------------- optimizers
def _rosenbrockish_losses(tx, steps=260):
    def loss(p):
        return jnp.sum((p["a"] - 1.0) ** 2) + 2.0 * jnp.sum(p["b"] ** 2)

    params = {"a": jnp.zeros(8), "b": jnp.ones(4)}
    state = tx.init(params)

    @jax.jit
    def step(params, state):
        l, g = jax.value_and_grad(loss)(params)
        upd, state = tx.update(g, state, params)
        return optax_apply(params, upd), state, l

    import optax

    def optax_apply(p, u):
        return optax.apply_updates(p, u)

    ls = []
    for _ in range(steps):
        params, state, l = step(params, state)
        ls.append(float(l))
    return ls


@pytest.mark.parametrize("maker", [
    lambda: onebit_adam(lr=3e-2, freeze_step=50),
    lambda: onebit_lamb(lr=0.5, freeze_step=50),  # trust-ratio clamps to
    # [0.01, 0.3] x lr, so the effective step needs a larger base lr
    lambda: zero_one_adam(lr=3e-2, var_freeze_step=50),
])
def test_onebit_optimizers_converge(maker):
    ls = _rosenbrockish_losses(maker())
    assert ls[-1] < 1e-2 * ls[0], (ls[0], ls[-1])
    # loss keeps improving after entering the compressed stage
    assert min(ls[55:]) < min(ls[:50])


def test_variance_freezes_after_freeze_step():
    from deepspeed_tpu.runtime.fp16.onebit import scale_by_onebit_adam

    tx = scale_by_onebit_adam(freeze_step=3)
    params = {"w": jnp.ones(4)}
    state = tx.init(params)
    # non-uniform grads: a uniform tensor quantizes exactly (zero residual)
    g = {"w": jnp.asarray([0.1, 0.5, -0.7, 0.2])}
    for _ in range(3):
        _, state = tx.update(g, state, params)
    v_frozen = np.asarray(state.v["w"]).copy()
    g2 = {"w": jnp.full(4, 100.0)}  # huge grad: v would change if learning
    _, state = tx.update(g2, state, params)
    np.testing.assert_array_equal(np.asarray(state.v["w"]), v_frozen)
    # error feedback active in compressed stage
    assert np.abs(np.asarray(state.error["w"])).max() > 0


def test_engine_accepts_onebit_adam():
    deepspeed_tpu.comm.reset_topology()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=gpt2.build(gpt2.GPT2Config.tiny()),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "OneBitAdam",
                              "params": {"lr": 1e-3, "freeze_step": 2}}})
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(5):
        batch = {"input_ids": rng.integers(
            0, 512, (engine.train_batch_size(), 17)).astype(np.int32)}
        _, m = engine.train_batch(batch)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
