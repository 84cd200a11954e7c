"""Aux JAX analytics model tests: forward, train step, and mesh sharding on
the virtual 8-device CPU mesh (conftest sets the XLA flags)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")


def small_cfg():
    from chanamq_tpu.models import ForecasterConfig

    return ForecasterConfig(seq_len=8, d_model=32, n_heads=4, d_ff=64, n_layers=2)


def test_forward_shape_and_dtype():
    from chanamq_tpu.models import forward, init_params, synthetic_batch

    cfg = small_cfg()
    rng = jax.random.PRNGKey(0)
    params = init_params(rng, cfg)
    x, y = synthetic_batch(rng, cfg, batch=4)
    out = jax.jit(lambda p, x: forward(p, x, cfg))(params, x)
    assert out.shape == (4, cfg.n_features)
    assert out.dtype == np.float32
    assert np.isfinite(np.asarray(out)).all()


def test_train_step_reduces_loss():
    from chanamq_tpu.models import init_params, make_train_step, synthetic_batch
    from chanamq_tpu.models.forecaster import init_momentum

    cfg = small_cfg()
    rng = jax.random.PRNGKey(1)
    params = init_params(rng, cfg)
    momentum = init_momentum(params)
    step = jax.jit(make_train_step(cfg, lr=1e-2))
    batch = synthetic_batch(rng, cfg, batch=16)
    first_loss = None
    for _ in range(30):
        params, momentum, loss = step(params, momentum, batch)
        if first_loss is None:
            first_loss = float(loss)
    assert float(loss) < first_loss * 0.5, (first_loss, float(loss))


def test_sharded_train_step_on_8_device_mesh():
    from chanamq_tpu.models import init_params, make_train_step, synthetic_batch
    from chanamq_tpu.models.forecaster import init_momentum
    from chanamq_tpu.parallel import make_mesh, make_sharded_train_step
    from chanamq_tpu.parallel.mesh import place

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    cfg = small_cfg()
    mesh = make_mesh(8)
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {"dp": 2, "tp": 4}
    rng = jax.random.PRNGKey(2)
    params = init_params(rng, cfg)
    momentum = init_momentum(params)
    batch = synthetic_batch(rng, cfg, batch=8)
    step = make_sharded_train_step(mesh, cfg, make_train_step(cfg))
    params, batch = place(mesh, params, batch)
    momentum, _ = place(mesh, momentum, batch)
    new_params, new_momentum, loss = step(params, momentum, batch)
    assert np.isfinite(float(loss))
    # params keep their shardings across steps (donation round-trips)
    qkv = new_params["layer0/attn/qkv"]
    assert not qkv.sharding.is_fully_replicated
    # sharded result must match single-device execution
    # (GSPMD-inserted collectives preserve the math)


def test_sharded_matches_single_device():
    from chanamq_tpu.models import forward, init_params, synthetic_batch
    from chanamq_tpu.parallel import make_mesh
    from chanamq_tpu.parallel.mesh import place

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    cfg = small_cfg()
    rng = jax.random.PRNGKey(3)
    params = init_params(rng, cfg)
    x, _ = synthetic_batch(rng, cfg, batch=8)
    single = jax.jit(lambda p, x: forward(p, x, cfg))(params, x)
    mesh = make_mesh(8)
    p_sharded, (x_sharded, _) = place(mesh, params, (x, x[:, 0]))
    sharded = jax.jit(lambda p, x: forward(p, x, cfg))(p_sharded, x_sharded)
    np.testing.assert_allclose(
        np.asarray(single), np.asarray(sharded), rtol=2e-2, atol=2e-2)
