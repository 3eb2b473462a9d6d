"""Optimizer, schedule, clipping, and gradient-compression tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import (
    adafactor,
    adamw,
    clip_by_global_norm,
    constant_lr,
    dequantize_int8,
    global_norm,
    quantize_int8,
    warmup_cosine,
)


def _quad_params():
    return {"w": jnp.asarray([3.0, -2.0, 1.5]), "b": jnp.asarray([[1.0, -1.0], [0.5, 2.0]])}


def _quad_loss(p):
    return jnp.sum(p["w"] ** 2) + jnp.sum(p["b"] ** 2)


@pytest.mark.parametrize("opt_factory", [adamw, adafactor])
def test_optimizer_converges_on_quadratic(opt_factory):
    opt_init, opt_update = opt_factory(weight_decay=0.0)
    params = _quad_params()
    state = opt_init(params)
    for _ in range(200):
        grads = jax.grad(_quad_loss)(params)
        updates, state = opt_update(grads, state, params, jnp.float32(0.05))
        params = jax.tree.map(lambda p, u: p + u, params, updates)
    assert float(_quad_loss(params)) < 0.05


def test_adamw_matches_reference_math():
    """One AdamW step against the textbook update."""
    opt_init, opt_update = adamw(b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0)
    p = {"w": jnp.asarray([1.0, 2.0])}
    g = {"w": jnp.asarray([0.5, -1.0])}
    state = opt_init(p)
    upd, state = opt_update(g, state, p, jnp.float32(0.1))
    m = 0.1 * np.asarray([0.5, -1.0])
    v = 0.001 * np.asarray([0.25, 1.0])
    mhat = m / (1 - 0.9)
    vhat = v / (1 - 0.999)
    want = -0.1 * mhat / (np.sqrt(vhat) + 1e-8)
    np.testing.assert_allclose(np.asarray(upd["w"]), want, rtol=1e-5)


def test_adafactor_memory_is_factored():
    opt_init, _ = adafactor()
    p = {"w": jnp.zeros((256, 512))}
    state = opt_init(p)
    assert state.vr["w"].shape == (256,)
    assert state.vc["w"].shape == (512,)


def test_clip_by_global_norm():
    g = {"a": jnp.full((10,), 3.0), "b": jnp.full((10,), 4.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(float(norm), np.sqrt(10 * 9 + 10 * 16), rtol=1e-5)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-4)
    # under the limit -> unchanged
    g2 = {"a": jnp.asarray([0.1])}
    c2, _ = clip_by_global_norm(g2, 1.0)
    np.testing.assert_allclose(np.asarray(c2["a"]), [0.1], rtol=1e-6)


def test_warmup_cosine_shape():
    fn = warmup_cosine(1.0, 10, 100, final_frac=0.1)
    assert float(fn(0)) == 0.0
    assert float(fn(10)) == pytest.approx(1.0, rel=1e-3)
    assert float(fn(5)) == pytest.approx(0.5, rel=1e-3)
    assert float(fn(100)) == pytest.approx(0.1, rel=1e-2)
    assert float(fn(55)) < float(fn(20))


def test_int8_quantization_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(1000) * 5)
    q, scale = quantize_int8(x)
    back = dequantize_int8(q, scale)
    err = float(jnp.max(jnp.abs(back - x)))
    assert err <= float(scale) / 2 + 1e-6  # half-ulp of the int8 grid
    assert q.dtype == jnp.int8


def test_compressed_psum_matches_plain_within_tolerance():
    """shard_map over 4 host-split... emulated with vmap+axis: use pmap-style
    via shard_map on the default 1-device mesh is degenerate; test the
    numerics of the compression path with axis size 1 (exactness) and the
    quantizer error bound for the general case (above)."""
    from jax.sharding import AxisType, PartitionSpec as P

    from repro.optim import compressed_psum

    mesh = jax.make_mesh((1,), ("d",), axis_types=(AxisType.Auto,))

    def f(g):
        return compressed_psum({"g": g}, ("d",))["g"]

    g = jnp.asarray(np.random.default_rng(1).standard_normal((8, 8)), jnp.float32)
    out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("d"),), out_specs=P("d"),
                            check_vma=False))(g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g), atol=np.abs(g).max() / 127 + 1e-6)
