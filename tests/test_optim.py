import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import adamw
from repro.optim.compression import (
    compress_decompress,
    compressed_psum,
    init_error_buffer,
)
from repro.launch.mesh import make_mesh


def _rosenbrock_ish(params):
    x, y = params["x"], params["y"]
    return jnp.sum((1 - x) ** 2) + 5 * jnp.sum((y - x**2) ** 2)


@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16", "int8"])
def test_adamw_converges(state_dtype):
    params = {"x": jnp.zeros((4, 8)), "y": jnp.zeros((4, 8))}
    state = adamw.init_adamw_state(params, state_dtype)
    loss0 = float(_rosenbrock_ish(params))
    for _ in range(300):
        grads = jax.grad(_rosenbrock_ish)(params)
        params, state = adamw.adamw_update(
            grads, state, params, 2e-2, weight_decay=0.0,
            state_dtype=state_dtype)
    loss1 = float(_rosenbrock_ish(params))
    assert loss1 < 0.05 * loss0, (state_dtype, loss0, loss1)


def test_int8_state_memory_is_int8():
    params = {"w": jnp.zeros((16, 256))}
    state = adamw.init_adamw_state(params, "int8")
    assert state.mu["w"].values.dtype == jnp.int8
    assert state.mu["w"].values.shape == (16, 256)
    assert state.mu["w"].scales.shape == (16, 1)


def test_clip_by_global_norm():
    tree = {"a": jnp.full((10,), 3.0)}
    clipped, norm = adamw.clip_by_global_norm(tree, 1.0)
    assert float(norm) == pytest.approx(np.sqrt(90), rel=1e-5)
    assert float(adamw.global_norm(clipped)) == pytest.approx(1.0, rel=1e-4)


def test_cosine_schedule_shape():
    lr = adamw.cosine_schedule(1e-3, warmup=10, total=100)
    assert float(lr(jnp.int32(0))) < 1e-3
    assert float(lr(jnp.int32(10))) == pytest.approx(1e-3, rel=0.1)
    assert float(lr(jnp.int32(99))) < 5e-4


def test_error_feedback_unbiased_over_time(key):
    """With error feedback, the accumulated compressed sum tracks the true
    sum (compression error does not accumulate)."""
    g = jax.random.normal(key, (8, 64))
    err = init_error_buffer({"g": g})
    total_true = np.zeros((8, 64))
    total_comp = np.zeros((8, 64))
    for i in range(50):
        gi = {"g": g * (1 + 0.1 * i)}
        comp, err = compress_decompress(gi, err)
        total_true += np.asarray(gi["g"])
        total_comp += np.asarray(comp["g"])
    rel = np.abs(total_comp - total_true).max() / np.abs(total_true).max()
    assert rel < 0.02, rel


def test_compressed_psum_single_device(key):
    mesh = make_mesh((1,), ("data",))
    x = jax.random.normal(key, (4, 32))

    def f(a):
        return compressed_psum(a, "data")

    y = jax.shard_map(f, mesh=mesh,
                      in_specs=jax.sharding.PartitionSpec(),
                      out_specs=jax.sharding.PartitionSpec(),
                      check_vma=False)(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=0.02)
