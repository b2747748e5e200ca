"""The CNN's 2×2 max-pool (``core.tasks.max_pool_2x2``) against
``jax.lax.reduce_window``: equal forward values and gradients, tie rule
included, and a training gradient with no select-and-scatter in it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tasks import _masked_ce, cnn_task, max_pool_2x2


def reduce_window_pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


def pool_input(shape, seed, ties):
    """ReLU'd normals (so some windows are all zeros), and with ``ties`` a
    window whose four elements share a positive maximum and one whose first
    and last corners do."""
    x = np.maximum(np.random.default_rng(seed).normal(size=shape), 0)
    x = x.astype(np.float32)
    if ties:
        x[0, 0:2, 0:2, 0] = 2.5
        x[-1, 2, 2, -1] = x[-1, 3, 3, -1] = 3.0
        x[-1, 3, 2, -1] = -1.0
    return jnp.asarray(x)


def pool_and_grad(pool, x, g):
    y, vjp = jax.vjp(pool, x)
    return y, vjp(g)[0]


@pytest.mark.parametrize("ties", [False, True], ids=["relu", "tie"])
@pytest.mark.parametrize("vmapped", [False, True], ids=["plain", "vmap"])
@pytest.mark.parametrize("shape", [(3, 28, 28, 16), (3, 14, 14, 32),
                                   (3, 5, 5, 32), (3, 7, 9, 4)],
                         ids=lambda s: "x".join(map(str, s)))
def test_max_pool_matches_reduce_window(shape, vmapped, ties):
    x = pool_input(shape, 0, ties)
    g = jax.random.normal(jax.random.PRNGKey(1),
                          jax.eval_shape(reduce_window_pool, x).shape)
    if vmapped:                      # two workers, the second untied
        x = jnp.stack([x, pool_input(shape, 1, False)])
        g = jnp.stack([g, -g])
    wrap = jax.vmap if vmapped else (lambda f: f)
    y, dx = pool_and_grad(wrap(max_pool_2x2), x, g)
    y_ref, dx_ref = pool_and_grad(wrap(reduce_window_pool), x, g)
    np.testing.assert_array_equal(y, y_ref)
    np.testing.assert_array_equal(dx, dx_ref)


@pytest.mark.parametrize("shape", [(3, 28, 28, 16), (3, 5, 5, 32)],
                         ids=lambda s: "x".join(map(str, s)))
def test_relu_after_pool_matches_relu_before(shape):
    """``cnn_task`` pools before its ReLU: equal values and gradients,
    windows whose maximum is zero or negative included."""
    x = np.random.default_rng(2).normal(size=shape).astype(np.float32)
    x[0, 0:2, 0:2, 0] = [[-1.0, 0.0], [0.0, -2.0]]
    x[0, 2:4, 2:4, 0] = -0.5
    x = jnp.asarray(x)
    g = jax.random.normal(jax.random.PRNGKey(4),
                          jax.eval_shape(reduce_window_pool, x).shape)
    y, dx = pool_and_grad(lambda v: jax.nn.relu(max_pool_2x2(v)), x, g)
    y_ref, dx_ref = pool_and_grad(
        lambda v: reduce_window_pool(jax.nn.relu(v)), x, g)
    np.testing.assert_array_equal(y, y_ref)
    np.testing.assert_array_equal(dx, dx_ref)


def test_tie_goes_to_first_corner():
    x = pool_input((1, 4, 4, 1), 0, ties=True)
    _, dx = pool_and_grad(max_pool_2x2, x, jnp.ones((1, 2, 2, 1)))
    assert dx[0, 0, 0, 0] == 1 and float(dx[0, :2, :2, 0].sum()) == 1
    assert dx[0, 2, 2, 0] == 1 and dx[0, 3, 3, 0] == 0


def test_cnn_gradient_has_no_select_and_scatter():
    task = cnn_task(28, 1, 10)
    params = task.init(jax.random.PRNGKey(0))
    x = jnp.zeros((4, 784))
    y = jnp.zeros((4,), jnp.int32)
    jaxpr = str(jax.make_jaxpr(jax.grad(task.loss))(params, x, y,
                                                     jnp.ones(4)))
    assert "select_and_scatter_add" not in jaxpr
    assert "max_pool_2x2" in jaxpr


def old_cnn_loss(image_hw, channels):
    """``cnn_task``'s loss as it was written with ``reduce_window``."""
    def apply(p, x):
        x = x.reshape(x.shape[0], image_hw, image_hw, channels)
        x = jax.lax.conv_general_dilated(
            x, p["c1"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = reduce_window_pool(jax.nn.relu(x))
        x = jax.lax.conv_general_dilated(
            x, p["c2"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = reduce_window_pool(jax.nn.relu(x))
        return x.reshape(x.shape[0], -1) @ p["w"] + p["b"]

    return lambda p, x, y, m: _masked_ce(apply(p, x), y, m)


@pytest.mark.parametrize("image_hw,width", [(10, 8), (28, 16)])
def test_cnn_loss_and_grad_match_reduce_window_cnn(image_hw, width):
    task = cnn_task(image_hw, 1, 10, width=width)
    params = task.init(jax.random.PRNGKey(2))
    kx, ky = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(kx, (8, image_hw * image_hw))
    y = jax.random.randint(ky, (8,), 0, 10)
    m = jnp.ones(8).at[-1].set(0.0)
    loss, grad = jax.value_and_grad(task.loss)(params, x, y, m)
    loss_ref, grad_ref = jax.value_and_grad(old_cnn_loss(image_hw, 1))(
        params, x, y, m)
    np.testing.assert_array_equal(loss, loss_ref)
    jax.tree.map(np.testing.assert_array_equal, grad, grad_ref)
