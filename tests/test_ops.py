import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cuda_mpi_gpu_cluster_programming_tpu.ops import conv2d, lrn, maxpool, relu

from oracle import conv2d_np, lrn_np, maxpool_np


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(485)


def test_conv2d_vs_oracle(rng):
    x = rng.standard_normal((9, 9, 3)).astype(np.float32)
    w = rng.standard_normal((3, 3, 3, 5)).astype(np.float32)
    b = rng.standard_normal(5).astype(np.float32)
    got = conv2d(jnp.asarray(x)[None], jnp.asarray(w), jnp.asarray(b), stride=2, padding=1)[0]
    want = conv2d_np(x, w, b, stride=2, padding=1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_conv2d_no_padding(rng):
    x = rng.standard_normal((11, 11, 2)).astype(np.float32)
    w = rng.standard_normal((5, 5, 2, 4)).astype(np.float32)
    b = np.zeros(4, np.float32)
    got = conv2d(jnp.asarray(x)[None], jnp.asarray(w), jnp.asarray(b), stride=4, padding=0)[0]
    want = conv2d_np(x, w, b, stride=4, padding=0)
    assert got.shape == (2, 2, 4)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_relu():
    x = jnp.array([[-1.0, 0.0, 2.5]])
    np.testing.assert_array_equal(relu(x), jnp.array([[0.0, 0.0, 2.5]]))


def test_maxpool_vs_oracle(rng):
    x = rng.standard_normal((7, 7, 4)).astype(np.float32)
    got = maxpool(jnp.asarray(x)[None], window=3, stride=2)[0]
    want = maxpool_np(x, window=3, stride=2)
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("alpha_over_size", [False, True])
def test_lrn_vs_oracle(rng, alpha_over_size):
    x = rng.standard_normal((4, 4, 8)).astype(np.float32)
    got = lrn(jnp.asarray(x)[None], size=5, alpha=1e-4, beta=0.75, k=2.0, alpha_over_size=alpha_over_size)[0]
    want = lrn_np(x, size=5, alpha=1e-4, beta=0.75, k=2.0, alpha_over_size=alpha_over_size)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_lrn_edge_truncation():
    # channel 0's window is [0..2] for size=5: denominator uses only 3 values
    x = np.ones((1, 1, 6), np.float32)
    got = np.asarray(
        lrn(jnp.asarray(x)[None], size=5, alpha=0.5, beta=1.0, k=1.0, alpha_over_size=True)[0]
    )
    want = lrn_np(x, size=5, alpha=0.5, beta=1.0, k=1.0, alpha_over_size=True)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[0, 0, 0] == pytest.approx(1.0 / (1.0 + 0.1 * 3))
    assert got[0, 0, 2] == pytest.approx(1.0 / (1.0 + 0.1 * 5))


def test_batch_axis(rng):
    x = rng.standard_normal((2, 9, 9, 3)).astype(np.float32)
    w = rng.standard_normal((3, 3, 3, 5)).astype(np.float32)
    b = rng.standard_normal(5).astype(np.float32)
    batched = conv2d(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b), stride=1, padding=1)
    for n in range(2):
        single = conv2d(jnp.asarray(x[n])[None], jnp.asarray(w), jnp.asarray(b), stride=1, padding=1)[0]
        np.testing.assert_allclose(batched[n], single, rtol=1e-6)


# --- the LRN's window sum as a band product (ops.reference.lrn) -------------


def _lrn_shifted(xp, x, *, size, alpha, beta, k, alpha_over_size=False):
    """The definition, in ``x``'s own type with ``xp`` = NumPy or jax.numpy:
    shifted adds of the zero-padded squares, so the window is truncated at
    the channel edges. The float64 reference of the tests below."""
    half, c = size // 2, x.shape[-1]
    p = xp.pad(x * x, [(0, 0)] * (x.ndim - 1) + [(half, half)])
    ssum = sum(p[..., d : d + c] for d in range(size))
    return x / (k + (alpha / size if alpha_over_size else alpha) * ssum) ** beta


def _activations(channels, dtype, rows=13):
    """Pool2-like activations up to 100 in magnitude, rounded to ``dtype``
    first: what is compared is the op's error, not the input's rounding."""
    x = np.clip(np.random.default_rng(30 + channels).standard_normal((8, rows, 13, channels)) * 30.0, -100.0, 100.0)
    return jnp.asarray(x, dtype)


@pytest.mark.parametrize("alpha_over_size", [False, True])
@pytest.mark.parametrize("size", [3, 5])
@pytest.mark.parametrize("channels", [7, 96, 256])
@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 2e-7), (jnp.bfloat16, 4e-3)], ids=["float32", "bf16"])
def test_lrn_band_product_vs_float64(dtype, limit, channels, size, alpha_over_size):
    """``max|out - ref| / max|ref|`` against the float64 definition on the
    same (already rounded) inputs. Readings on the CPU backend on these
    inputs at 256 channels, size 5: float32 1.2e-7 with the band product and
    with the ``reduce_window`` it replaced (1.0e-7 to 1.4e-7 over all the
    cases, either form); bf16 3.5e-3 with the band product, which accumulates
    in float32 (3.2e-3 to 3.5e-3 over the cases), against 8.3e-3 with the
    ``reduce_window``, which added the squares in bf16 (7.2e-3 to 8.4e-3;
    ISSUE 30 read 3.3e-3 against 7.3e-3 on its inputs)."""
    kw = dict(size=size, alpha=1e-4, beta=0.75, k=2.0, alpha_over_size=alpha_over_size)
    x = _activations(channels, dtype)
    got = lrn(x, **kw)
    assert got.dtype == x.dtype and got.shape == x.shape
    want = _lrn_shifted(np, np.asarray(x, np.float64), **kw)
    err = np.abs(np.asarray(got, np.float64) - want).max() / np.abs(want).max()
    assert err <= limit, err


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bf16"])
def test_lrn_pixel_does_not_depend_on_its_rows(dtype):
    """The row-sharded path hands a shard 4 of pool2's 13 rows (3 at the
    last): the product contracts over channels, so a pixel's result is
    bitwise the one it has in the whole image."""
    kw = dict(size=5, alpha=1e-4, beta=0.75, k=2.0)
    x = _activations(256, dtype)
    jitted = jax.jit(lambda v: lrn(v, **kw))
    whole = np.asarray(jitted(x), np.float32)
    for rows in (slice(0, 4), slice(4, 8), slice(12, 13)):
        part = np.asarray(jitted(x[:, rows]), np.float32)
        np.testing.assert_array_equal(part, whole[:, rows])


@pytest.mark.parametrize("alpha_over_size", [False, True])
@pytest.mark.parametrize("channels", [7, 96])
def test_lrn_gradient_vs_float64(channels, alpha_over_size):
    """Autodiff through the band product (the band is symmetric: the
    backward is the same product) against ``jax.grad`` of the shifted-adds
    form in float64."""
    kw = dict(size=5, alpha=1e-2, beta=0.75, k=2.0, alpha_over_size=alpha_over_size)
    rng = np.random.default_rng(31)
    x = rng.standard_normal((2, 3, 3, channels)) * 3.0
    w = rng.standard_normal(x.shape)

    with jax.enable_x64(True):
        want = np.asarray(jax.grad(lambda v: jnp.sum(_lrn_shifted(jnp, v, **kw) * w))(jnp.asarray(x, jnp.float64)))
    got = jax.grad(lambda v: jnp.sum(lrn(v, **kw) * jnp.asarray(w, jnp.float32)))(jnp.asarray(x, jnp.float32))
    assert np.abs(np.asarray(got, np.float64) - want).max() <= 1e-5 * np.abs(want).max()
