"""Pallas kernels vs ref.py oracles: shape/dtype sweep in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container lacks hypothesis; deterministic shim
    from _hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.clip_reduce import clip_reduce, scale_contract
from repro.kernels.fused_clip import fused_norm_clip
from repro.kernels.ghost_norm import ghost_norm, ghost_norm_blocked
from repro.kernels.paged_attn import paged_attn

SHAPES = [
    (2, 8, 16, 24),
    (3, 300, 130, 70),
    (1, 513, 33, 1100),
    (4, 128, 128, 128),
]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_ghost_norm_kernel(shape, dtype):
    b, t, din, dout = shape
    key = jax.random.PRNGKey(hash(shape) & 0xFFFF)
    a = jax.random.normal(key, (b, t, din)).astype(dtype)
    g = (jax.random.normal(jax.random.fold_in(key, 1), (b, t, dout)) * 0.1
         ).astype(dtype)
    got = ghost_norm(a, g, bt=128, dk=128, interpret=True)
    want = ref.ghost_norm_ref(a, g)
    rtol = 4e-3 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(got, want, rtol=rtol)


# clip_reduce past SHAPES: din 200 (not a multiple of 128) with a head-like
# dout of 128·13, and din 384 (128·3) with dout 130: no derived or explicit
# tile divides them, so the last blocks are ragged; T = 40 and 100 are no
# multiple of the explicit row tile, nor T = 100 of the derived one (the
# zero-row pad).
CLIP_SHAPES = SHAPES + [(2, 40, 200, 1664), (3, 100, 384, 130)]


@pytest.mark.parametrize("shape", CLIP_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_clip_reduce_kernel(shape, dtype):
    """Explicit 128 tiles and the derived ones, against the f32 oracle;
    factors span 0 to 1, with the first example's at 0."""
    b, t, din, dout = shape
    key = jax.random.PRNGKey(hash(shape) & 0xFFF)
    a = jax.random.normal(key, (b, t, din)).astype(dtype)
    g = (jax.random.normal(jax.random.fold_in(key, 1), (b, t, dout)) * 0.1
         ).astype(dtype)
    f = jax.random.uniform(jax.random.fold_in(key, 2), (b,))
    if b > 1:
        f = f.at[0].set(0.0).at[-1].set(1.0)
    want = ref.clip_reduce_ref(a, g, f)
    rtol = 4e-3 if dtype == jnp.bfloat16 else 1e-4
    for tiles in (dict(bi=128, bj=128, bt=128), {}):
        got = clip_reduce(a, g, f, **tiles, interpret=True)
        np.testing.assert_allclose(got, want, rtol=rtol, atol=1e-4,
                                   err_msg=str(tiles))


@pytest.mark.parametrize("bt", [None, 32])
def test_clip_reduce_sensitivity_bound(bt):
    """With c_b = C / ‖A_bᵀG_b‖, one example's clipped sum from bf16
    operands has Frobenius norm at most C·(1 + 1e-5): the factor stays an
    unquantized f32 that scales the f32 partial sums."""
    b, t, din, dout, clip = 4, 96, 200, 300, 0.7
    key = jax.random.PRNGKey(5)
    a = jax.random.normal(key, (b, t, din)).astype(jnp.bfloat16)
    g = jax.random.normal(jax.random.fold_in(key, 1), (b, t, dout)
                          ).astype(jnp.bfloat16)
    norms = jnp.sqrt(ref.ghost_norm_ref(a, g))
    c = clip / norms
    for i in range(b):
        got = clip_reduce(a[i:i + 1], g[i:i + 1], c[i:i + 1], bt=bt,
                          interpret=True)
        assert float(jnp.linalg.norm(got)) <= clip * (1 + 1e-5), i


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.integers(1, 80), st.integers(1, 50),
       st.integers(1, 50))
def test_ghost_norm_property(b, t, din, dout):
    key = jax.random.PRNGKey(b * 997 + t)
    a = jax.random.normal(key, (b, t, din))
    g = jax.random.normal(jax.random.fold_in(key, 1), (b, t, dout))
    got = ghost_norm(a, g, bt=32, dk=32, interpret=True)
    want = ref.ghost_norm_ref(a, g)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-5)
    assert bool(jnp.all(got >= -1e-5))  # norms² are nonnegative


def test_kernel_block_shape_sweep():
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (2, 200, 96))
    g = jax.random.normal(jax.random.fold_in(key, 1), (2, 200, 64))
    want = ref.ghost_norm_ref(a, g)
    for bt in (32, 64, 256):
        for dk in (32, 128):
            got = ghost_norm(a, g, bt=bt, dk=dk, interpret=True)
            np.testing.assert_allclose(got, want, rtol=2e-4)


# ---------------------------------------------------------------------------
# Blocked ghost-norm kernel (per-shard clipping hot path).
# ---------------------------------------------------------------------------

BLOCKED_CASES = [
    # (B, T, din, dout, M, axis) — T not a multiple of bt, narrow blocks
    (2, 8, 16, 24, 4, "out"),
    (3, 70, 48, 40, 4, "out"),
    (3, 70, 48, 40, 6, "in"),
    (1, 130, 36, 128, 2, "out"),
]


@pytest.mark.parametrize("case", BLOCKED_CASES)
def test_ghost_norm_blocked_kernel(case):
    b, t, din, dout, m, axis = case
    # crc32, not hash(): case contains strings and str hashes are salted
    # per process — a CI failure must be reproducible locally
    import zlib
    key = jax.random.PRNGKey(zlib.crc32(repr(case).encode()) & 0xFFFF)
    a = jax.random.normal(key, (b, t, din))
    g = jax.random.normal(jax.random.fold_in(key, 1), (b, t, dout)) * 0.1
    got = ghost_norm_blocked(a, g, m, block_axis=axis, bt=32, dk=32,
                             interpret=True)
    want = ref.ghost_norm_blocked_ref(a, g, m, block_axis=axis)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    # per-block norms² must sum to the full-layer norm²
    np.testing.assert_allclose(jnp.sum(got, -1), ref.ghost_norm_ref(a, g),
                               rtol=1e-4)


def test_ghost_norm_blocked_bad_args():
    a = jnp.zeros((2, 8, 6))
    g = jnp.zeros((2, 8, 10))
    with pytest.raises(ValueError):
        ghost_norm_blocked(a, g, 3, block_axis="out")  # 10 % 3 != 0
    with pytest.raises(ValueError):
        ghost_norm_blocked(a, g, 2, block_axis="diag")


# ---------------------------------------------------------------------------
# Fused norm+clip kernel (one HBM pass over A, G).
# ---------------------------------------------------------------------------

FUSED_CASES = [
    (2, 8, 16, 24),
    (3, 70, 48, 40),    # ragged: T % bt != 0, din < dk
    (1, 130, 36, 140),  # dout > one 128 lane tile
]


@pytest.mark.parametrize("case", FUSED_CASES)
@pytest.mark.parametrize("with_extra", [False, True])
def test_fused_norm_clip_kernel(case, with_extra):
    b, t, din, dout = case
    key = jax.random.PRNGKey(hash(case) & 0xFFF)
    a = jax.random.normal(key, (b, t, din))
    g = jax.random.normal(jax.random.fold_in(key, 1), (b, t, dout)) * 0.1
    extra = (jax.random.uniform(jax.random.fold_in(key, 2), (b,))
             if with_extra else None)
    # exercise the whole threshold encoding: clip, pass-through, direct scale
    c = jnp.array(([0.5, jnp.inf, -0.7, 0.01] * b)[:b])
    got_n, got_dw = fused_norm_clip(a, g, c, extra, bt=32, interpret=True)
    want_n, want_dw = ref.fused_norm_clip_ref(a, g, c, extra)
    np.testing.assert_allclose(got_n, want_n, rtol=1e-4)
    np.testing.assert_allclose(got_dw, want_dw, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Compiled by default: interpret mode only when a caller asks for it.
# ---------------------------------------------------------------------------

_A, _G = jnp.ones((2, 8, 16)), jnp.ones((2, 8, 16))
_F = jnp.ones((2,))
DEFAULT_MODE_CALLS = {
    "ghost_norm": lambda: ghost_norm(_A, _G),
    "ghost_norm_blocked": lambda: ghost_norm_blocked(_A, _G, 2),
    "fused_norm_clip": lambda: fused_norm_clip(_A, _G, _F),
    "clip_reduce": lambda: clip_reduce(_A, _G, _F),
    "scale_contract": lambda: scale_contract(_A, _G, _F),
    "paged_attn": lambda: paged_attn(
        jnp.ones((2, 1, 1, 16)), jnp.ones((3, 8, 1, 16)),
        jnp.ones((3, 8, 1, 16)), jnp.zeros((2, 1), jnp.int32),
        jnp.zeros((2,), jnp.int32), scale=0.25),
}


@pytest.mark.parametrize("name", sorted(DEFAULT_MODE_CALLS))
def test_kernel_without_interpret_flag_compiles(name):
    """Called without `interpret=`, a kernel goes to the Mosaic compiler,
    which the CPU backend refuses: a fallback to the interpreter would
    hide the device from a run that believes it is on one."""
    if jax.default_backend() == "tpu":
        pytest.skip("the compiled path runs on a TPU")
    with pytest.raises(ValueError, match="interpret mode"):
        DEFAULT_MODE_CALLS[name]()
