"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU):
shapes × dtypes × masking variants, per the assignment's kernel contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention_fwd
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.proxy_blocks.ops import mxu_block, stream_block
from repro.kernels.proxy_blocks.ref import mxu_ref, stream_ref
from repro.kernels.ssd.ops import ssd_diag_block
from repro.kernels.ssd.ref import ssd_diag_ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,h,g,d,win,causal", [
    (1, 256, 4, 2, 64, None, True),
    (2, 256, 2, 2, 128, 128, True),
    (1, 384, 4, 1, 64, None, True),
    (1, 512, 2, 1, 64, None, False),
])
def test_flash_kernel_sweep(b, s, h, g, d, win, causal, dtype, rng):
    q = jnp.asarray(rng.normal(0, 1, (b, s, h, d)), dtype)
    k = jnp.asarray(rng.normal(0, 1, (b, s, g, d)), dtype)
    v = jnp.asarray(rng.normal(0, 1, (b, s, g, d)), dtype)
    out = flash_attention_fwd(q, k, v, causal=causal, window=win,
                              interpret=True)
    r = h // g
    qq = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kk = jnp.repeat(k, r, axis=2).transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vv = jnp.repeat(v, r, axis=2).transpose(0, 2, 1, 3).reshape(b * h, s, d)
    ref = attention_ref(qq, kk, vv, causal=causal, window=win)
    ref = ref.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


@pytest.mark.parametrize("b,c,q,g,r,p,n", [
    (1, 2, 32, 1, 4, 16, 16),
    (2, 2, 16, 2, 8, 8, 32),
    (1, 1, 64, 1, 12, 16, 16),   # r > slab width: exercises head slabbing
])
def test_ssd_kernel_sweep(b, c, q, g, r, p, n, rng):
    h = g * r
    x = jnp.asarray(rng.normal(0, 1, (b, c, q, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.1, (b, c, q, h)), jnp.float32)
    adt = -jnp.asarray(rng.uniform(0.01, 0.5, (b, c, q, h)), jnp.float32)
    cum = jnp.cumsum(adt, axis=2)
    bm = jnp.asarray(rng.normal(0, 1, (b, c, q, g, n)), jnp.float32)
    cm = jnp.asarray(rng.normal(0, 1, (b, c, q, g, n)), jnp.float32)
    out = ssd_diag_block(x, dt, cum, bm, cm, r, interpret=True)
    ref = ssd_diag_ref(x.reshape(b, c, q, g, r, p), dt.reshape(b, c, q, g, r),
                       cum.reshape(b, c, q, g, r), bm, cm)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.reshape(b, c, q, h, p)),
                               atol=2e-4)


def test_ssd_chunked_vs_sequential_recurrence(rng):
    """Chunked SSD (dual form) == literal state-space recurrence."""
    from repro.models.ssm import ssd_chunked
    b, l, h, p, n, g = 1, 64, 4, 16, 16, 1
    x = jnp.asarray(rng.normal(0, 1, (b, l, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.1, (b, l, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.1, 1.0, (h,)), jnp.float32)
    bm = jnp.asarray(rng.normal(0, 1, (b, l, g, n)), jnp.float32)
    cm = jnp.asarray(rng.normal(0, 1, (b, l, g, n)), jnp.float32)
    y = np.asarray(ssd_chunked(x, dt, a, bm, cm, chunk=16))
    state = np.zeros((b, h, p, n))
    for i in range(l):
        da = np.exp(np.asarray(dt[:, i]) * np.asarray(a))
        state = state * da[..., None, None] + \
            (np.asarray(dt[:, i])[..., None] * np.asarray(x[:, i]))[..., None] \
            * np.asarray(bm[:, i])[:, :, None, :]
        np.testing.assert_allclose(
            y[:, i], np.einsum("bhpn,bhn->bhp", state,
                               np.asarray(cm[:, i])), atol=1e-3)


def test_ssd_prefill_state_matches_decode(rng):
    """Prefill's returned SSM state == state after step-by-step decode."""
    from repro.models.ssm import ssd_chunked
    b, l, h, p, n = 1, 32, 2, 8, 8
    x = jnp.asarray(rng.normal(0, 1, (b, l, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.1, (b, l, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.1, 1.0, (h,)), jnp.float32)
    bm = jnp.asarray(rng.normal(0, 1, (b, l, 1, n)), jnp.float32)
    cm = jnp.asarray(rng.normal(0, 1, (b, l, 1, n)), jnp.float32)
    _, final = ssd_chunked(x, dt, a, bm, cm, chunk=8, return_final=True)
    state = np.zeros((b, h, p, n))
    for i in range(l):
        da = np.exp(np.asarray(dt[:, i]) * np.asarray(a))
        state = state * da[..., None, None] + \
            (np.asarray(dt[:, i])[..., None] * np.asarray(x[:, i]))[..., None] \
            * np.asarray(bm[:, i])[:, :, None, :]
    np.testing.assert_allclose(np.asarray(final), state, atol=1e-4)


@pytest.mark.parametrize("reps", [1, 7, 32])
def test_mxu_block_kernel(reps, rng):
    a = jnp.asarray(rng.uniform(-1, 1, (128, 128)), jnp.bfloat16)
    b = jnp.asarray(rng.uniform(-1, 1, (128, 128)) / 128, jnp.bfloat16)
    out = mxu_block(a, b, reps, interpret=True)
    ref = mxu_ref(a, b, reps)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=2e-2)


@pytest.mark.parametrize("n,reps", [(2048, 3), (4096, 17)])
def test_stream_block_kernel(n, reps, rng):
    v = jnp.asarray(rng.uniform(0, 1, (n,)), jnp.float32)
    out = stream_block(v, reps, interpret=True)
    ref = stream_ref(v, reps)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)
