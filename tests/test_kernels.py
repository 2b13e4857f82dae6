"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch, ops, ref
from repro.models import conv_backbone
from repro.kernels.flash_attention import flash_attention as fa_raw


@pytest.mark.parametrize("causal,window,cap", [
    (True, None, None), (False, None, None), (True, 24, None),
    (True, None, 50.0), (True, 24, 30.0),
])
def test_flash_attention_smoke(key, causal, window, cap):
    """Fast tier-1 reference check: one small shape per masking/capping
    variant (the full shape sweep is the `slow` test below)."""
    s, d, dtype = 64, 32, jnp.float32
    q = jax.random.normal(key, (2, s, d), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, s, d), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, s, d), dtype)
    o = fa_raw(q, k, v, causal=causal, window=window, softcap=cap,
               block_q=32, block_k=32, interpret=True)
    r = ref.attention_ref(q, k, v, causal=causal, window=window, softcap=cap)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=2e-5, rtol=2e-5)


@pytest.mark.slow
@pytest.mark.parametrize("s,d,dtype", [
    (128, 64, jnp.float32), (192, 64, jnp.float32), (256, 128, jnp.float32),
    (128, 64, jnp.bfloat16), (100, 32, jnp.float32),
])
@pytest.mark.parametrize("causal,window,cap", [
    (True, None, None), (False, None, None), (True, 48, None),
    (True, None, 50.0), (True, 32, 30.0),
])
def test_flash_attention_sweep(key, s, d, dtype, causal, window, cap):
    q = jax.random.normal(key, (2, s, d), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, s, d), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, s, d), dtype)
    o = fa_raw(q, k, v, causal=causal, window=window, softcap=cap,
               block_q=64, block_k=64, interpret=True)
    r = ref.attention_ref(q, k, v, causal=causal, window=window, softcap=cap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("b,f,c", [(64, 32, 5), (130, 64, 10), (16, 16, 3)])
def test_mahalanobis_sweep(key, b, f, c):
    q = jax.random.normal(key, (b, f))
    mu = jax.random.normal(jax.random.fold_in(key, 1), (c, f))
    a = jax.random.normal(jax.random.fold_in(key, 2), (c, f, f))
    sinv = jnp.einsum("cij,ckj->cik", a, a) + 0.1 * jnp.eye(f)
    got = ops.mahalanobis(q, mu, sinv)
    want = ref.mahalanobis_ref(q, mu, sinv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("b,f,c", [(100, 48, 7), (257, 64, 4), (8, 8, 2)])
def test_segment_pool_sweep(key, b, f, c):
    x = jax.random.normal(key, (b, f))
    y = jax.random.randint(jax.random.fold_in(key, 1), (b,), 0, c)
    s1, c1 = ops.segment_pool(x, y, c)
    s2, c2 = ref.segment_pool_ref(x, y, c)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


def _bf16_exact(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


@pytest.mark.parametrize("b,h,w,cin,cout", [
    (3, 16, 12, 3, 64), (130, 8, 6, 3, 32), (2, 6, 10, 1, 8)])
def test_conv3x3_relu_pool_sweep(key, b, h, w, cin, cout):
    """The fused image block against the XLA composite (conv3x3 SAME +
    bias, ReLU, 2x2 max-pool), operands pre-rounded to bfloat16 so the
    kernel's rounding of them is exact; the bias has negative entries,
    and 130 images span two 128-lane blocks."""
    x = _bf16_exact(jax.random.normal(key, (b, h, w, cin)))
    wt = _bf16_exact(0.3 * jax.random.normal(jax.random.fold_in(key, 1),
                                             (3, 3, cin, cout)))
    bias = jax.random.normal(jax.random.fold_in(key, 2), (cout,))
    assert float(jnp.min(bias)) < 0
    got = ops.conv3x3_relu_pool(x, wt, bias)
    want = conv_backbone._conv_block(x, wt, bias, None)
    assert got.shape == want.shape == (b, h // 2, w // 2, cout)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", [
    "b3_16x12_cin3", "b130_8x6_cin3", "b40_6x10_cin1", "constant",
    "negative_bias"])
def test_conv3x3_relu_pool_bwd_sweep(key, case):
    """The fused block's weight and bias gradients against ``jax.vjp`` of
    the XLA composite, on bfloat16-exact operands and cotangent.  Shapes:
    130 images span two lane blocks, 40 fill part of one.  ``constant``:
    a constant image under a centre-only kernel, so all four phases of
    every window tie, and only routing to the first one puts the border
    windows' other taps on the padding as the composite does.
    ``negative_bias``: every phase is <= 0, so both gradients are 0."""
    shapes = {"b3_16x12_cin3": (3, 16, 12, 3, 64),
              "b130_8x6_cin3": (130, 8, 6, 3, 32),
              "b40_6x10_cin1": (40, 6, 10, 1, 8)}
    b, h, w, cin, cout = shapes.get(case, (5, 8, 6, 3, 16))
    ks = jax.random.split(key, 4)
    x = _bf16_exact(jax.random.normal(ks[0], (b, h, w, cin)))
    wt = _bf16_exact(0.3 * jax.random.normal(ks[1], (3, 3, cin, cout)))
    bias = _bf16_exact(jax.random.normal(ks[2], (cout,)))
    g = _bf16_exact(jax.random.normal(ks[3], (b, h // 2, w // 2, cout)))
    if case == "constant":
        x = jnp.full_like(x, 0.5)
        wt = jnp.zeros_like(wt).at[1, 1].set(wt[1, 1])
    if case == "negative_bias":
        bias = jnp.full_like(bias, -100.0)
    _, vjp = jax.vjp(lambda w_, b_: conv_backbone._conv_block(x, w_, b_, None),
                     wt, bias)
    want_dw, want_db = vjp(g)
    dw, db = ops.conv3x3_relu_pool_bwd(x, wt, bias, g)
    assert dw.shape == wt.shape and db.shape == bias.shape
    if case == "negative_bias":
        assert not np.any(np.asarray(dw)) and not np.any(np.asarray(db))
    else:
        assert np.any(np.asarray(want_dw))
    np.testing.assert_allclose(np.asarray(dw), np.asarray(want_dw),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(db), np.asarray(want_db),
                               rtol=1e-5, atol=1e-5)


def test_image_block_input_gradient_is_the_composites(monkeypatch, key):
    """An image that carries a tangent takes the XLA composite and its
    VJP, so every gradient, the input's too, is the composite's; without
    one, the weights' and bias' gradient is the backward kernel's."""
    monkeypatch.setattr(dispatch, "pallas_native", lambda: True)
    x = jax.random.normal(key, (32, 8, 8, 3))
    wt = 0.3 * jax.random.normal(jax.random.fold_in(key, 1), (3, 3, 3, 8))
    bias = jax.random.normal(jax.random.fold_in(key, 2), (8,))

    def loss(block, *args):
        return jnp.sum(jnp.sin(block(*args)))

    composite = lambda x, w, b: conv_backbone._conv_block(x, w, b, None)
    got = jax.grad(loss, argnums=(1, 2, 3))(conv_backbone._image_block,
                                            x, wt, bias)
    want = jax.grad(loss, argnums=(1, 2, 3))(composite, x, wt, bias)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    fused = lambda *args: loss(conv_backbone._image_block, *args)
    jaxpr = str(jax.make_jaxpr(jax.grad(fused, argnums=(1, 2)))(x, wt, bias))
    assert "conv3x3_relu_pool_bwd" in jaxpr


@pytest.mark.parametrize("case,fused", [
    ("image", True), ("odd_h", False), ("odd_w", False), ("film", False),
    ("cin_5", False), ("batch_8", False), ("batch_16", False),
    ("batch_32", True), ("batch_1000", True)])
def test_conv3x3_relu_pool_engages_on_image_blocks_only(monkeypatch, case,
                                                         fused):
    """With the platform policy forced to TPU the backbone fuses a
    FiLM-free block of an image (<= 4 channels) with even H and W and a
    batch of at least a quarter of the kernel's 128 lanes (serving's adapt
    chunk of 32), and only that block; every other block, and a smaller
    batch (serving's query chunk of 8), keeps the composite."""
    monkeypatch.setattr(dispatch, "pallas_native", lambda: True)
    cin = 5 if case == "cin_5" else 3
    cfg = conv_backbone.ConvBackboneConfig(in_channels=cin, widths=(8, 8),
                                           feature_dim=4)
    params = conv_backbone.init_conv_backbone(jax.random.key(0), cfg)
    film = ([dict(gamma=jnp.zeros(8), beta=jnp.zeros(8))] * 2
            if case == "film" else None)
    batch = int(case[6:]) if case.startswith("batch_") else 64
    x = jnp.zeros((batch, 7 if case == "odd_h" else 8,
                   9 if case == "odd_w" else 8, cin))
    jaxpr = str(jax.make_jaxpr(
        lambda x: conv_backbone.conv_features(params, x, film, cfg))(x))
    assert jaxpr.count("pallas_call") == (1 if fused else 0)


@pytest.mark.parametrize("e,c,d,f,dtype", [
    (4, 64, 96, 80, jnp.float32), (2, 130, 64, 64, jnp.float32),
    (3, 32, 48, 40, jnp.bfloat16),
])
def test_gmm_sweep(key, e, c, d, f, dtype):
    x = jax.random.normal(key, (e, c, d), dtype)
    w = jax.random.normal(jax.random.fold_in(key, 1), (e, d, f), dtype)
    got = ops.gmm(x, w)
    want = ref.gmm_ref(x, w)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("g,q,p,n", [(6, 32, 16, 8), (2, 64, 32, 16)])
def test_ssd_chunk_sweep(key, g, q, p, n):
    x = jax.random.normal(key, (g, q, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (g, q)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (g,)))
    B = jax.random.normal(jax.random.fold_in(key, 3), (g, q, n))
    C = jax.random.normal(jax.random.fold_in(key, 4), (g, q, n))
    y, st, cd, sd = ops.ssd_chunk(x, dt, A, B, C)
    for i in range(g):
        yr, sr, cdr, sdr = ref.ssd_chunk_ref(
            x[i][:, None, :], dt[i][:, None], A[i:i + 1],
            B[i][:, None, :], C[i][:, None, :])
        np.testing.assert_allclose(np.asarray(y[i]), np.asarray(yr[:, 0]),
                                   atol=3e-4, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(st[i]), np.asarray(sr[0]),
                                   atol=3e-4, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(cd[i]), np.asarray(cdr[0]),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(sd[i]), np.asarray(sdr[:, 0]),
                                   atol=1e-5)


def test_ssd_kernel_composes_with_model(key):
    """Kernel-computed chunks + jnp inter-chunk recurrence == model SSD."""
    from repro.models.mamba2 import ssd_chunked
    b, s, h, p, n, chunk = 2, 64, 3, 8, 4, 16
    x = jax.random.normal(key, (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (b, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (h,)))
    B = jax.random.normal(jax.random.fold_in(key, 3), (b, s, h, n))
    C = jax.random.normal(jax.random.fold_in(key, 4), (b, s, h, n))
    y_model, final_model = ssd_chunked(x, dt, A, B, C, chunk)

    nc = s // chunk

    # flatten (b, nc, h) into G for the kernel
    def to_g(t, feat):
        t = t.reshape(b, nc, chunk, h, feat)
        return t.transpose(0, 1, 3, 2, 4).reshape(b * nc * h, chunk, feat)

    xg = to_g(x, p)
    Bg = to_g(B, n)
    Cg = to_g(C, n)
    dtg = dt.reshape(b, nc, chunk, h).transpose(0, 1, 3, 2).reshape(-1, chunk)
    Ag = jnp.tile(A, b * nc)
    yk, stk, cdk, sdk = ops.ssd_chunk(xg, dtg, Ag, Bg, Cg)

    # inter-chunk recurrence in jnp
    stk = stk.reshape(b, nc, h, p, n)
    cdk = cdk.reshape(b, nc, h)
    sdk = sdk.reshape(b, nc, h, chunk).transpose(0, 1, 3, 2)  # (b,nc,chunk,h)
    yk = yk.reshape(b, nc, h, chunk, p).transpose(0, 1, 3, 2, 4)

    state = jnp.zeros((b, h, p, n))
    ys = []
    for ci in range(nc):
        y_off = jnp.einsum("blhn,bhpn,blh->blhp",
                           C.reshape(b, nc, chunk, h, n)[:, ci], state,
                           sdk[:, ci])
        ys.append(yk[:, ci] + y_off)
        state = state * cdk[:, ci][:, :, None, None] + stk[:, ci]
    y_full = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_model),
                               atol=2e-3, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(state), np.asarray(final_model),
                               atol=2e-3, rtol=1e-2)
