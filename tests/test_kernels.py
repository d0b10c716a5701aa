"""Pallas kernel validation: interpret-mode sweeps vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import given, settings, st  # hypothesis, optional (see conftest)

from repro.core.qconfig import Granularity, QuantSpec
from repro.core.quantizer import fake_quant_nograd
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(2)


@pytest.mark.parametrize("shape", [(8, 128), (200, 300), (1024, 64),
                                   (7, 513), (256, 4096)])
@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_qdq_row_sweep(shape, bits, dtype):
    x = (jax.random.normal(KEY, shape) * 5).astype(dtype)
    got = ops.fused_fake_quant(x, QuantSpec(bits, Granularity.PER_TOKEN))
    want = ref.qdq_row_ref(x, bits)
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
    else:
        # bf16: (a) jit-fused vs eager rounding can resolve ties one grid
        # step apart (both valid quantizations); (b) the bf16 OUTPUT adds a
        # representation error of ~|v|*2^-8.  Assert one-LSB agreement under
        # that combined tolerance, with large agreement in the half-LSB band.
        qmax = 2 ** (bits - 1) - 1
        xf = np.asarray(x, np.float32)
        scale = np.abs(xf).max(-1, keepdims=True) / qmax
        tol = 1.05 * scale + np.abs(w) * 2.0 ** -7 + 1e-6
        err = np.abs(g - w)
        assert (err <= tol).all(), float((err - tol).max())
        assert (err > 0.51 * scale + np.abs(w) * 2.0 ** -7
                ).mean() < 0.01


@pytest.mark.parametrize("gran", [Granularity.PER_CHANNEL,
                                  Granularity.PER_TENSOR])
@pytest.mark.parametrize("bits", [4, 8])
def test_qdq_scaled_matches_core(gran, bits):
    x = jax.random.normal(KEY, (96, 257)) * 2
    spec = QuantSpec(bits, gran)
    got = ops.fused_fake_quant(x, spec)
    want = fake_quant_nograd(x, spec)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 512, 128),
                                   (100, 300, 257), (64, 64, 64),
                                   (130, 257, 90)])
def test_int8_matmul_sweep(m, k, n):
    kx, kw = jax.random.split(KEY)
    x = jax.random.normal(kx, (m, k), jnp.float32) * 2
    w = jax.random.normal(kw, (k, n), jnp.float32)
    got = ops.int8_quantized_matmul(x, w, out_dtype=jnp.float32)

    # Quantizer-contract bound (cross-implementation equality is flaky on
    # round ties): |got - x@w| <= rs_i*cs_j*(0.5*sum|wq| + 0.5*sum|xq| + K/4)
    xf, wf = np.asarray(x, np.float64), np.asarray(w, np.float64)
    rs = np.maximum(np.abs(xf).max(1, keepdims=True), 1e-12) / 127
    cs = np.maximum(np.abs(wf).max(0, keepdims=True), 1e-12) / 127
    bound = (0.5 * rs * cs * (np.abs(wf / cs).sum(0, keepdims=True)
                              + np.abs(xf / rs).sum(1, keepdims=True))
             + rs * cs * k * 0.25) * 1.05 + 1e-5
    err = np.abs(np.asarray(got, np.float64) - xf @ wf)
    assert (err <= bound).all(), float((err - bound).max())
    # and the int core is exact (test_int8_matmul_ref_consistency); here
    # additionally require decent fidelity vs fp
    rel = err.max() / np.abs(xf @ wf).max()
    assert rel < 0.05, rel


def test_int8_matmul_ref_consistency():
    """kernel(int payloads) == ref.int8_matmul_ref exactly."""
    from repro.kernels.int8_matmul import int8_matmul
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(-128, 128, (128, 256)), jnp.int8)
    w = jnp.asarray(rng.randint(-128, 128, (256, 128)), jnp.int8)
    rs = jnp.asarray(rng.rand(128, 1).astype(np.float32))
    cs = jnp.asarray(rng.rand(1, 128).astype(np.float32))
    got = int8_matmul(x, w, rs, cs, out_dtype=jnp.float32, interpret=True)
    want = ref.int8_matmul_ref(x, w, rs, cs, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_int8_matmul_batched_input():
    x = jax.random.normal(KEY, (2, 10, 64))
    w = jax.random.normal(KEY, (64, 32))
    got = ops.int8_quantized_matmul(x, w, out_dtype=jnp.float32)
    assert got.shape == (2, 10, 32)
    rel = float(jnp.max(jnp.abs(got - x @ w)) / jnp.max(jnp.abs(x @ w)))
    assert rel < 0.05, rel


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (130, 257, 90),
                                   (64, 300, 100)])
def test_int8_matmul_nt_matches_ref(m, k, n):
    """dx-path kernel (fused g-quant prologue) vs the pure-jnp oracle."""
    from repro.kernels.int8_matmul import int8_matmul_nt
    rng = np.random.RandomState(3)
    g = jnp.asarray(rng.randn(m, n).astype(np.float32))
    wq = jnp.asarray(rng.randint(-128, 128, (k, n)), jnp.int8)
    fold = jnp.asarray(rng.rand(1, n).astype(np.float32) + 0.01)
    qs = jnp.maximum(jnp.max(jnp.abs(g) * fold, axis=1, keepdims=True),
                     1e-12) / 127.0
    pad_r, pad_c = (-m) % 128, (-n) % 128
    pk = (-k) % 128
    gp = jnp.pad(g, ((0, pad_r), (0, pad_c)))
    got = int8_matmul_nt(gp, jnp.pad(wq, ((0, pk), (0, pad_c))),
                         jnp.pad(fold, ((0, 0), (0, pad_c))),
                         jnp.pad(qs, ((0, pad_r), (0, 0))),
                         out_dtype=jnp.float32, interpret=True)[:m, :k]
    want = ref.int8_matmul_nt_ref(g, wq, fold, qs, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (130, 257, 90),
                                   (100, 64, 300)])
def test_int8_matmul_tn_matches_ref(m, k, n):
    """dW-path kernel (fused g-quant prologue) vs the pure-jnp oracle."""
    from repro.kernels.int8_matmul import int8_matmul_tn
    rng = np.random.RandomState(4)
    xq = jnp.asarray(rng.randint(-128, 128, (m, k)), jnp.int8)
    g = jnp.asarray(rng.randn(m, n).astype(np.float32))
    fold = jnp.asarray(rng.rand(m, 1).astype(np.float32) + 0.01)
    qs = jnp.maximum(jnp.max(jnp.abs(g) * fold, axis=0, keepdims=True),
                     1e-12) / 127.0
    pm, pk, pn = (-m) % 128, (-k) % 128, (-n) % 128
    got = int8_matmul_tn(jnp.pad(xq, ((0, pm), (0, pk))),
                         jnp.pad(g, ((0, pm), (0, pn))),
                         jnp.pad(fold, ((0, pm), (0, 0))),
                         jnp.pad(qs, ((0, 0), (0, pn))),
                         out_dtype=jnp.float32, interpret=True)[:k, :n]
    want = ref.int8_matmul_tn_ref(xq, g, fold, qs, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert np.isfinite(np.asarray(got)).all()


def _layout_args(layout, m, k, n, rng):
    """Padded operands of one int8 layout on the (m, k, n) of its kernel's
    docstring, with the zero padding (and zero scales) of ops.py."""
    pm, pk, pn = (-m) % 128, (-k) % 128, (-n) % 128
    ints = lambda r, c: jnp.asarray(rng.randint(-128, 128, (r, c)), jnp.int8)
    pos = lambda r, c: jnp.asarray(rng.rand(r, c).astype(np.float32) + 0.01)
    pad = lambda a, r, c: jnp.pad(a, ((0, r), (0, c)))
    if layout == "fwd":
        return (pad(ints(m, k), pm, pk), pad(ints(k, n), pk, pn),
                pad(pos(m, 1), pm, 0), pad(pos(1, n), 0, pn))
    g = jnp.asarray(rng.randn(m, n).astype(np.float32), jnp.bfloat16)
    gf = g.astype(jnp.float32)
    if layout == "nt":
        fold = pos(1, n)
        qs = jnp.max(jnp.abs(gf) * fold, axis=1, keepdims=True) / 127.0
        return (pad(g, pm, pn), pad(ints(k, n), pk, pn), pad(fold, 0, pn),
                pad(qs, pm, 0))
    fold = pos(m, 1)
    qs = jnp.max(jnp.abs(gf) * fold, axis=0, keepdims=True) / 127.0
    return (pad(ints(m, k), pm, pk), pad(g, pm, pn), pad(fold, pm, 0),
            pad(qs, 0, pn))


@pytest.mark.parametrize("layout", ["fwd", "nt", "tn"])
@pytest.mark.parametrize("m,k,n", [(300, 200, 1376), (130, 257, 90)])
def test_picked_tiles_bit_identical_to_128_grid(layout, m, k, n):
    """Re-tiling moves no number: int32 accumulation is exact and the quant
    prologue and dequant epilogue are elementwise, so the picked tiles give
    the 128^3 grid's output bit for bit (ragged shapes, padding lanes with
    zero scales included)."""
    from repro.kernels import int8_matmul as mm
    fn = {"fwd": mm.int8_matmul, "nt": mm.int8_matmul_nt,
          "tn": mm.int8_matmul_tn}[layout]
    args = _layout_args(layout, m, k, n, np.random.RandomState(6))
    picked = fn(*args, out_dtype=jnp.float32, interpret=True)
    grid128 = fn(*args, out_dtype=jnp.float32, bm=128, bn=128, bk=128,
                 interpret=True)
    mp, kp, np_ = (-(-d // 128) * 128 for d in (m, k, n))
    t = mm.pick_tiles(layout, mp, kp, np_, jnp.float32,
                      None if layout == "fwd" else jnp.bfloat16)
    assert (t.bm, t.bn, t.bk) != (128, 128, 128)
    assert np.isfinite(np.asarray(picked)).all()
    np.testing.assert_array_equal(np.asarray(picked), np.asarray(grid128))


@pytest.mark.parametrize("layout", ["fwd", "nt", "tn"])
@pytest.mark.parametrize("m,k,n", [
    (16384, 768, 2304), (16384, 768, 768), (16384, 768, 3072),
    (16384, 3072, 768), (16384, 4096, 4096), (16384, 4096, 512),
    (16384, 4096, 11008), (16384, 11008, 4096), (128, 768, 2304),
    (384, 256, 1408), (64, 128, 128)])
def test_pick_tiles_divide_and_fit(layout, m, k, n):
    """The picked tiles are multiples of 128 (or a whole dimension), divide
    the padded dimensions, hold every double-buffered block and the int32
    accumulator within the VMEM bytes reported, and stay in the budget;
    M <= 128 (a serving batch) takes the whole of M."""
    from repro.kernels import int8_matmul as mm
    out = jnp.bfloat16
    g = None if layout == "fwd" else jnp.bfloat16
    t = mm.pick_tiles(layout, m, k, n, out, g)
    for b, d in ((t.bm, m), (t.bn, n), (t.bk, k)):
        assert b == d or b % 128 == 0, (b, d)
        assert d % b == 0, (b, d)
    if m <= 128:
        assert t.bm == m
    g_b = 2 if g is not None else 0
    ins = {"fwd": t.bm * t.bk + t.bk * t.bn,
           "nt": g_b * t.bm * t.bn + t.bk * t.bn,
           "tn": t.bm * t.bk + g_b * t.bm * t.bn}[layout]
    out_elems = {"fwd": t.bm * t.bn, "nt": t.bm * t.bk,
                 "tn": t.bk * t.bn}[layout]
    assert 2 * (ins + 2 * out_elems) + 4 * out_elems <= t.vmem_bytes
    assert t.vmem_bytes == mm.vmem_bytes(layout, t.bm, t.bn, t.bk, 2, g_b)
    assert t.vmem_bytes <= mm.VMEM_BUDGET
    params = t.compiler_params()
    assert params.vmem_limit_bytes == (
        t.vmem_bytes if t.vmem_bytes > mm.DEFAULT_SCOPED_VMEM else None)


def test_zero_scale_padding_guard():
    """0-scale rows/cols (zero-padding of ragged shapes) must not emit
    NaN/Inf from the quant prologue's division or the dequant epilogue."""
    from repro.kernels.int8_matmul import int8_matmul, int8_matmul_nt
    rng = np.random.RandomState(5)
    # forward epilogue: one all-zero scale row / col
    x = jnp.asarray(rng.randint(-128, 128, (128, 128)), jnp.int8)
    w = jnp.asarray(rng.randint(-128, 128, (128, 128)), jnp.int8)
    rs = jnp.asarray(rng.rand(128, 1).astype(np.float32)).at[7, 0].set(0.0)
    cs = jnp.asarray(rng.rand(1, 128).astype(np.float32)).at[0, 9].set(0.0)
    out = int8_matmul(x, w, rs, cs, out_dtype=jnp.float32, interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    # nt prologue: a 0 q_scale row divides g/0 without the guard
    g = jnp.asarray(rng.randn(128, 128).astype(np.float32))
    fold = jnp.asarray(rng.rand(1, 128).astype(np.float32))
    qs = jnp.maximum(jnp.max(jnp.abs(g) * fold, 1, keepdims=True),
                     1e-12) / 127.0
    qs = qs.at[3, 0].set(0.0)
    out = int8_matmul_nt(g, w, fold, qs, out_dtype=jnp.float32,
                         interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    # the dx/dW ops wrappers pad ragged shapes with exactly such 0 scales
    dx = ops.int8_bwd_dx(g[:100, :90], w[:60, :90],
                         jnp.abs(fold[:, :90]) + 0.01)
    dw = ops.int8_bwd_dw(x[:100, :60], jnp.asarray(rng.rand(100, 1),
                                                   jnp.float32),
                         g[:100, :90])
    assert np.isfinite(np.asarray(dx, np.float32)).all()
    assert np.isfinite(np.asarray(dw)).all()
    assert dx.shape == (100, 60) and dw.shape == (60, 90)


def test_fused_fake_quant_routing(monkeypatch):
    """REPRO_FUSED_FQ=1 routes eligible training-path qdq calls through the
    fused Pallas kernel; the reference stays the oracle."""
    from repro.core.qconfig import QuantRecipe, RoundMode
    from repro.core.qlinear import _train_fake_quant, quantized_linear
    x = jax.random.normal(KEY, (96, 257)) * 2
    spec = QuantSpec(8, Granularity.PER_CHANNEL)
    monkeypatch.setenv("REPRO_FUSED_FQ", "1")
    got = _train_fake_quant(x, spec)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(fake_quant_nograd(x, spec)),
                               rtol=1e-5, atol=1e-5)
    assert ops.fused_fake_quant_eligible(spec, x)
    # ineligible specs keep the reference: stochastic (needs a key stream)...
    sr = QuantSpec(8, Granularity.PER_TOKEN, round_mode=RoundMode.STOCHASTIC)
    assert not ops.fused_fake_quant_eligible(sr, x)
    # ...asymmetric, blockwise, and 1-D inputs
    assert not ops.fused_fake_quant_eligible(
        QuantSpec(8, Granularity.PER_TOKEN, symmetric=False), x)
    assert not ops.fused_fake_quant_eligible(
        QuantSpec(8, Granularity.PER_TOKEN, block_size=64), x)
    assert not ops.fused_fake_quant_eligible(
        QuantSpec(8, Granularity.PER_TOKEN), x[0])
    # end-to-end: routed fwd+bwd of the fake-quant linear matches unrouted
    r = QuantRecipe(weights=QuantSpec(8, Granularity.PER_CHANNEL),
                    acts=QuantSpec(8, Granularity.PER_TOKEN),
                    grads=QuantSpec(8, Granularity.PER_TOKEN))
    w = jax.random.normal(KEY, (257, 64)) * 0.2

    def loss(xx, ww):
        return jnp.sum(quantized_linear(xx, ww, r) ** 2)

    dx_f, dw_f = jax.grad(loss, argnums=(0, 1))(x, w)
    monkeypatch.setenv("REPRO_FUSED_FQ", "0")
    dx_r, dw_r = jax.grad(loss, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(dx_f), np.asarray(dx_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dw_f), np.asarray(dw_r),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 300), st.integers(1, 300), st.integers(2, 8))
def test_property_qdq_row_any_shape(rows, cols, bits):
    rng = np.random.RandomState(rows * 301 + cols)
    x = jnp.asarray(rng.randn(rows, cols).astype(np.float32))
    got = ops.fused_fake_quant(x, QuantSpec(bits, Granularity.PER_TOKEN))
    want = ref.qdq_row_ref(x, bits)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
