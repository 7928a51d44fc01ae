"""Fused Pallas GLM kernels vs the XLA objective path.

Runs the real kernel bodies in interpreter mode on the CPU backend (the
same stand-in strategy the conftest uses for the device mesh), asserting
numerical agreement with ops.objective's XLA expressions — which are
themselves tested against finite differences in test_objective.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data.containers import LabeledData
from photon_ml_tpu.ops import objective, pallas_glm
from photon_ml_tpu.ops.losses import LOGISTIC, POISSON, SMOOTHED_HINGE, SQUARED
from photon_ml_tpu.ops.normalization import NormalizationContext

LOSSES = [LOGISTIC, SQUARED, POISSON, SMOOTHED_HINGE]


def _problem(rng, n, d, poisson_scale=False, x_dtype=jnp.float32):
    X = rng.normal(size=(n, d)).astype(np.float32)
    if poisson_scale:
        X *= 0.1
    if d > 1000:  # rows of unit norm, as the wide dense cell's (lr-epsilon)
        X /= np.sqrt(d)
    y = (rng.uniform(size=n) > 0.5).astype(np.float32)
    offsets = rng.normal(size=n).astype(np.float32) * 0.1
    weights = rng.uniform(0.5, 2.0, size=n).astype(np.float32)
    w = (rng.normal(size=d) * 0.1).astype(np.float32)
    return (
        jnp.asarray(X).astype(x_dtype),
        jnp.asarray(y),
        jnp.asarray(offsets),
        jnp.asarray(weights),
        jnp.asarray(w),
    )


# (rows, features, X's storage). The row tile is 1,024 at d = 64 and 512 at
# d = 2,000 (`_tile_for`); 1,100 rows are no multiple of 128 and leave a
# ragged last tile at either width, 1,024 fit exactly. Interpret mode pads a
# boundary block with NaN in every operand, so each ragged case has NaNs
# planted beyond row n (test_the_row_mask_is_what_keeps_the_padding_out
# shows they are there).
SHAPES = [
    pytest.param(1024, 64, jnp.float32, id="1024"),
    pytest.param(1100, 64, jnp.float32, id="1100"),
    pytest.param(1100, 64, jnp.bfloat16, id="1100-bf16"),
    pytest.param(1100, 2000, jnp.float32, id="1100x2000"),
    pytest.param(1100, 2000, jnp.bfloat16, id="1100x2000-bf16"),
]


@pytest.mark.parametrize("loss", LOSSES, ids=lambda l: l.name)
@pytest.mark.parametrize("n,d,x_dtype", SHAPES)
def test_value_gradient_sums_match_xla(rng, loss, n, d, x_dtype):
    X, y, off, wt, w = _problem(rng, n, d, poisson_scale=loss is POISSON, x_dtype=x_dtype)
    data = LabeledData(features=X, labels=y, offsets=off, weights=wt)

    val_ref, g_ref = objective.value_and_gradient(loss, w, data)
    shift = jnp.zeros(())
    val, g, sum_u = pallas_glm.value_gradient_sums(
        loss, w, shift, X, y, off, wt, interpret=True
    )
    np.testing.assert_allclose(float(val), float(val_ref), rtol=2e-5)
    # Scale-relative bound: hilo's 2-pass decomposition carries ~2^-16
    # representation error of the LARGEST magnitudes, so tiny elements of a
    # mixed-magnitude gradient can miss a per-element rtol while the result
    # is accurate to ~1e-5 of the vector's scale.
    g_scale = float(np.max(np.abs(np.asarray(g_ref)))) + 1e-6
    assert float(np.max(np.abs(np.asarray(g) - np.asarray(g_ref)))) < 3e-5 * g_scale
    u = wt * loss.d1(X.astype(jnp.float32) @ w + off, y)
    np.testing.assert_allclose(float(sum_u), float(jnp.sum(u)), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("loss", [LOGISTIC, SQUARED, POISSON], ids=lambda l: l.name)
@pytest.mark.parametrize("n,d,x_dtype", SHAPES[1:])
def test_hessian_vector_sums_match_xla(rng, loss, n, d, x_dtype):
    X, y, off, wt, w = _problem(rng, n, d, poisson_scale=loss is POISSON, x_dtype=x_dtype)
    v = jnp.asarray((rng.normal(size=d)).astype(np.float32))
    data = LabeledData(features=X, labels=y, offsets=off, weights=wt)

    hv_ref = objective.hessian_vector(loss, w, v, data)
    hv, sum_r = pallas_glm.hessian_vector_sums(
        loss, w, jnp.zeros(()), v, jnp.zeros(()), X, y, off, wt, interpret=True
    )
    hv_scale = float(np.max(np.abs(np.asarray(hv_ref)))) + 1e-6
    assert float(np.max(np.abs(np.asarray(hv) - np.asarray(hv_ref)))) < 3e-5 * hv_scale
    Xf = X.astype(jnp.float32)
    r = wt * loss.d2(Xf @ w + off, y) * (Xf @ v)
    np.testing.assert_allclose(float(sum_r), float(jnp.sum(r)), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("kernel", ["value_gradient", "hessian_vector"])
@pytest.mark.parametrize("n,d,x_dtype", SHAPES)
def test_a_matrix_that_lies_column_major_gives_the_same_sums_bit_for_bit(rng, kernel, n, d, x_dtype):
    """The kernels read X as it lies: told `column_major` they take
    (d, tile) blocks of X^T — the same tile of rows, the same two
    contractions with X's axes exchanged — and return the sums of the
    (tile, d) read bit for bit, ragged last tile and its NaN padding too."""
    from jax.experimental.layout import Format, Layout

    X, y, off, wt, w = _problem(rng, n, d, x_dtype=x_dtype)
    columns = jax.device_put(X, Format(Layout((1, 0)), X.sharding))
    assert pallas_glm.lies_row_major(X) and not pallas_glm.lies_row_major(columns)
    zero = jnp.zeros(())

    def call(features, column_major):
        if kernel == "value_gradient":
            return pallas_glm.value_gradient_sums(
                LOGISTIC, w, zero, features, y, off, wt, interpret=True, column_major=column_major
            )
        return pallas_glm.hessian_vector_sums(
            LOGISTIC, w, zero, w, zero, features, y, off, wt, interpret=True, column_major=column_major
        )

    as_rows = call(X, False)
    assert all(np.isfinite(np.asarray(a)).all() for a in as_rows)
    for features in (columns, X):  # the flag says how to read, never what
        for a, b in zip(call(features, True), as_rows):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kernel", ["value_gradient", "hessian_vector"])
def test_the_row_mask_is_what_keeps_the_padding_out(rng, monkeypatch, kernel):
    """The control of the ragged cases above: with the masks taken out, the
    same calls return NaN — the padding beyond row n is NaN in interpret
    mode, and a zero in `u` does not silence a NaN row of X in a matmul."""
    X, y, off, wt, w = _problem(rng, 1100, 2000, x_dtype=jnp.bfloat16)
    zero = jnp.zeros(())

    def call():
        if kernel == "value_gradient":
            return pallas_glm.value_gradient_sums(LOGISTIC, w, zero, X, y, off, wt, interpret=True)[1]
        return pallas_glm.hessian_vector_sums(LOGISTIC, w, zero, w, zero, X, y, off, wt, interpret=True)[0]

    def retrace():  # the jitted wrappers hold the kernels they traced
        pallas_glm.value_gradient_sums.clear_cache()
        pallas_glm.hessian_vector_sums.clear_cache()

    assert np.isfinite(np.asarray(call())).all()
    retrace()
    # Rows masked, X not: the per-row operands are zero beyond n, X is NaN.
    row_mask = pallas_glm._row_mask
    monkeypatch.setattr(
        pallas_glm, "_row_mask",
        lambda n, tile, axis: row_mask(n, tile, axis) | (axis == 0),
    )
    try:
        assert np.isnan(np.asarray(call())).all()
    finally:
        retrace()


def test_objective_dispatch_with_normalization(rng, monkeypatch):
    """The objective-layer dispatch must apply the shift/factor algebra to the
    kernel's raw sums identically to the XLA branch."""
    n, d = 2048, 128  # above the should_use size floor
    X, y, off, wt, w = _problem(rng, n, d)
    data = LabeledData(features=X, labels=y, offsets=off, weights=wt)
    norm = NormalizationContext(
        factors=jnp.asarray(rng.uniform(0.5, 2.0, size=d).astype(np.float32)),
        shifts=jnp.asarray((rng.normal(size=d) * 0.1).astype(np.float32)),
    )

    val_ref, g_ref = objective.value_and_gradient(LOGISTIC, w, data, norm, l2=0.3)
    v = jnp.asarray(rng.normal(size=d).astype(np.float32))
    hv_ref = objective.hessian_vector(LOGISTIC, w, v, data, norm, l2=0.3)

    monkeypatch.setattr(pallas_glm, "FORCE_INTERPRET", True)
    assert pallas_glm.should_use(data.features, w)
    val, g = objective.value_and_gradient(LOGISTIC, w, data, norm, l2=0.3)
    hv = objective.hessian_vector(LOGISTIC, w, v, data, norm, l2=0.3)

    np.testing.assert_allclose(float(val), float(val_ref), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(hv), np.asarray(hv_ref), rtol=2e-4, atol=2e-4)


def test_should_use_policy(rng):
    big = jnp.zeros((4096, 256), jnp.float32)
    w_big = jnp.zeros((256,), jnp.float32)
    small = jnp.zeros((128, 16), jnp.float32)
    w_small = jnp.zeros((16,), jnp.float32)
    wide = jnp.zeros((4096, 32768), jnp.float32)
    w_wide = jnp.zeros((32768,), jnp.float32)

    # CPU backend without the test hook: always off.
    assert not pallas_glm.should_use(big, w_big)
    try:
        pallas_glm.FORCE_INTERPRET = True
        assert pallas_glm.should_use(big, w_big)
        # Small (vmapped per-entity) problems and very wide ones stay on XLA.
        assert not pallas_glm.should_use(small, w_small)
        assert not pallas_glm.should_use(wide, w_wide)
        # Sparse containers are not dense arrays.
        from photon_ml_tpu.data.containers import SparseFeatures

        sf = SparseFeatures(
            indices=jnp.zeros((4096, 8), jnp.int32),
            values=jnp.zeros((4096, 8), jnp.float32),
            dim=256,
        )
        assert not pallas_glm.should_use(sf, w_big)
    finally:
        pallas_glm.FORCE_INTERPRET = False


def test_health_probe_gates_dispatch(rng, monkeypatch):
    """A kernel the backend refuses must stop the job with the compiler's
    message — never a quiet switch to the XLA objective."""
    big = jnp.zeros((4096, 256), jnp.float32)
    w = jnp.zeros((256,), jnp.float32)
    monkeypatch.setattr(pallas_glm, "FORCE_INTERPRET", True)

    # Healthy: probe passes and is cached.
    monkeypatch.setattr(pallas_glm, "_HEALTHY", False)
    assert pallas_glm.should_use(big, w)
    assert pallas_glm._HEALTHY is True

    # Crashing kernel: raises, carrying the message, and stays unhealthy.
    monkeypatch.setattr(pallas_glm, "_HEALTHY", False)
    def boom(*a, **k):
        raise RuntimeError("mosaic says no")
    monkeypatch.setattr(pallas_glm, "value_gradient_sums", boom)
    with pytest.raises(RuntimeError, match="mosaic says no"):
        pallas_glm.should_use(big, w)
    assert pallas_glm._HEALTHY is False

    # The one explicit way round: the kill switch, which never probes.
    monkeypatch.setattr(pallas_glm, "_ENABLED", False)
    assert not pallas_glm.should_use(big, w)


def test_health_probe_checks_numerics(rng, monkeypatch):
    big = jnp.zeros((4096, 256), jnp.float32)
    w = jnp.zeros((256,), jnp.float32)
    monkeypatch.setattr(pallas_glm, "FORCE_INTERPRET", True)
    monkeypatch.setattr(pallas_glm, "_HEALTHY", False)

    real = pallas_glm.value_gradient_sums
    def wrong(*a, **k):
        val, g, su = real(*a, **k)
        return val + 100.0, g, su  # silently wrong value
    monkeypatch.setattr(pallas_glm, "value_gradient_sums", wrong)
    with pytest.raises(RuntimeError, match="disagree.*value"):
        pallas_glm.should_use(big, w)

@pytest.mark.parametrize("fault", ["refused", "wrong"])
def test_health_probe_covers_the_hessian_vector_form_a_tron_fit_calls(monkeypatch, fault):
    """ISSUE 40: a TRON solve on a bf16-stored matrix that lies column-major
    calls `hessian_vector_sums` in that form, which the probe ran only
    row-major in float32: a Mosaic refusal (or a wrong sum) of that form has
    to stop the job at the dispatch decision, not in the first TRON fit."""
    big = jnp.zeros((4096, 256), jnp.float32)
    w = jnp.zeros((256,), jnp.float32)
    monkeypatch.setattr(pallas_glm, "FORCE_INTERPRET", True)
    monkeypatch.setattr(pallas_glm, "_HEALTHY", False)

    real, forms = pallas_glm.hessian_vector_sums, []

    def probed(loss, w_eff, shift, v_eff, v_shift, features, *rest, **kw):
        form = (jnp.dtype(features.dtype).name, bool(kw.get("column_major")))
        forms.append(form)
        if form == ("bfloat16", True):
            if fault == "refused":
                raise RuntimeError("mosaic refuses bf16 X^T blocks")
            hv, sum_r = real(loss, w_eff, shift, v_eff, v_shift, features, *rest, **kw)
            return hv * 3.0, sum_r
        return real(loss, w_eff, shift, v_eff, v_shift, features, *rest, **kw)

    monkeypatch.setattr(pallas_glm, "hessian_vector_sums", probed)
    match = "mosaic refuses" if fault == "refused" else "disagree.*hessian_vector_bf16_column_major"
    with pytest.raises(RuntimeError, match=match):
        pallas_glm.should_use(big, w)
    assert ("float32", False) in forms and ("bfloat16", True) in forms
    assert pallas_glm._HEALTHY is False
