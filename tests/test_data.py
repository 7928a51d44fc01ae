"""Data-plane tests: containers (incl. batched sparse), stats, libsvm, index map."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data.containers import SparseFeatures, pack_csr_to_ell
from photon_ml_tpu.data.index_map import INTERCEPT_KEY, IndexMap, feature_key
from photon_ml_tpu.data.libsvm import read_libsvm, write_libsvm
from photon_ml_tpu.data.stats import summarize


def _random_sparse(rng, n=25, d=9, density=0.4):
    dense = rng.normal(size=(n, d)).astype(np.float32)
    dense *= rng.uniform(size=(n, d)) < density
    indptr = [0]
    idxs, vals = [], []
    for r in range(n):
        nz = np.nonzero(dense[r])[0]
        idxs.extend(nz)
        vals.extend(dense[r, nz])
        indptr.append(len(idxs))
    sp = pack_csr_to_ell(
        np.asarray(indptr), np.asarray(idxs), np.asarray(vals, np.float32), d
    )
    return dense, sp


def test_sparse_to_dense_batched(rng):
    """to_dense must be correct with leading batch dims (entity blocks)."""
    indices = jnp.asarray(
        [[[0, 1], [1, 2]], [[2, 0], [0, 1]]], jnp.int32
    )  # (2, 2, 2)
    values = jnp.ones((2, 2, 2), jnp.float32)
    sp = SparseFeatures(indices, values, 3)
    dense = sp.to_dense()
    assert dense.shape == (2, 2, 3)
    np.testing.assert_allclose(dense[0], [[1, 1, 0], [0, 1, 1]])
    np.testing.assert_allclose(dense[1], [[1, 0, 1], [1, 1, 0]])


def test_sparse_rmatvec_rejects_batched():
    sp = SparseFeatures(jnp.zeros((2, 3, 2), jnp.int32), jnp.ones((2, 3, 2)), 4)
    with pytest.raises(ValueError):
        sp.rmatvec(jnp.ones((2, 3)))
    with pytest.raises(ValueError):
        sp.sq_rmatvec(jnp.ones((2, 3)))


def test_sparse_matvec_batched_matches_vmap(rng):
    dense0, sp0 = _random_sparse(rng)
    dense1, sp1 = _random_sparse(rng)
    sp = SparseFeatures(
        jnp.stack([sp0.indices, sp1.indices]),
        jnp.stack([sp0.values, sp1.values]),
        sp0.dim,
    )
    w = jnp.asarray(rng.normal(size=sp0.dim).astype(np.float32))
    out = sp.matvec(w)
    np.testing.assert_allclose(out[0], dense0 @ np.asarray(w), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(out[1], dense1 @ np.asarray(w), rtol=1e-4, atol=1e-5)


def _padded_blocks(rng, ell_axis, k, blocks=3, n=17, d=11):
    """(blocks, n, k) ELL planes in the container's own form: unique ids
    within a row, the tail of each row padding (index 0, value 0.0), one
    row all padding where k allows a choice."""
    indices = np.zeros((blocks, n, k), np.int32)
    values = np.zeros((blocks, n, k), np.float32)
    for b in range(blocks):
        for r in range(n):
            filled = 0 if r == 0 else int(rng.integers(0, k + 1))
            indices[b, r, :filled] = rng.choice(d, size=filled, replace=False)
            values[b, r, :filled] = rng.normal(size=filled)
    if ell_axis == -2:
        indices, values = indices.swapaxes(-1, -2), values.swapaxes(-1, -2)
    return SparseFeatures(jnp.asarray(indices), jnp.asarray(values), d, ell_axis)


@pytest.mark.parametrize("batching", ["plain", "vmap"])
@pytest.mark.parametrize("k", [1, 6], ids=["k1", "k6"])
@pytest.mark.parametrize("ell_axis", [-1, -2], ids=["nk", "kn"])
@pytest.mark.parametrize("product", ["matvec", "rmatvec", "sq_rmatvec", "grad"])
def test_ell_products_a_plane_at_a_time_match_dense(rng, product, ell_axis, k, batching):
    """The plane loop against the densified matrix: the order of the sums
    differs, so the gap is float32 rounding, not zero."""
    sf = _padded_blocks(rng, ell_axis, k)
    blocks, n, d = sf.shape
    w = jnp.asarray(rng.normal(size=(blocks, d)).astype(np.float32))
    u = jnp.asarray(rng.normal(size=(blocks, n)).astype(np.float32))

    def loss(margins, c):
        return jnp.sum(jnp.tanh(margins) * c)

    sparse, dense, arg = {
        "matvec": (lambda s, w, u: s.matvec(w), lambda X, w, u: X @ w, w),
        "rmatvec": (lambda s, w, u: s.rmatvec(u), lambda X, w, u: u @ X, u),
        "sq_rmatvec": (lambda s, w, u: s.sq_rmatvec(u), lambda X, w, u: u @ jnp.square(X), u),
        "grad": (
            jax.grad(lambda s, w, u: loss(s.matvec(w), u), argnums=1),
            jax.grad(lambda X, w, u: loss(X @ w, u), argnums=1),
            w,
        ),
    }[product]
    X = sf.to_dense()
    if batching == "vmap":
        got, want = jax.vmap(sparse)(sf, w, u), jax.vmap(dense)(X, w, u)
    else:
        one = jax.tree.map(lambda a: a[0], sf)
        got, want = sparse(one, w[0], u[0]), dense(X[0], w[0], u[0])
    assert got.shape == want.shape and got.dtype == arg.dtype
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-6)


def test_summarize_dense_vs_numpy(rng):
    X = rng.normal(size=(50, 6)).astype(np.float32)
    X[:, 2] = 0.0
    s = summarize(jnp.asarray(X))
    np.testing.assert_allclose(s.mean, X.mean(0), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(s.variance, X.var(0, ddof=1), rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(s.max, X.max(0), rtol=1e-5)
    np.testing.assert_allclose(s.min, X.min(0), rtol=1e-5)
    np.testing.assert_allclose(s.num_nonzeros, (X != 0).sum(0))
    np.testing.assert_allclose(s.norm_l2, np.linalg.norm(X, axis=0), rtol=1e-4)


def test_summarize_sparse_matches_dense(rng):
    """Sparse summary (segment reductions, never densifies) == dense summary."""
    dense, sp = _random_sparse(rng, n=40, d=11)
    sd = summarize(jnp.asarray(dense))
    ss = summarize(sp)
    for field in ("mean", "variance", "num_nonzeros", "max", "min", "norm_l1", "norm_l2", "mean_abs"):
        np.testing.assert_allclose(
            getattr(ss, field), getattr(sd, field), rtol=1e-3, atol=1e-4, err_msg=field
        )


def test_summarize_sparse_all_positive_feature(rng):
    """A feature with entries in every row and no zeros must not see an
    implicit-zero min."""
    n, d = 8, 3
    indices = np.tile(np.arange(3, dtype=np.int32), (n, 1))
    values = rng.uniform(1.0, 2.0, size=(n, d)).astype(np.float32)
    sp = SparseFeatures(jnp.asarray(indices), jnp.asarray(values), d)
    s = summarize(sp)
    assert float(s.min[0]) >= 1.0  # not clamped to 0


def test_libsvm_round_trip(tmp_path, rng):
    path = str(tmp_path / "a.libsvm")
    with open(path, "w") as f:
        f.write("+1 1:0.5 3:2.0\n-1 2:1.5\n# comment line\n\n+1 1:-1.0\n")
    ds = read_libsvm(path)
    assert ds.num_rows == 3
    assert ds.dim == 4  # 3 features + intercept
    np.testing.assert_allclose(ds.labels, [1.0, 0.0, 1.0])
    X = ds.to_dense()
    np.testing.assert_allclose(X[:, -1], 1.0)  # intercept column
    np.testing.assert_allclose(X[0, :3], [0.5, 0.0, 2.0])

    out = str(tmp_path / "b.libsvm")
    write_libsvm(out, ds)
    ds2 = read_libsvm(out, add_intercept=False)
    np.testing.assert_allclose(ds2.to_dense(), X, rtol=1e-5)


def test_libsvm_no_intercept_regression_labels(tmp_path):
    path = str(tmp_path / "c.libsvm")
    with open(path, "w") as f:
        f.write("2.5 1:1.0\n-3.5 2:1.0\n")
    ds = read_libsvm(path, add_intercept=False)
    assert ds.dim == 2
    np.testing.assert_allclose(ds.labels, [2.5, -3.5])  # not 0/1-mapped


def test_index_map_basics():
    im = IndexMap.from_feature_names(["b", "a", "c", "a"], add_intercept=True)
    assert len(im) == 4
    assert im.get_index("a") == 0 and im.get_index("b") == 1  # sorted
    assert im.intercept_index == 3
    assert im.get_feature_name(im[INTERCEPT_KEY]) == INTERCEPT_KEY
    assert im.get_index("missing") == -1
    assert feature_key("age", "18-25") == "age\x0118-25"


def test_index_map_save_load(tmp_path):
    im = IndexMap.from_feature_names(["x", "y"], add_intercept=False)
    p = str(tmp_path / "m" / "map.json")
    im.save(p)
    im2 = IndexMap.load(p)
    assert dict(im2.items()) == dict(im.items())


def test_pack_csr_truncation(rng):
    indptr = np.asarray([0, 3])
    indices = np.asarray([0, 1, 2])
    values = np.asarray([0.1, 5.0, -3.0], np.float32)
    sp = pack_csr_to_ell(indptr, indices, values, 4, max_nnz=2)
    # Keeps the two largest |values|: 5.0 and -3.0.
    kept = set(np.asarray(sp.indices[0]).tolist())
    assert kept == {1, 2}
