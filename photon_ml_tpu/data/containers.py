"""Device-resident columnar data containers.

TPU-native counterpart of the reference's row-oriented `LabeledPoint`
(photon-lib data/LabeledPoint.scala:32) and per-entity `LocalDataset`
(photon-api data/LocalDataset.scala:35). Instead of JVM objects holding Breeze
vectors, a batch of N labeled points is a struct-of-arrays: a dense or padded
sparse design matrix plus (labels, offsets, weights) vectors. Padding rows are
expressed with weight 0, which makes every weighted reduction mask-correct for
free — the idiom the whole framework uses to map ragged data onto static
shapes.

Sparse features use an ELL-style padded layout `(indices, values)` of shape
(N, K): K = max nonzeros per row, padding entries point at index 0 with value
0.0. Margins and gradients loop over the K planes: a plane is one gather of N
coefficients added into the margins, or one scatter-add of N entries into the
gradient, so the coefficient vector and the gradient stay in VMEM and nothing
of N * K elements is made. The padding invariant is what the products rest
on: they promise XLA that every index is in [0, dim), and a padding slot
contributes `w[0] * 0.0` to a margin and `0.0` to feature 0's gradient. For
dense shards the design matrix feeds the MXU directly.

A plane whose ids are all neighbours (one small field of a field-major design
matrix: a weekday, a device type, the intercept) need not be gathered at all:
`annotate_spans` reads each plane's least and greatest id from the concrete
arrays, and `matvec` multiplies such a plane against that span of the
coefficients by compare, select and reduce on the vector unit ("dense span",
below); the wide planes keep the gather, and every plane the scatter-add.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# The widest span class whose plane's margins are a dense span; a plane whose
# non-padding ids span more is gathered. Measured, not tuned
# (`examples/probe_dense_span.py` on a v5e; PERF.md section 3, the probe's
# table, PR 39): the largest class at which the dense-span product takes less
# than half of the gather's time at both sparse cells' row counts. A plane of
# 8,000,000 entries into 1,000,000 features: 20.2 ms at 2,048 against 54.2,
# 72.8 ms at 4,096; of 11,460,155 entries: 24.5 ms against 77.7, 43.9 ms.
DENSE_SPAN_LIMIT = 2048
# The narrowest class: one full row of lanes. Classes are powers of two from
# here to the limit, so that a field whose rarest ids a seed may or may not
# draw still compiles one program.
DENSE_SPAN_FLOOR = 128


@dataclasses.dataclass(frozen=True)
class SparseFeatures:
    """Padded ELL sparse matrix: row r has features indices[r, k] -> values[r, k].

    `dim` (the feature-space width) is static metadata so shapes stay known to
    XLA. Every index lies in [0, dim) and a padding slot is index 0 with
    value 0.0: `matvec` / `rmatvec` / `sq_rmatvec` promise XLA the first
    (an index out of range reads or writes what the device finds there,
    unchecked) and need the second for a padding slot to add nothing.

    Invariant: non-padding indices are unique within a row. matvec/rmatvec are
    linear so duplicates would still sum correctly there, but moment-based
    consumers (the sparse Pearson feature-selection path in
    data/game_dataset.py) count per-column presence and would diverge from the
    dense branch on duplicated entries. `pack_csr_to_ell` accumulates
    duplicates; hand-built arrays must honor the invariant themselves.

    `ell_axis` selects the plane layout: -1 is the standard (..., N, K);
    -2 stores (..., K, N) — the TPU-friendly layout for entity BLOCKS,
    where K (nnz per row, often ~10) would otherwise sit in the 128-lane
    minor tile dimension and XLA would pad every block copy by 128/K (a
    measured 14.2x HBM expansion inside the vmapped per-entity solves at
    MovieLens-20M scale; transposed, the padding is K->multiple-of-8,
    ~1.8x). The row axis N (bucket capacity, a power of two >= 8) tiles
    cleanly as the minor dimension.
    """

    indices: Array  # (..., N, K) int32, or (..., K, N) when ell_axis == -2
    values: Array  # float, same shape as indices
    dim: int = dataclasses.field(metadata=dict(static=True))
    ell_axis: int = dataclasses.field(default=-1, metadata=dict(static=True))
    # The dense-span annotation, made by `annotate_spans` from the concrete
    # arrays and by nothing else: no argument of the constructor, so whatever
    # builds a shard from other arrays (`dataclasses.replace(feats,
    # indices=...)` too) builds it unannotated, and an annotation cannot
    # outlive the indices it was read from. `span_classes` is static: a
    # plane's class is 0 (wide: its margins are gathered) or the power of two,
    # DENSE_SPAN_FLOOR to DENSE_SPAN_LIMIT, that holds its non-padding ids'
    # span. `span_lo` is data: the (K,) int32 least ids, so two seeds of one
    # field layout run one program. Unannotated, `matvec` traces what it
    # traced before there was an annotation.
    span_lo: Optional[Array] = dataclasses.field(default=None, init=False)
    span_classes: Tuple[int, ...] = dataclasses.field(default=(), init=False)

    @property
    def shape(self) -> Tuple[int, ...]:
        if self.ell_axis == -2:
            return (*self.values.shape[:-2], self.values.shape[-1], self.dim)
        return (*self.values.shape[:-1], self.dim)

    def _planes(self) -> Tuple[Array, Array]:
        """(indices, values) with the plane axis K leading: (K, ..., N)."""
        return (
            jnp.moveaxis(self.indices, self.ell_axis, 0),
            jnp.moveaxis(self.values, self.ell_axis, 0),
        )

    def matvec(self, w: Array) -> Array:
        """x @ w for every row, a plane at a time: K gathers of N entries
        of `w`, each multiplied by its plane of values and added to the
        (..., N) margins.

        One gather of all N * K entries made XLA's TPU compiler read `w`
        from HBM for every entry and write (then re-lay, then reduce) an
        N * K temporary; a plane's gather takes its table from VMEM, and the
        loop carries only the margins (PERF.md section 5, `lr-criteo.fit`).
        The table is the loop's own copy of `w`, one zero longer so that it
        is a copy: a buffer that lives as long as the loop is placed in VMEM,
        where a `w` the caller keeps (an L-BFGS start, alive through the
        solve) stayed in HBM, at 15 ns a gathered entry against 6.6. The
        indices are promised in bounds, which the padding invariant gives: a
        padding slot gathers `w[0]` and multiplies it by 0.0.

        An annotated plane of class S is no gather: its margins are
        `sum_s where(i - lo == s, w[lo + s], 0) * v` over the S lanes of its
        span, one reduce fusion with the rows on the lanes, exact in float32
        (one term is non-zero) and so bit-equal to the gathered product. An id
        outside [lo, lo + S), a padding slot's among them, matches no lane.
        The narrow planes go first, a rolled loop a class; then the scan over
        the wide planes, each taken out of the stored arrays by index."""
        w = jnp.asarray(w)
        # (class, its planes) in ascending class, planes in stored order.
        narrow = [
            (c, [k for k, ck in enumerate(self.span_classes) if ck == c])
            for c in sorted(set(self.span_classes) - {0})
        ]
        if not narrow or w.ndim != 1:
            table = jnp.pad(w, [(0, 0)] * (w.ndim - 1) + [(0, 1)])
            idx, val = self._planes()

            def plane(z, iv):
                i, v = iv
                return z + table.at[..., i].get(mode="promise_in_bounds") * v, None

            z0 = jnp.zeros(w.shape[:-1] + idx.shape[1:], jnp.result_type(w, val))
            return jax.lax.scan(plane, z0, (idx, val))[0]
        # Padded by the widest class: a span at the end of `w` is sliced whole,
        # not clamped down onto its neighbours.
        table = jnp.pad(w, (0, narrow[-1][0]))
        idx, val = self._planes()
        z = jnp.zeros(idx.shape[1:], jnp.result_type(w, val))
        for span, planes in narrow:
            lanes = jnp.arange(span, dtype=jnp.int32)[:, None]

            def dense_span(z, k):
                i, v, lo = _plane(idx, k), _plane(val, k), self.span_lo[k]
                w_span = jax.lax.dynamic_slice(table, (lo,), (span,))
                hit = (i - lo)[None, :] == lanes
                return z + jnp.sum(jnp.where(hit, w_span[:, None], 0), axis=0) * v, None

            z = jax.lax.scan(dense_span, z, jnp.asarray(planes, jnp.int32))[0]

        def gathered(z, k):
            return z + table.at[_plane(idx, k)].get(mode="promise_in_bounds") * _plane(val, k), None

        wide = [k for k, c in enumerate(self.span_classes) if not c]
        return jax.lax.scan(gathered, z, jnp.asarray(wide, jnp.int32))[0] if wide else z

    def _scatter_planes(self, u: Array, square: bool) -> Array:
        """sum_k scatter-add of `values[k] * u` (`values[k]**2 * u` if
        `square`) at `indices[k]` into one (dim,) accumulator, which stays
        in VMEM across the loop. Every plane, annotated or not: see
        `rmatvec` for why the transpose has no dense-span form."""
        if self.indices.ndim != 2:
            raise ValueError("rmatvec is per-problem; vmap over leading axes")

        def plane(g, iv):
            i, v = iv
            v = jnp.square(v) if square else v
            return g.at[i].add(v * u, mode="promise_in_bounds"), None

        g0 = jnp.zeros((self.dim,), jnp.result_type(self.values, u))
        return jax.lax.scan(plane, g0, self._planes())[0]

    def rmatvec(self, u: Array) -> Array:
        """X^T u (the transpose of `matvec`), a plane at a time: K
        scatter-adds of N entries each. A padding slot adds 0.0 to feature 0.

        2-D only: batched blocks go through vmap (which rewrites the scatter
        per-lane); an unbatched call on (..., N, K) data would silently sum
        across batch members, so it is rejected.

        What the scatter-add costs (v5e, one plane of 8,000,000 entries into
        1,000,000 features; PERF.md section 3, PR 39): 54-71 ms a plane,
        6.8-8.9 ns an entry, the more the more neighbouring ids repeat. It
        adds an id's entries one after another in float32 (what that rounds
        to on a hot feature, and why the transpose has no dense-span form
        yet: PERF.md section 7).
        """
        return self._scatter_planes(u, square=False)

    def sq_rmatvec(self, u: Array) -> Array:
        """Sum_i u_i * x_i^2 elementwise over features (for Hessian diagonals).
        2-D only, like `rmatvec`."""
        return self._scatter_planes(u, square=True)

    def to_dense(self) -> Array:
        """Densify, batch-dim safe (one-hot contraction over the K axis)."""
        onehot = jax.nn.one_hot(self.indices, self.dim, dtype=self.values.dtype)
        if self.ell_axis == -2:
            return jnp.einsum("...kn,...knd->...nd", self.values, onehot)
        return jnp.einsum("...nk,...nkd->...nd", self.values, onehot)


def _with_spans(features: SparseFeatures, span_lo, span_classes) -> SparseFeatures:
    """A copy of `features` (the same arrays) that carries the annotation: for
    `annotate_spans`, which read it from those arrays, and for the pytree's
    way back from its leaves."""
    out = dataclasses.replace(features)
    object.__setattr__(out, "span_lo", span_lo)
    object.__setattr__(out, "span_classes", tuple(span_classes))
    return out


# What `register_dataclass` would make of it, had the annotation been an
# argument of the constructor: the arrays and the least ids are the children,
# under their attribute names; the rest is static.
jax.tree_util.register_pytree_with_keys(
    SparseFeatures,
    lambda f: (
        tuple((jax.tree_util.GetAttrKey(n), getattr(f, n)) for n in ("indices", "values", "span_lo")),
        (f.dim, f.ell_axis, f.span_classes),
    ),
    lambda static, arrays: _with_spans(
        SparseFeatures(arrays[0], arrays[1], static[0], static[1]), arrays[2], static[2]
    ),
    flatten_func=lambda f: ((f.indices, f.values, f.span_lo), (f.dim, f.ell_axis, f.span_classes)),
)


def _plane(planes: Array, k) -> Array:
    """Plane k of a (K, N) view of the stored arrays: the dynamic slice a scan
    over the planes makes, by an index the caller chose."""
    return jax.lax.dynamic_index_in_dim(planes, k, 0, keepdims=False)


@jax.jit
def _plane_spans(indices: Array, values: Array) -> Array:
    """(2, K): each plane's least and greatest id over its non-padding
    entries (value != 0; a padding slot is index 0 and would stretch every
    span down to id 0). A plane with no entry reads (int32 max, -1)."""
    live = values != 0
    lo = jnp.min(jnp.where(live, indices, jnp.iinfo(jnp.int32).max), axis=0)
    hi = jnp.max(jnp.where(live, indices, -1), axis=0)
    return jnp.stack([lo, hi])


def span_class(lo: int, hi: int) -> int:
    """The class of a plane whose non-padding ids are [lo, hi]: the power of
    two from DENSE_SPAN_FLOOR up that holds the span, or 0 (wide) where that
    passes DENSE_SPAN_LIMIT. A plane with no entry is the narrowest class."""
    span = max(hi - lo + 1, 1)
    if span > DENSE_SPAN_LIMIT:
        return 0
    return max(DENSE_SPAN_FLOOR, 1 << (span - 1).bit_length())


def annotate_spans(features: SparseFeatures) -> SparseFeatures:
    """`features` with its planes' span classes and least ids, READ from the
    concrete arrays: one jitted reduction over them (on a sample-sharded shard
    over the global array, so every device compiles the one program) and one
    fetch of 2 K integers. The only maker of the annotation; callers keep the
    result for as long as they keep the shard (`GameDataset.annotated_shard`).
    A shard with no narrow plane, a batched or (K, N) one, or host arrays come
    back as they are: unannotated, the products are what they were."""
    if (
        features.ell_axis != -1
        or not isinstance(features.indices, jax.Array)
        or features.indices.ndim != 2
    ):
        return features
    lo, hi = np.asarray(_plane_spans(features.indices, features.values)).astype(np.int64)
    classes = tuple(span_class(int(l), int(h)) for l, h in zip(lo, hi))
    if not any(classes):
        return features
    # An empty plane's least id is 0: whatever lane its padding slots match
    # is multiplied by their 0.0.
    lo = np.where(hi < lo, 0, lo).astype(np.int32)
    # The least ids lie replicated beside sample-sharded planes.
    sharding = features.indices.sharding
    if isinstance(sharding, jax.sharding.NamedSharding):
        sharding = jax.sharding.NamedSharding(sharding.mesh, jax.sharding.PartitionSpec())
    elif len(sharding.device_set) != 1:
        return features
    return _with_spans(features, jax.device_put(lo, sharding), classes)


def span_note(features: SparseFeatures) -> dict:
    """How many of a shard's planes take the dense-span product, for the run
    profile (stage note `ell_planes`)."""
    classes = features.span_classes
    return {
        "planes": int(features.indices.shape[features.ell_axis]),
        "dense_span": sum(1 for c in classes if c),
        "classes": sorted({c for c in classes if c}),
        "limit": DENSE_SPAN_LIMIT,
    }


Features = Union[Array, SparseFeatures]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LabeledData:
    """A batch of labeled points (label, x, offset, weight).

    Counterpart of RDD[LabeledPoint] / Iterable[LabeledPoint] in the reference
    (DistributedObjectiveFunction.scala:34, SingleNodeObjectiveFunction.scala).
    `weights` doubles as the padding mask (weight 0 = absent row).
    """

    features: Features  # (N, D) dense or SparseFeatures
    labels: Array  # (N,)
    offsets: Array  # (N,)
    weights: Array  # (N,)
    # How a dense `features` lies on its device(s), for the fused kernels
    # (ops/pallas_glm, "How X lies"): True where whoever holds the concrete
    # array read it column-major (`pallas_glm.lies_row_major`), and the
    # kernels then take (d, tile) blocks of X^T and relay nothing. Static,
    # and a hint only: a wrong value costs a relayout, never a wrong sum.
    column_major: bool = dataclasses.field(default=False, metadata=dict(static=True))

    @property
    def num_rows(self) -> int:
        return self.labels.shape[-1]

    @property
    def feature_dim(self) -> int:
        if hasattr(self.features, "dim"):  # SparseFeatures / bucketed layout
            return self.features.dim
        return self.features.shape[-1]

    def with_offsets(self, offsets: Array) -> "LabeledData":
        return dataclasses.replace(self, offsets=offsets)


def dense_data(
    X,
    y,
    *,
    offsets=None,
    weights=None,
    dtype=jnp.float32,
) -> LabeledData:
    """Convenience constructor from host arrays."""
    X = jnp.asarray(X, dtype=dtype)
    y = jnp.asarray(y, dtype=dtype)
    n = y.shape[0]
    offsets = jnp.zeros(n, dtype) if offsets is None else jnp.asarray(offsets, dtype)
    weights = jnp.ones(n, dtype) if weights is None else jnp.asarray(weights, dtype)
    return LabeledData(X, y, offsets, weights)


def pack_csr_to_ell(
    indptr: np.ndarray,
    indices: np.ndarray,
    values: np.ndarray,
    dim: int,
    *,
    max_nnz: Optional[int] = None,
    dtype=np.float32,
    assume_clean: bool = False,
    extra_col: Optional[Tuple[int, float]] = None,
    return_host: bool = False,
    device: bool = True,
) -> Union[SparseFeatures, Tuple[SparseFeatures, Tuple[np.ndarray, np.ndarray]]]:
    """Host-side CSR -> padded ELL conversion.

    Rows with more than `max_nnz` entries keep their largest-|value| entries
    (mirrors the spirit of the reference's active-feature filters rather than
    failing); by default max_nnz = max row length, i.e. lossless.

    `assume_clean=True` asserts no (row, col) duplicates exist — callers that
    decoded through the native reader get this guaranteed by the decoder
    (avro_reader.cc dedup_row accumulates in-record duplicates at decode
    time) and skip the O(nnz log nnz) dedup sort here.
    `extra_col=(index, value)` appends one constant dense column (the
    intercept) host-side, avoiding a CSR rebuild + re-sort in the caller.
    """
    n = len(indptr) - 1
    indptr = np.asarray(indptr, np.int64)
    indices = np.asarray(indices)
    values = np.asarray(values)
    row_lens = np.diff(indptr)
    k_full = int(row_lens.max()) if n else 0
    k = k_full if max_nnz is None else int(max_nnz)
    k = max(k, 1)
    extra = 1 if extra_col is not None else 0
    out_idx = np.zeros((n, k + extra), dtype=np.int32)
    out_val = np.zeros((n, k + extra), dtype=dtype)
    if extra_col is not None:
        out_idx[:, k] = extra_col[0]
        out_val[:, k] = extra_col[1]

    rows = None  # COO row ids, built only by the paths that need them

    def _rows():
        nonlocal rows
        if rows is None:
            rows = np.repeat(np.arange(n, dtype=np.int64), row_lens)
        return rows

    if not assume_clean and len(indices):
        rows = _rows()
        # One global stable sort by (row, col) finds AND accumulates
        # duplicates vectorized — the former per-row np.unique loop was the
        # single largest cost of the whole ingest path (94% of assembly wall
        # at 200k rows; VERDICT r04 item 1).
        key = rows * np.int64(dim) + indices.astype(np.int64)
        order = np.argsort(key, kind="stable")
        sk = key[order]
        dup = sk[1:] == sk[:-1]
        if dup.any():
            first = np.empty(len(sk), bool)
            first[0] = True
            np.logical_not(dup, out=first[1:])
            starts = np.nonzero(first)[0]
            # float64 accumulation in sorted-key order: equal keys keep CSR
            # order under the stable sort, so sums are bit-identical to the
            # former sequential np.add.at accumulation.
            acc = np.add.reduceat(values.astype(np.float64)[order], starts)
            ukey = sk[starts]
            rows = ukey // np.int64(dim)
            indices = (ukey % np.int64(dim)).astype(indices.dtype)
            values = acc.astype(values.dtype)
            row_lens = np.bincount(rows, minlength=n)
            indptr = np.zeros(n + 1, np.int64)
            np.cumsum(row_lens, out=indptr[1:])
            k_full = int(row_lens.max()) if n else 0
            # The ELL width stays at the PRE-dedup maximum (as it always
            # did); dedup only shortens rows, leaving extra padding.
            # Deduped rows come out column-sorted (as np.unique sorted them
            # in the former loop); clean rows keep CSR entry order.

    if k_full > k:
        # Largest-|value| truncation, only for the (rare) offending rows.
        big = np.nonzero(row_lens > k)[0]
        rows = _rows()
        keep_mask = np.ones(len(rows), bool)
        for r in big:
            lo, hi = int(indptr[r]), int(indptr[r + 1])
            drop = np.argsort(-np.abs(values[lo:hi]))[k:]
            keep_mask[lo + drop] = False
        # Entries kept in CSR-position order; the reference loop wrote them
        # in descending-|value| order, but within-row ELL order is free (see
        # SparseFeatures invariant) and position order keeps this vectorized.
        rows = rows[keep_mask]
        indices = indices[keep_mask]
        values = values[keep_mask]
        row_lens = np.minimum(row_lens, k)
        indptr = np.zeros(n + 1, np.int64)
        np.cumsum(row_lens, out=indptr[1:])

    # Entry placement, preserving entry order within each row: a sequential
    # native pass when available (photon_ell_fill — one walk writes both
    # planes), else one vectorized numpy scatter. The intercept column is
    # prefilled above, so the native call fills the body only.
    filled = False
    try:
        from photon_ml_tpu.native.bucketed_pack import ell_fill_native

        filled = ell_fill_native(row_lens, indices, values, out_idx, out_val)
    except Exception:
        filled = False
    if not filled:
        rows = _rows()
        pos = np.arange(len(rows), dtype=np.int64) - np.repeat(indptr[:-1], row_lens)
        out_idx[rows, pos] = indices
        out_val[rows, pos] = values
    # `device=False` keeps the planes as numpy (ingest's lazy-upload path:
    # GameDataset.ShardDict materializes on first device use, so shards
    # whose training runs on the bucketed/projected layouts never upload).
    if device:
        sf = SparseFeatures(jnp.asarray(out_idx), jnp.asarray(out_val), dim)
    else:
        sf = SparseFeatures(out_idx, out_val, dim)
    if return_host:
        # The host planes, free at this point: ingest stashes them
        # (GameDataset.host_ell) so projector/statistics consumers read
        # host memory instead of pulling the device arrays back.
        return sf, (out_idx, out_val)
    return sf
