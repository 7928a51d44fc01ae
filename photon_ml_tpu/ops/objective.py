"""GLM objective: weighted loss value / gradient / Hessian products.

TPU-native collapse of the reference's aggregator family
(ValueAndGradientAggregator.scala:34-280, HessianVectorAggregator.scala:23-173,
HessianDiagonalAggregator.scala, HessianMatrixAggregator.scala) and of the
objective-function hierarchy that routes to them
(DistributedGLMLossFunction.scala:48-147, SingleNodeGLMLossFunction.scala:165).

Where the reference runs a hand-written per-datum hot loop inside
RDD.treeAggregate, here each quantity is a closed-form vectorized expression
over the whole (sharded) batch:

    z   = X (w*factor) - shifts.(w*factor) + offset            margins
    f   = sum_i weight_i * l(z_i, y_i)  (+ lambda/2 ||w||^2)
    g   = factor * (X^T u - (sum u) shifts) + lambda w,  u = weight * l'(z)
    Hv  = factor * (X^T r - (sum r) shifts) + lambda v,
          r = weight * l''(z) * ((X (v*factor)) - shifts.(v*factor))

Normalization is folded in as coefficient algebra exactly like the reference
(see ops/normalization.py) so the data is never rewritten. When data is
sharded over a device mesh, the sums above become XLA all-reduces over ICI —
the treeAggregate equivalent — inserted automatically under jit/shard_map.

All functions are pure and vmappable: the same code serves the fixed effect
(one big problem, data-parallel) and random effects (vmap over thousands of
small entity problems).

The loss is a weighted *sum*, not mean, matching the reference — so
regularization weights are directly comparable.
"""

from __future__ import annotations

import dataclasses
from typing import Literal, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from photon_ml_tpu.data.bucketed import BucketedSparseFeatures
from photon_ml_tpu.data.containers import DenseBlock, LabeledData, SparseFeatures
from photon_ml_tpu.ops import pallas_glm, pallas_sparse
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.ops.normalization import NormalizationContext

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ObjectiveRoute:
    """What computes the objective of one shard: decided once, on the
    concrete shard (`route_for`), and closed over by the programs that solve
    it — static under jit. The kinds:

    - "xla": the XLA composition — a dense matmul, the ELL planes' gathers
      and scatter-adds, a `DenseBlock`, or a bucketed pack through XLA;
    - "dense": the fused dense kernel (ops/pallas_glm) on one device;
    - "dense_sharded": that kernel on each device's rows of a sample-sharded
      matrix under shard_map, and one psum an evaluation;
    - "ell_sharded": the ELL planes through XLA on each device's rows of a
      sample-sharded shard, summed over the mesh once an evaluation;
    - "bucketed_fused": the single-stream sparse kernel (ops/pallas_sparse);
    - "bucketed_grouped": the sparse matvec and rmatvec kernels, for a pack
      whose tile the fused kernel cannot hold in VMEM.

    `column_major` (dense kinds): the matrix lies column-major, and the
    kernels read (d, tile) blocks of X^T (pallas_glm, "How X lies").
    `mesh` and `axis` (sharded kinds): the 1-D mesh the rows are cut over."""

    kind: Literal[
        "xla", "dense", "dense_sharded", "ell_sharded", "bucketed_fused", "bucketed_grouped"
    ] = "xla"
    column_major: bool = False
    mesh: Optional[Mesh] = None
    axis: Optional[str] = None

    @property
    def dense_kernel(self) -> bool:
        return self.kind in ("dense", "dense_sharded")

    @property
    def sparse_kernels(self) -> bool:
        return self.kind in ("bucketed_fused", "bucketed_grouped")


XLA = ObjectiveRoute()


def route_for(features) -> ObjectiveRoute:
    """The route of a shard, read from the concrete arrays: their type,
    shape, dtype, placement and layout. The one place the kernel layer is
    chosen. A bucketed pack's route depends on its static structure only,
    so it is read the same from a traced pack."""
    if isinstance(features, BucketedSparseFeatures):
        if not pallas_sparse.should_use(features):
            return XLA
        fused = pallas_sparse.fused_feasible(features)
        return ObjectiveRoute("bucketed_fused" if fused else "bucketed_grouped")
    if isinstance(features, SparseFeatures):
        # Sample-sharded planes are summed over the mesh by the program
        # itself (`_summed_over_samples`).
        if features.ell_axis != -1 or features.indices.ndim != 2:
            return XLA
        from photon_ml_tpu.parallel.mesh import leading_axis_mesh

        mesh = leading_axis_mesh(features.indices, require_divisible=True)
        if mesh is None:
            return XLA
        return ObjectiveRoute("ell_sharded", mesh=mesh, axis=mesh.axis_names[0])
    if isinstance(features, DenseBlock):
        return XLA
    mode = pallas_glm.dispatch(
        features, jax.ShapeDtypeStruct((features.shape[-1],), jnp.float32)
    )
    if mode is False:
        return XLA
    # How the matrix lies is read from the device array (a host array has no
    # layout and is read row-major): where it lies column-major the kernels
    # take (d, tile) blocks of X^T, a bitcast, and no program relays it.
    column_major = isinstance(features, jax.Array) and not pallas_glm.lies_row_major(features)
    if mode is True:
        return ObjectiveRoute("dense", column_major)
    return ObjectiveRoute("dense_sharded", column_major, mode.mesh, mode.axis)


def _eff(w: Array, norm: Optional[NormalizationContext]) -> Tuple[Array, Array]:
    """(effective coefficients, scalar margin shift)."""
    if norm is None or norm.is_identity:
        return w, jnp.zeros((), dtype=w.dtype)
    return norm.effective_coefficients(w), norm.margin_shift(w)


def margin_params(
    w: Array, norm: Optional[NormalizationContext]
) -> Tuple[Array, Array]:
    """Public view of `_eff` for scoring-side consumers (the transformer's
    row-stable dense margin path and the serving engine): the effective
    coefficient vector plus the scalar margin shift normalization folds in."""
    return _eff(w, norm)


def _matvec(features, w_eff: Array, route: ObjectiveRoute) -> Array:
    if isinstance(features, BucketedSparseFeatures):
        if route.sparse_kernels:
            return pallas_sparse.matvec(
                features, w_eff, interpret=pallas_glm.FORCE_INTERPRET
            )
        return pallas_sparse.matvec_xla(features, w_eff)
    if isinstance(features, (SparseFeatures, DenseBlock)):
        return features.matvec(w_eff)
    return features @ w_eff


def _rmatvec(features, u: Array, route: ObjectiveRoute, square: bool = False) -> Array:
    """X^T u, or (`square`) sum_i u_i * x_i^2 per feature (Hessian diagonals)."""
    if isinstance(features, BucketedSparseFeatures):
        if route.sparse_kernels:
            return pallas_sparse.rmatvec(
                features, u, interpret=pallas_glm.FORCE_INTERPRET, square=square
            )
        return pallas_sparse.rmatvec_xla(features, u, square=square)
    if isinstance(features, (SparseFeatures, DenseBlock)):
        return features.sq_rmatvec(u) if square else features.rmatvec(u)
    return u @ (jnp.square(features) if square else features)


def _own_route(features) -> ObjectiveRoute:
    """The route of the functions that take none (margins, `value`, the
    Hessian's diagonal and matrix): a bucketed pack's own, every other
    shard's XLA."""
    return route_for(features) if isinstance(features, BucketedSparseFeatures) else XLA


def _needs_row_sum(norm: Optional[NormalizationContext]) -> bool:
    """Does the normalization's gradient algebra need sum_i u_i (shifts)?"""
    return norm is not None and not norm.is_identity and norm.shifts is not None


def _value_gradient_sums(loss, w_eff, shift, data: LabeledData, row_sum: bool, route: ObjectiveRoute):
    """(value, X^T u, sum u or None) of the rows in `data`, through XLA."""
    z = _matvec(data.features, w_eff, route) + shift + data.offsets
    val = jnp.sum(data.weights * loss.loss(z, data.labels))
    u = data.weights * loss.d1(z, data.labels)
    return val, _rmatvec(data.features, u, route), jnp.sum(u) if row_sum else None


def _hessian_vector_sums(loss, w_eff, shift, v_eff, v_shift, data: LabeledData, row_sum: bool,
                         route: ObjectiveRoute):
    """(X^T r, sum r or None) of the rows in `data`, through XLA."""
    z = _matvec(data.features, w_eff, route) + shift + data.offsets
    d2 = loss.d2(z, data.labels)
    q = _matvec(data.features, v_eff, route) + v_shift
    r = data.weights * d2 * q
    return _rmatvec(data.features, r, route), jnp.sum(r) if row_sum else None


def _summed_over_samples(route: ObjectiveRoute, sums, replicated, data: LabeledData):
    """`sums(*replicated, data)` on each device's own rows of a sample-sharded
    ELL shard, and the devices' results added ONCE: every device runs the
    one-device plane loops (table and accumulator in VMEM) into its own
    partial, and one `psum` reduces value and (d,) gradient together. Left to
    the partitioner, the loop's replicated accumulator is reduced after every
    plane's scatter-add: K reductions of d floats an evaluation."""
    from jax.sharding import PartitionSpec as P

    from photon_ml_tpu.parallel.mesh import shard_map_compat

    axis = route.axis
    rows = P(axis)
    # The shard's (N, K) planes are cut by sample; what else it carries (the
    # planes' least ids, where it is annotated) is every device's.
    features = jax.tree.map(lambda a: P(axis, None) if a.ndim == 2 else P(), data.features)

    def per_device(replicated, features, labels, offsets, weights):
        partial = sums(*replicated, LabeledData(features, labels, offsets, weights))
        with jax.named_scope("allreduce"):
            return jax.lax.psum(partial, axis)

    return shard_map_compat(
        per_device,
        mesh=route.mesh,
        in_specs=(P(), features, rows, rows, rows),
        out_specs=P(),
    )(replicated, data.features, data.labels, data.offsets, data.weights)


def compute_margins(
    w: Array, data: LabeledData, norm: Optional[NormalizationContext] = None
) -> Array:
    """z_i = x_i.(w*factor) + shift-term + offset_i (LabeledPoint.computeMargin)."""
    w_eff, shift = _eff(w, norm)
    return _matvec(data.features, w_eff, _own_route(data.features)) + shift + data.offsets


def value(
    loss: PointwiseLoss,
    w: Array,
    data: LabeledData,
    norm: Optional[NormalizationContext] = None,
    l2: float | Array = 0.0,
) -> Array:
    z = compute_margins(w, data, norm)
    val = jnp.sum(data.weights * loss.loss(z, data.labels))
    return val + 0.5 * l2 * jnp.dot(w, w)


def value_and_gradient(
    loss: PointwiseLoss,
    w: Array,
    data: LabeledData,
    norm: Optional[NormalizationContext] = None,
    l2: float | Array = 0.0,
    route: ObjectiveRoute = XLA,
) -> Tuple[Array, Array]:
    """One fused pass: margins computed once, shared by value and gradient.

    Replaces ValueAndGradientAggregator.calculateValueAndGradient + its
    treeAggregate (lines 137-161, 240-255 of the reference file).

    `route` (`route_for`, decided once on the concrete shard) says what
    computes the sums: the fused dense kernel that streams X from HBM once
    for both matmuls (ops/pallas_glm.py), on one device or per device; the
    ELL planes summed over samples; the bucketed sparse kernels; or the XLA
    composition, which is also what a vmapped per-entity solve runs.
    """
    w_eff, shift = _eff(w, norm)
    row_sum = _needs_row_sum(norm)
    if route.kind == "bucketed_fused":
        # One stream over the bucketed entries computes value, u and the
        # gradient together (pallas_sparse._fused_kernel): the same raw sums
        # as the dense fused kernel. The per-level layout rides in the
        # features pytree and the kernels branch per level.
        val, g, sum_u = pallas_sparse.fused_value_gradient_sums(
            loss, w_eff, shift, data.features, data.labels, data.offsets,
            data.weights, interpret=pallas_glm.FORCE_INTERPRET,
        )
    elif route.kind == "ell_sharded":
        val, g, sum_u = _summed_over_samples(
            route,
            lambda w_, s_, d_: _value_gradient_sums(loss, w_, s_, d_, row_sum, route),
            (w_eff, shift), data,
        )
    elif route.kind == "dense_sharded":
        val, g, sum_u = pallas_glm.sharded_value_gradient_sums(
            loss, w_eff, shift, data.features, data.labels, data.offsets,
            data.weights, mesh=route.mesh, axis=route.axis,
            interpret=pallas_glm.FORCE_INTERPRET, column_major=route.column_major,
        )
    elif route.kind == "dense":
        val, g, sum_u = pallas_glm.value_gradient_sums(
            loss, w_eff, shift, data.features, data.labels, data.offsets,
            data.weights, interpret=pallas_glm.FORCE_INTERPRET,
            column_major=route.column_major,
        )
    else:
        val, g, sum_u = _value_gradient_sums(loss, w_eff, shift, data, row_sum, route)
    if norm is not None and not norm.is_identity:
        if norm.shifts is not None:
            g = g - sum_u * norm.shifts
        if norm.factors is not None:
            g = g * norm.factors
    return val + 0.5 * l2 * jnp.dot(w, w), g + l2 * w


def hessian_vector(
    loss: PointwiseLoss,
    w: Array,
    v: Array,
    data: LabeledData,
    norm: Optional[NormalizationContext] = None,
    l2: float | Array = 0.0,
    route: ObjectiveRoute = XLA,
) -> Array:
    """Gauss-Newton/Hessian product H(w) v (HessianVectorAggregator.scala:23-142).

    Exact for the GLM losses here (their Hessian is X^T diag(weight*l'') X in
    the normalized space).

    On a dense-kernel route [w|v] is packed into one [D, 2] right-hand side
    so both forward matvecs and the backward contraction run in a single
    pass over X (ops/pallas_glm.py); a bucketed route's products are its
    matvec and rmatvec kernels.
    """
    w_eff, shift = _eff(w, norm)
    v_eff, v_shift = _eff(v, norm)
    row_sum = _needs_row_sum(norm)
    if route.kind == "ell_sharded":
        hv, sum_r = _summed_over_samples(
            route,
            lambda w_, s_, v_, vs_, d_: _hessian_vector_sums(loss, w_, s_, v_, vs_, d_, row_sum, route),
            (w_eff, shift, v_eff, v_shift), data,
        )
    elif route.kind == "dense_sharded":
        hv, sum_r = pallas_glm.sharded_hessian_vector_sums(
            loss, w_eff, shift, v_eff, v_shift, data.features, data.labels,
            data.offsets, data.weights, mesh=route.mesh,
            axis=route.axis, interpret=pallas_glm.FORCE_INTERPRET,
            column_major=route.column_major,
        )
    elif route.kind == "dense":
        hv, sum_r = pallas_glm.hessian_vector_sums(
            loss, w_eff, shift, v_eff, v_shift, data.features, data.labels,
            data.offsets, data.weights, interpret=pallas_glm.FORCE_INTERPRET,
            column_major=route.column_major,
        )
    else:
        hv, sum_r = _hessian_vector_sums(loss, w_eff, shift, v_eff, v_shift, data, row_sum, route)
    if norm is not None and not norm.is_identity:
        if norm.shifts is not None:
            hv = hv - sum_r * norm.shifts
        if norm.factors is not None:
            hv = hv * norm.factors
    return hv + l2 * v


def hessian_diagonal(
    loss: PointwiseLoss,
    w: Array,
    data: LabeledData,
    norm: Optional[NormalizationContext] = None,
    l2: float | Array = 0.0,
) -> Array:
    """diag H = factor^2 * sum_i c_i (x_ij - s_j)^2 + lambda, c = weight * l''.

    (HessianDiagonalAggregator.scala:96-102; used for SIMPLE variance.)
    Expanded as sum c x^2 - 2 s (sum c x) + s^2 (sum c) so the sparse path
    never densifies.
    """
    w_eff, shift = _eff(w, norm)
    feats = data.features
    route = _own_route(feats)
    z = _matvec(feats, w_eff, route) + shift + data.offsets
    c = data.weights * loss.d2(z, data.labels)
    sq = _rmatvec(feats, c, route, square=True)
    lin = _rmatvec(feats, c, route)
    diag = sq
    if norm is not None and norm.shifts is not None:
        s = norm.shifts
        diag = sq - 2.0 * s * lin + jnp.square(s) * jnp.sum(c)
    if norm is not None and norm.factors is not None:
        diag = diag * jnp.square(norm.factors)
    return diag + l2


def hessian_matrix(
    loss: PointwiseLoss,
    w: Array,
    data: LabeledData,
    norm: Optional[NormalizationContext] = None,
    l2: float | Array = 0.0,
) -> Array:
    """Full D x D Hessian (HessianMatrixAggregator.scala:96-102; FULL variance).

    Densifies sparse features — intended for modest D (the reference holds the
    same D x D Breeze matrix on the driver).
    """
    w_eff, shift = _eff(w, norm)
    z = _matvec(data.features, w_eff, _own_route(data.features)) + shift + data.offsets
    c = data.weights * loss.d2(z, data.labels)
    feats = data.features
    if isinstance(feats, BucketedSparseFeatures):
        X = pallas_sparse.to_dense_xla(feats)
    elif isinstance(feats, (SparseFeatures, DenseBlock)):
        X = feats.to_dense()
    else:
        X = feats
    if norm is not None and norm.shifts is not None:
        X = X - norm.shifts
    # A dense block is contracted in float32 (`DenseBlock`): the TPU's
    # default precision would round it to bfloat16.
    precision = jax.lax.Precision.HIGHEST if isinstance(feats, DenseBlock) else None
    H = jnp.matmul((X * c[:, None]).T, X, precision=precision)
    if norm is not None and norm.factors is not None:
        H = H * jnp.outer(norm.factors, norm.factors)
    return H + l2 * jnp.eye(w.shape[0], dtype=w.dtype)
