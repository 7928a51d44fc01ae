"""Random-effect feature-space projectors.

Counterpart of photon-api projector/* — Projector.scala:58,
IndexMapProjector.scala:92, IndexMapProjectorRDD.scala:36-218,
ProjectionMatrix.scala:32-99, ProjectionMatrixBroadcast.scala:32-131,
IdentityProjector.scala, ProjectorType.scala, RandomEffectProjector.scala:74
and model/RandomEffectModelInProjectedSpace.scala:129.

Purpose (same as the reference): shrink each entity's feature space so the
per-entity random-effect models are dense-small. The reference builds one
projector per entity as an RDD keyed by REId, each with its own projected
dimension. On TPU the per-entity coefficient store is ONE (E+1, D_proj)
matrix, so every entity shares a common padded projected dimension:

  * IndexMapProjector: per-entity index compaction. For each entity, the
    distinct global feature indices appearing in its samples (active +
    passive, IndexMapProjectorRDD.scala:60-90) are assigned local slots
    0..k_e-1; D_proj = max_e k_e (padded). Projection rewrites the ELL
    `indices` arrays host-side ONCE at dataset-build time — on device nothing
    changes except that gathers/scatters run over D_proj instead of the full
    shard width. Back-projection scatters each row through its entity's
    slot->global table.
  * RandomProjector: a shared Gaussian matrix P (D, d) with N(0, 1/d)
    entries (ProjectionMatrix.scala:99); features are densified through the
    MXU (X @ P), models live in projected space, and back-projection is
    w_orig = P w_proj (the reference's projectCoefficients transpose map).
  * IdentityProjector: no-op.

All projectors expose the same surface: `project_features` (global ->
projected sample features), `back_project_matrix` (projected coefficient
matrix -> original-space rows, for saving/inspection), and `projected_dim`.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.data.containers import Features, SparseFeatures
from photon_ml_tpu.types import ProjectorType

Array = jax.Array


class IdentityProjector:
    """ProjectorType.IDENTITY — original space == projected space
    (IdentityProjector.scala)."""

    def __init__(self, dim: int):
        self.original_dim = dim
        self.projected_dim = dim

    def project_features(
        self, features: Features, entity_rows: np.ndarray, host_planes=None
    ) -> Features:
        return features

    def back_project_matrix(self, matrix: Array) -> Array:
        return matrix

    def project_matrix(self, matrix: Array) -> Array:
        return matrix


class IndexMapProjector:
    """Per-entity index compaction (IndexMapProjectorRDD.scala:36-218).

    `slot_tables[e, j]` = global feature index occupying local slot j of
    entity e (or -1 for padding). Row E (the unseen-entity row) has an empty
    table. Built host-side from the samples' sparse indices; the projected
    dimension is the max per-entity distinct-feature count, optionally
    rounded up to a multiple of 8 for TPU lane alignment.
    """

    def __init__(self, slot_tables: np.ndarray, original_dim: int):
        self.slot_tables = slot_tables  # (E + 1, D_proj) int64, -1 = pad
        self.original_dim = int(original_dim)
        self.projected_dim = int(slot_tables.shape[1])
        # Device-side mapper (data/device_assemble.DeviceIndexMapper) when
        # the build ran on device: later projections (training shard,
        # validation data) are one XLA program each instead of host
        # searchsorted sweeps. None on the host path — consumers fall back.
        self._device_mapper = None
        # Fused-pass byproduct: the original shard's feature summary,
        # computed in the SAME program as the projector key sort when the
        # caller asked for it (GameEstimator's normalization contexts).
        self.original_stats = None

    @classmethod
    def build(
        cls,
        features: SparseFeatures,
        entity_rows: np.ndarray,
        num_entities: int,
        *,
        pad_multiple: int = 8,
        host_planes=None,
        want_stats: bool = False,
    ) -> "IndexMapProjector":
        """Collect each entity's distinct active feature indices
        (IndexMapProjectorRDD.scala:60-90 unions active+passive; here
        `entity_rows` covers every sample so both are included).
        `host_planes` is ingest's (indices, values) host copy
        (GameDataset.host_ell) — without it, np.asarray on a device array
        is a synchronous device->host copy of the whole shard.

        Device path (data/device_assemble.py, PHOTON_DEVICE_ASSEMBLY):
        the nnz-sized key sort/unique/table scatter runs as XLA programs
        — bitwise-identical slot tables, with only the E-sized counts
        crossing back to host. `want_stats` additionally folds the
        feature-summary moments into the same sweep (the fused auxiliary
        pass); the host path ignores it (stats run separately there)."""
        if host_planes is not None:
            idx, val = host_planes
        else:
            idx = np.asarray(features.indices)
            val = np.asarray(features.values)
        ent = np.asarray(entity_rows)

        from photon_ml_tpu.data import device_assemble

        if device_assemble.enabled() and device_assemble.projector_supported(
            num_entities, features.dim
        ):
            built = device_assemble.build_index_mapper(
                idx,
                val,
                ent,
                num_entities,
                features.dim,
                pad_multiple=pad_multiple,
                want_stats=want_stats,
            )
            if built is not None:
                tables, mapper, stats = built
                proj = cls(tables, features.dim)
                proj._device_mapper = mapper
                proj.original_stats = stats
                return proj
        # Flatten to (entity, global-index) pairs for nonzero entries and
        # take per-entity distinct indices in one vectorized pass. The pair
        # is packed into ONE int64 key — np.unique on a 2-D stack sorts a
        # void view with per-element memcmp comparators, which measured ~25x
        # slower than the integer sort at 2.4M pairs (the dominant cost of
        # GameEstimator.prepare before this).
        ent_flat = np.repeat(ent, idx.shape[1])
        idx_flat = idx.reshape(-1)
        keep = (val.reshape(-1) != 0.0) & (ent_flat < num_entities)
        dimw = np.int64(features.dim)
        keys = np.unique(ent_flat[keep] * dimw + idx_flat[keep])
        pair_ent = keys // dimw
        pair_idx = keys % dimw
        counts = np.bincount(pair_ent, minlength=num_entities)
        d_proj = max(1, int(counts.max()) if len(counts) else 1)
        if pad_multiple > 1:
            d_proj = ((d_proj + pad_multiple - 1) // pad_multiple) * pad_multiple
        tables = np.full((num_entities + 1, d_proj), -1, np.int64)
        # keys are sorted by (entity, global); slot j of entity e is the
        # j-th distinct global index of e.
        starts = np.searchsorted(pair_ent, np.arange(num_entities))
        slot = np.arange(len(keys)) - starts[pair_ent]
        tables[pair_ent, slot] = pair_idx
        return cls(tables, features.dim)

    def project_arrays(
        self, idx: np.ndarray, val: np.ndarray, ent: np.ndarray
    ):
        """Host-side core of project_features on numpy planes; returns the
        projected (indices int32, values) numpy pair."""
        # One GLOBAL searchsorted instead of a per-entity loop: each
        # entity's valid slots, keyed as entity * (dim + 1) + global_index,
        # concatenate into one array that is sorted by construction (tables
        # are per-entity sorted and entity ids increase). An ELL entry's
        # local slot is then its position within its entity's segment.
        valid_mask = self.slot_tables >= 0
        seg_lens = valid_mask.sum(axis=1)
        offsets = np.zeros(len(seg_lens) + 1, np.int64)
        np.cumsum(seg_lens, out=offsets[1:])
        dimw = np.int64(self.original_dim + 1)
        flat_ent = np.repeat(
            np.arange(self.slot_tables.shape[0], dtype=np.int64), seg_lens
        )
        flat_keys = flat_ent * dimw + self.slot_tables[valid_mask]
        entry_keys = ent[:, None] * dimw + idx
        pos = np.searchsorted(flat_keys, entry_keys.reshape(-1)).reshape(idx.shape)
        pos_c = np.minimum(pos, max(len(flat_keys) - 1, 0))
        hit = (
            (flat_keys[pos_c] == entry_keys) & (val != 0.0)
            if len(flat_keys)
            else np.zeros(idx.shape, bool)
        )
        local = pos_c - offsets[ent][:, None]
        out = np.where(hit, local, 0).astype(np.int32)
        val = np.where(hit, val, 0.0).astype(val.dtype)
        return out, val

    def project_features(
        self,
        features: SparseFeatures,
        entity_rows: np.ndarray,
        host_planes=None,
    ) -> SparseFeatures:
        """Rewrite global ELL indices to per-entity local slots (one-time).
        Entries whose feature is absent from the entity's table (value-0
        padding, or unseen entities) are zeroed out. `host_planes` avoids
        the device->host copy (see build). A device-built projector
        projects as one XLA program (bitwise-equal to the host sweep)."""
        if host_planes is not None:
            idx, val = host_planes
        else:
            idx = np.asarray(features.indices)
            val = np.asarray(features.values)
        from photon_ml_tpu.data import device_assemble

        if self._device_mapper is not None and device_assemble.enabled():
            out_d, v_d = device_assemble.project_ell_device(
                self._device_mapper, idx, val, np.asarray(entity_rows)
            )
            return SparseFeatures(out_d, v_d, self.projected_dim)
        out, v = self.project_arrays(idx, val, np.asarray(entity_rows))
        return SparseFeatures(
            jnp.asarray(out), jnp.asarray(v), self.projected_dim
        )

    def back_project_matrix(self, matrix: Array) -> Array:
        """(E+1, D_proj) -> (E+1, D) scatter through the slot tables
        (projectCoefficients direction, IndexMapProjectorRDD.scala:96-120).
        Padding slots scatter into a dummy extra column that is dropped."""
        m = np.asarray(matrix)
        e1, _ = m.shape
        out = np.zeros((e1, self.original_dim + 1), m.dtype)
        cols = np.where(self.slot_tables >= 0, self.slot_tables, self.original_dim)
        np.add.at(out, (np.arange(e1)[:, None], cols), m)
        return jnp.asarray(out[:, : self.original_dim])

    def project_matrix(self, matrix: Array) -> Array:
        """(E+1, D) original-space rows -> (E+1, D_proj) projected rows (the
        warm-start direction: gather each entity's slots). Exact inverse of
        back_project_matrix on this projector's support."""
        m = np.asarray(matrix)
        cols = np.where(self.slot_tables >= 0, self.slot_tables, 0)
        out = np.take_along_axis(m, cols, axis=1)
        out[self.slot_tables < 0] = 0.0
        return jnp.asarray(out)

    def entity_coefficients(self, matrix: Array, entity_row: int) -> Dict[int, float]:
        """One entity's model as {global feature index: weight} (sparse save
        path, ModelProcessingUtils.saveModelsRDDToHDFS)."""
        row = np.asarray(matrix[entity_row])
        table = self.slot_tables[entity_row]
        return {int(g): float(w) for g, w in zip(table, row) if g >= 0 and w != 0.0}


class RandomProjector:
    """Shared Gaussian random projection (ProjectionMatrix.scala:32-99,
    ProjectionMatrixBroadcast.scala).

    P has i.i.d. N(0, 1/d_proj) entries (ProjectionMatrix.scala:99's
    Gaussian generation); projection is a dense matmul so sparse shards are
    densified through the MXU. The reference broadcasts P to executors; here
    it is a replicated device array.
    """

    def __init__(self, matrix: Array):
        self.matrix = matrix  # (D, d_proj)
        self.original_dim = int(matrix.shape[0])
        self.projected_dim = int(matrix.shape[1])

    @classmethod
    def build(cls, original_dim: int, projected_dim: int, seed: int = 0) -> "RandomProjector":
        key = jax.random.PRNGKey(seed)
        p = jax.random.normal(key, (original_dim, projected_dim)) / jnp.sqrt(
            jnp.asarray(projected_dim, jnp.float32)
        )
        return cls(p)

    def project_features(
        self, features: Features, entity_rows: np.ndarray, host_planes=None
    ) -> Array:
        if isinstance(features, SparseFeatures):
            # Sparse x P: gather P rows at the ELL indices and reduce —
            # avoids densifying X itself.
            rows = jnp.take(self.matrix, features.indices, axis=0)  # (N, K, d)
            return jnp.einsum("nk,nkd->nd", features.values, rows)
        return features @ self.matrix

    def back_project_matrix(self, matrix: Array) -> Array:
        """w_orig = P w_proj per entity row (ProjectionMatrix
        projectCoefficients)."""
        return matrix @ self.matrix.T

    def project_matrix(self, matrix: Array) -> Array:
        """Approximate original->projected coefficient map (warm start only):
        least-squares through P, i.e. w_proj = (P^T P)^-1 P^T w_orig."""
        p = self.matrix
        gram = p.T @ p
        return jnp.linalg.solve(gram, p.T @ matrix.T).T


Projector = object  # IdentityProjector | IndexMapProjector | RandomProjector


def build_projector(
    projector_type: ProjectorType,
    features: Features,
    entity_rows: np.ndarray,
    num_entities: int,
    *,
    projected_dim: Optional[int] = None,
    seed: int = 0,
    host_planes=None,
    want_stats: bool = False,
) -> Projector:
    """RandomEffectProjector.build (RandomEffectProjector.scala:74). The
    default for random-effect coordinates is INDEX_MAP
    (CoordinateDataConfiguration.scala:59-66)."""
    if isinstance(features, SparseFeatures):
        dim = features.dim
    else:
        dim = int(features.shape[-1])
    if projector_type == ProjectorType.IDENTITY:
        return IdentityProjector(dim)
    if projector_type == ProjectorType.RANDOM:
        if projected_dim is None:
            raise ValueError("RANDOM projector requires projected_dim")
        return RandomProjector.build(dim, projected_dim, seed)
    if projector_type == ProjectorType.INDEX_MAP:
        if not isinstance(features, SparseFeatures):
            # Dense shards have nothing to compact per entity; identity.
            return IdentityProjector(dim)
        return IndexMapProjector.build(
            features,
            entity_rows,
            num_entities,
            host_planes=host_planes,
            want_stats=want_stats,
        )
    raise ValueError(f"unknown projector type {projector_type}")


@dataclasses.dataclass
class ProjectedShard:
    """A projected feature shard + its projector, registered on the dataset
    under `shard_name` for the owning random-effect coordinate."""

    shard_name: str
    projector: Projector


def project_shard(
    dataset,
    re_dataset,
    projector_type: ProjectorType,
    *,
    projected_dim: Optional[int] = None,
    seed: int = 0,
    want_stats: bool = False,
) -> ProjectedShard:
    """Create the projected view of `re_dataset`'s feature shard and register
    it on the GameDataset under '<shard>@<re_type>' — the per-coordinate
    projected space of RandomEffectCoordinateInProjectedSpace.scala:31. The
    RandomEffectDataset is repointed at the projected shard; its gather
    blocks are unchanged (projection is per-sample, not per-slot).
    """
    shard = re_dataset.feature_shard
    entity_rows = np.asarray(re_dataset.sample_entity_rows)
    host_planes = getattr(dataset, "host_ell", {}).get(shard)
    # Peek (ShardDict.host_view): projector construction must not force the
    # raw shard's device upload — with host planes the projection runs
    # entirely on host, and only the PROJECTED shard ships to the device.
    feats_src = (
        dataset.peek_shard(shard)
        if hasattr(dataset, "peek_shard")
        else dataset.shards[shard]
    )
    projector = build_projector(
        projector_type,
        feats_src,
        entity_rows,
        re_dataset.num_entities,
        projected_dim=projected_dim,
        seed=seed,
        host_planes=host_planes,
        want_stats=want_stats,
    )
    if isinstance(projector, IdentityProjector):
        return ProjectedShard(shard, projector)
    new_name = f"{shard}@{re_dataset.config.random_effect_type}"
    # Never overwrite an existing projected shard (two coordinates may share
    # (shard, re_type) with different projector configs).
    suffix = 2
    while new_name in dataset.shards:
        new_name = f"{shard}@{re_dataset.config.random_effect_type}#{suffix}"
        suffix += 1
    if isinstance(projector, IndexMapProjector) and host_planes is None:
        # No ingest host copy (hand-built dataset): fall back to reading
        # the (possibly device) arrays once.
        host_planes = (
            np.asarray(feats_src.indices),
            np.asarray(feats_src.values),
        )
    if (
        isinstance(projector, IndexMapProjector)
        and projector._device_mapper is not None
    ):
        # Device-resident path: the projection and the (K, N) transpose
        # run as XLA programs and the projected shard is BORN in device
        # memory — no host planes, no upload stage, bitwise-equal entries.
        # (No host_ell stash: the projected planes have no host consumer —
        # Pearson statistics read the ORIGINAL shard, before repointing.)
        # The build's device-resident planes are reused (take_planes) so
        # the raw ELL ships host->device exactly once.
        from photon_ml_tpu.data import device_assemble

        staged = projector._device_mapper.take_planes()
        src_idx, src_val = staged if staged is not None else (
            host_planes[0],
            host_planes[1],
        )
        out_d, v_d = device_assemble.project_ell_device(
            projector._device_mapper, src_idx, src_val, entity_rows
        )
        idx_t_d, val_t_d = device_assemble.transpose_planes_device(
            out_d, v_d, projector.projected_dim
        )
        dataset.shards[new_name] = SparseFeatures(
            idx_t_d, val_t_d, projector.projected_dim, ell_axis=-2
        )
    elif isinstance(projector, IndexMapProjector):
        # Host-plane path: project on host, stash the projected planes
        # (Pearson stats / downstream host consumers), then upload ONCE in
        # the TRANSPOSED (K, N) block layout — the orientation the
        # entity-block gathers consume directly (gather_block_features), so
        # no per-bucket transpose copies ever materialize on device.
        # Projected dims are small, so indices ship as int16 when they fit
        # (halves the index-plane transfer and HBM residence).
        out, v = projector.project_arrays(
            host_planes[0], host_planes[1], entity_rows
        )
        dataset.host_ell[new_name] = (out, v)
        idx_t = out.T
        if projector.projected_dim < (1 << 15):
            idx_t = idx_t.astype(np.int16)
        projected = SparseFeatures(
            np.ascontiguousarray(idx_t),
            np.ascontiguousarray(v.T),
            projector.projected_dim,
            ell_axis=-2,
        )
        if hasattr(dataset.shards, "prefetch"):
            # Lazy-upload ShardDict: register the HOST planes and let the
            # data-plane pipeline ship them asynchronously (the coordinate-
            # descent loop prefetches coordinate k+1's shard during
            # coordinate k's solve) instead of paying the transfer
            # synchronously inside prepare.
            dataset.shards[new_name] = projected
        else:
            # Plain-dict datasets have no lazy materialization — upload now.
            dataset.shards[new_name] = dataclasses.replace(
                projected,
                indices=jnp.asarray(projected.indices),
                values=jnp.asarray(projected.values),
            )
    else:
        dataset.shards[new_name] = projector.project_features(
            dataset.shards[shard], entity_rows
        )
    re_dataset.config = dataclasses.replace(re_dataset.config, feature_shard=new_name)
    return ProjectedShard(new_name, projector)
