"""GAME datasets: columnar samples + entity-blocked random-effect layout.

Counterpart of photon-api data/ (GameConverters.scala:44-129,
FixedEffectDataset.scala:31-152, RandomEffectDataset.scala:45-466,
RandomEffectDatasetPartitioner.scala:44-171, LocalDataset.scala:35-329,
CoordinateDataConfiguration.scala) and photon-lib data/GameDatum.scala:38.

Structural translation (the central TPU design decision of this framework):

* The reference represents a GAME dataset as RDD[(uid, GameDatum)] and builds
  per-coordinate views by shuffling — groupByKey per entity for random
  effects, with a frequency-balanced partitioner, per-entity reservoir caps,
  and an active (train+score) / passive (score-only) split.

* Here every sample lives at a fixed slot in a device-resident sample axis
  (uid = row index). A fixed-effect view is just (shard features, labels,
  offsets, weights). A random-effect view is built ONCE, host-side, as
  *entity blocks*: entities are bucketed by padded size (power-of-two
  capacities), each bucket holding a (num_entities_in_bucket, bucket_size)
  gather matrix into the sample axis plus a validity mask. Training gathers
  rows into dense (E, S, D) blocks and vmaps the solver; scoring gathers a
  per-sample entity row. The groupByKey shuffle, the partitioner, and the
  MinHeap reservoir all collapse into this one static indexing structure,
  and the per-iteration residual exchange becomes pure gathers/scatters.

* Active/passive: rows beyond a per-entity cap (numActiveDataPointsUpperBound,
  RandomEffectDataset.scala:339-408) are excluded from the gather blocks
  (training) but still scored via the per-sample entity-row index — the
  passive-data path (:410) costs nothing here. The reservoir choice of which
  rows stay active is deterministic per entity (seeded by a stable hash,
  mirroring the byteswap64-keyed heap's fault-tolerance determinism,
  RandomEffectDataset.scala:375-384).
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from typing import Dict, List, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.data.containers import (
    DenseBlock,
    Features,
    LabeledData,
    SparseFeatures,
    annotate_spans,
    dense_block,
    dense_block_admitted,
    dense_block_bytes,
)
from photon_ml_tpu.types import ProjectorType
from photon_ml_tpu.utils import faults

Array = jax.Array

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class FixedEffectDataConfig:
    """FixedEffectDataConfiguration (CoordinateDataConfiguration.scala:37)."""

    feature_shard: str


@dataclasses.dataclass(frozen=True)
class RandomEffectDataConfig:
    """RandomEffectDataConfiguration (CoordinateDataConfiguration.scala:59-66).

    active_upper_bound caps rows per entity used for training (overflow is
    scored only); active_lower_bound drops entities with too few rows from
    training entirely; min_bucket is the smallest padded block size (TPU
    lane-friendly).
    """

    random_effect_type: str
    feature_shard: str
    active_upper_bound: Optional[int] = None
    active_lower_bound: Optional[int] = None
    # Per-entity Pearson feature selection: keep at most
    # ceil(ratio * n_entity_rows) features ranked by |corr(feature, label)|
    # (RandomEffectDataset.featureSelectionOnActiveData:447-465,
    # LocalDataset.stableComputePearsonCorrelationScore:187+). None = off.
    num_features_to_samples_ratio_upper_bound: Optional[float] = None
    min_bucket: int = 8
    # Feature-space projection for the per-entity models; default INDEX_MAP
    # as in the reference (CoordinateDataConfiguration.scala:59-66).
    # projected_dim applies to RANDOM projection only.
    projector_type: ProjectorType = ProjectorType.INDEX_MAP
    projected_dim: Optional[int] = None
    # Upper bound on gather cells (entities x padded capacity) per training
    # block: buckets with more entities split into equal chunks (the last
    # padded with inert dummies so every chunk shares one compiled
    # program). Bounds the transient HBM of the vmapped per-entity solves
    # independently of dataset scale — 2M cells x (K~10 entries x 8 B x
    # ~1.8 tile padding + 12 B labels/offsets/weights) is a few hundred MB
    # per in-flight block.
    max_block_cells: int = 1 << 21


class ShardDict(dict):
    """Feature shards with upload-on-first-use device materialization.

    Ingest stores sparse shards as HOST numpy planes; the first consumer
    that indexes a shard triggers one jnp.asarray per plane and the device
    copy is cached back. Decision-phase consumers (pack/projector gating,
    which only need dtype/dim or read the host planes anyway) peek with
    `host_view` — so a shard whose training runs entirely on the bucketed
    or projected layout NEVER ships its raw ELL to the device (at
    MovieLens-20M scale that is ~1.6 GB of the chip's 16 GB of HBM).

    `prefetch` extends the lazy upload to an ASYNC one: a consumer that
    knows it will need a shard soon (the coordinate-descent loop, before
    solving the previous coordinate; the transformer, before per-
    coordinate prep) starts the upload on a background thread and the
    eventual `__getitem__` joins it instead of faulting synchronously —
    the upload overlaps device solve/host prep. Uploads are
    double-buffered (pipeline.AsyncUploader, max 2 in flight) so host
    staging memory stays bounded.
    """

    _uploader = None  # lazily-built pipeline.AsyncUploader
    # Guards the one-time _uploader creation: two threads prefetching
    # concurrently on a fresh dict must share ONE uploader, or the loser's
    # in-flight future is stranded in an overwritten instance and the
    # consumer re-uploads the same shard in parallel.
    _uploader_init_lock = threading.Lock()

    def _materialize(self, v: SparseFeatures) -> SparseFeatures:
        faults.fault_point("upload")
        return dataclasses.replace(
            v,
            indices=jnp.asarray(v.indices),
            values=jnp.asarray(v.values),
        )

    def prefetch(self, key) -> None:
        """Start the device upload of `key` in the background (no-op when
        the shard is dense, already device-resident, or already in
        flight). Safe to call from any thread."""
        try:
            v = super().__getitem__(key)
        except KeyError:
            return
        if not isinstance(v, SparseFeatures) or isinstance(v.indices, jax.Array):
            return
        if self._uploader is None:
            from photon_ml_tpu.data.pipeline import AsyncUploader

            with ShardDict._uploader_init_lock:
                if self._uploader is None:
                    self._uploader = AsyncUploader()
        self._uploader.submit(key, lambda: self._materialize(v))

    def __getitem__(self, key):
        v = super().__getitem__(key)
        if isinstance(v, SparseFeatures) and not isinstance(v.indices, jax.Array):
            from photon_ml_tpu.utils.observability import stage_timer

            host = v
            fut = (
                self._uploader.pop(key) if self._uploader is not None else None
            )
            if fut is not None:
                # Prefetched: the uploader thread already recorded the
                # upload wall where it ran; the join wait here is the
                # (hopefully ~zero) non-overlapped remainder.
                try:
                    v = fut.result()
                except Exception:
                    # The async path (with its own retries) gave up; the
                    # shard is still needed, so degrade to the synchronous
                    # in-thread path below before surfacing anything.
                    logger.warning(
                        "async upload of shard %r failed; degrading to a "
                        "synchronous upload",
                        key,
                        exc_info=True,
                    )
                    faults.COUNTERS.increment("fallback_sync_uploads")
                    fut = None
            if fut is None:
                with stage_timer("upload"):
                    v = faults.retry(
                        lambda: self._materialize(host),
                        label=f"upload of shard {key!r}",
                    )
            super().__setitem__(key, v)
        return v

    def host_view(self, key):
        """The stored value without triggering a device upload."""
        return super().__getitem__(key)


@dataclasses.dataclass
class HostCSR:
    """Host-side CSR stash from ingest for the data-plane bucketed pack.

    Row-id expansion and the constant intercept column are deferred to
    `to_coo()` (the pack consumer), so the ingest wall never pays the COO
    concatenation — the reference likewise builds its per-partition layout
    once at dataset construction (RandomEffectDataset.scala:229-264).
    """

    indptr: np.ndarray  # (n_rows + 1,) int64
    cols: np.ndarray  # (nnz,) feature ids
    vals: np.ndarray  # (nnz,) float32
    dim: int
    extra_col: Optional[tuple] = None  # (intercept index, value) per row
    # Background bucketed-pack handle (ops/pallas_sparse.begin_pack_async):
    # ingest starts the host-side pack on a thread; the first consuming
    # coordinate joins it via finish_pack.
    pack_future: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False
    )

    def to_coo(self):
        """Expand to (rows, cols, vals, dim) COO triplets."""
        n = len(self.indptr) - 1
        rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(self.indptr))
        cols = self.cols.astype(np.int64, copy=False)
        vals = self.vals
        if self.extra_col is not None:
            rows = np.concatenate([rows, np.arange(n, dtype=np.int64)])
            cols = np.concatenate(
                [cols, np.full(n, self.extra_col[0], np.int64)]
            )
            vals = np.concatenate(
                [vals, np.full(n, self.extra_col[1], np.float32)]
            )
        return rows, cols, vals, self.dim


@dataclasses.dataclass
class GameDataset:
    """Columnar GAME data in fixed sample order (GameDatum.scala:38 columns).

    `id_tags` holds host-side per-sample entity/grouping keys (userId,
    movieId, queryId, ...) — the idTagToValueMap of the reference, columnar.
    """

    shards: Dict[str, Features]
    labels: Array
    offsets: Array
    weights: Array
    id_tags: Dict[str, np.ndarray]
    # Host-side CSR per shard (HostCSR) stashed by the ingest path. Lets the
    # bucketed sparse pack (ops/pallas_sparse maybe_pack) run in the data
    # plane — straight from host arrays, before any device transfer —
    # instead of pulling device ELL arrays back to host. Consumed (popped)
    # by the first coordinate that packs the shard, so the arrays don't pin
    # host RAM for the training run's lifetime. Absent for hand-built
    # datasets.
    host_csr: Dict[str, "HostCSR"] = dataclasses.field(default_factory=dict)
    # Host copies of each shard's ELL planes (indices, values numpy) from
    # ingest. Projector construction and feature statistics read these
    # instead of pulling the device arrays back (np.asarray on a device
    # array is a synchronous device->host copy of all of it). Absent for
    # hand-built datasets (consumers fall back to np.asarray).
    host_ell: Dict[str, tuple] = dataclasses.field(default_factory=dict)
    # Factorized id-tag columns from ingest: tag -> (codes int64 per sample,
    # sorted unique value table). Semantically identical to
    # np.unique(id_tags[tag], return_inverse=True) but computed over the
    # SMALL value table — entity grouping at 10^7 rows skips the
    # n_samples-string sort. Absent for hand-built datasets (consumers fall
    # back to id_tags).
    tag_codes: Dict[str, tuple] = dataclasses.field(default_factory=dict)
    # Pack-once cache: the bucketed layout is a property of the shard data,
    # so reg-weight sweeps / warm-start chains that rebuild coordinates
    # reuse it instead of re-packing per configuration.
    bucketed_cache: Dict[str, object] = dataclasses.field(default_factory=dict)
    # Per-stage ingest breakdown (utils/contracts.INGEST_TIMING_REQUIRED_KEYS)
    # attached by read_game_dataset; empty for hand-built datasets. The
    # bench e2e contract fails loudly when a dataset that came from disk is
    # missing any key.
    ingest_timing: Dict[str, object] = dataclasses.field(default_factory=dict)
    # Zero-weight rows at the end that even the sample axis out over a mesh,
    # made by parallel/mesh.py or stated by a caller that made them itself;
    # they are counted in `num_samples`.
    pad_rows: int = 0

    @property
    def num_samples(self) -> int:
        return int(self.labels.shape[0])

    def peek_shard(self, name: str) -> Features:
        """The shard WITHOUT triggering ShardDict's device materialization —
        the accessor for decision-phase/host-plane consumers (pack gating,
        projector construction, statistics)."""
        shards = self.shards
        if hasattr(shards, "host_view"):
            return shards.host_view(name)
        return shards[name]

    def annotated_shard(self, name: str) -> Features:
        """The shard as scoring and the ELL objective read it: an ELL shard
        with its planes' dense-span annotation (`containers.annotate_spans`:
        one reduction over the arrays and one fetch), made at the first call
        and kept with the data set's other per-shard decisions, so no later
        fit or scoring of these rows reads the spans again. Anything else, and
        a shard with no narrow plane, as `shards[name]` holds it."""
        feats = self.shards[name]
        if not isinstance(feats, SparseFeatures):
            return feats
        key = ("ell_spans", name)
        cached = self.bucketed_cache.get(key)
        if cached is None or cached.indices is not feats.indices:
            cached = self.bucketed_cache[key] = annotate_spans(feats)
        return cached

    def dense_block_shard(self, name: str) -> Optional[DenseBlock]:
        """The ELL shard laid as one `DenseBlock` in its values' dtype, X^T
        of shape (dim, n), where `dense_block_admitted` admits its shapes as one
        entity of all n rows (the random effects' rule: the block within
        DENSE_BLOCK_MAX_BYTES, dim within 128 ids a plane); else None, and
        for a shard whose planes lie on more than one device. Built in one
        program at the first call and kept with the data set's other
        per-shard decisions, so no rebuilt coordinate or later fit lays it
        again. The ingest's CSR stash is dropped, and a pack it started is
        cancelled and never waited on: no bucketed pack is made of it."""
        feats = self.peek_shard(name)
        if not isinstance(feats, SparseFeatures) or feats.ell_axis != -1 or feats.indices.ndim != 2:
            return None
        rows, planes = feats.indices.shape
        if not dense_block_admitted(1, planes, rows, feats.dim):
            return None
        if isinstance(feats.indices, jax.Array) and len(feats.indices.sharding.device_set) != 1:
            return None
        feats = self.shards[name]
        key = ("dense_block", name)
        cached = self.bucketed_cache.get(key)
        if cached is None or cached[0] is not feats.indices:
            csr = self.host_csr.pop(name, None)
            if getattr(csr, "pack_future", None) is not None:
                csr.pack_future.cancel()
            cached = self.bucketed_cache[key] = (feats.indices, _lay_dense_block(feats))
        return cached[1]

    def release_stash(self) -> None:
        """Drop the ingest CSR stash when no coordinate will consume it
        (scoring, validation datasets) — cancelling any background pack
        first so a not-yet-started pack never runs and a discarded one is
        never waited on."""
        for csr in self.host_csr.values():
            fut = getattr(csr, "pack_future", None)
            if fut is not None:
                fut.cancel()
        self.host_csr.clear()

    def labeled_data(self, shard: str, offsets: Optional[Array] = None) -> LabeledData:
        """Fixed-effect view for one feature shard (FixedEffectDataset)."""
        return LabeledData(
            self.shards[shard],
            self.labels,
            self.offsets if offsets is None else offsets,
            self.weights,
        )

    @classmethod
    def build(
        cls,
        shards: Mapping[str, Features],
        labels,
        *,
        offsets=None,
        weights=None,
        id_tags: Optional[Mapping[str, Sequence]] = None,
        dtype=jnp.float32,
    ) -> "GameDataset":
        labels = jnp.asarray(labels, dtype)
        n = labels.shape[0]
        # Defaults are placed as the labels are: sharded beside sharded
        # labels, on their device beside labels another device holds.
        offsets = jnp.zeros_like(labels) if offsets is None else jnp.asarray(offsets, dtype)
        weights = jnp.ones_like(labels) if weights is None else jnp.asarray(weights, dtype)
        tags = {k: np.asarray(v) for k, v in (id_tags or {}).items()}
        for k, v in tags.items():
            if len(v) != n:
                raise ValueError(f"id tag {k!r} has {len(v)} values for {n} samples")
        return cls(ShardDict(shards), labels, offsets, weights, tags)


# One program lays a fixed-effect shard dense: its plane loop is one
# elementwise fusion that writes the block and no (dim, n) intermediate.
_lay_dense_block = jax.jit(dense_block)


def _ell_row_planes(feats: SparseFeatures):
    """Host (N, K) index/value planes regardless of the stored ELL layout."""
    idx = np.asarray(feats.indices)
    val = np.asarray(feats.values)
    if feats.ell_axis == -2:
        idx = np.moveaxis(idx, -1, -2)
        val = np.moveaxis(val, -1, -2)
    return idx, val


def take_rows(dataset: GameDataset, rows) -> GameDataset:
    """Row-subset of a GameDataset, built entirely host-side.

    The incremental-refresh fast path (game/incremental.py) carves the
    changed entities' samples out of a merged dataset with this: shards
    are read through `peek_shard` (no device materialization — the subset
    uploads lazily like any hand-built dataset) and fancy-indexed per
    plane; labels/offsets/weights and every id-tag column slice the same
    `rows`, so the subset preserves sample alignment and relative order.
    """
    rows = np.asarray(rows)
    shards: Dict[str, Features] = {}
    for name in dataset.shards:
        feats = dataset.peek_shard(name)
        if isinstance(feats, SparseFeatures):
            idx, val = _ell_row_planes(feats)
            shards[name] = dataclasses.replace(
                feats, indices=idx[rows], values=val[rows], ell_axis=-1
            )
        else:
            shards[name] = np.asarray(feats)[rows]
    return GameDataset.build(
        shards,
        np.asarray(dataset.labels)[rows],
        offsets=np.asarray(dataset.offsets)[rows],
        weights=np.asarray(dataset.weights)[rows],
        id_tags={k: np.asarray(v)[rows] for k, v in dataset.id_tags.items()},
    )


def concat_datasets(a: GameDataset, b: GameDataset) -> GameDataset:
    """Append dataset `b`'s samples after `a`'s (the merged view a
    streamed delta batch trains against). Shard sets, feature dims, and
    id-tag columns must match; ELL planes pad to the wider K so padding
    slots (value 0.0) stay inert. Built host-side like `take_rows`."""
    if set(a.shards) != set(b.shards):
        raise ValueError(
            f"cannot concat datasets with different shard sets "
            f"{sorted(a.shards)} vs {sorted(b.shards)}"
        )
    if set(a.id_tags) != set(b.id_tags):
        raise ValueError(
            f"cannot concat datasets with different id-tag columns "
            f"{sorted(a.id_tags)} vs {sorted(b.id_tags)}"
        )
    shards: Dict[str, Features] = {}
    for name in a.shards:
        fa, fb = a.peek_shard(name), b.peek_shard(name)
        if isinstance(fa, SparseFeatures) != isinstance(fb, SparseFeatures):
            raise ValueError(f"shard {name!r}: sparse/dense layouts differ")
        if isinstance(fa, SparseFeatures):
            if fa.dim != fb.dim:
                raise ValueError(
                    f"shard {name!r}: dims differ ({fa.dim} vs {fb.dim})"
                )
            ia, va = _ell_row_planes(fa)
            ib, vb = _ell_row_planes(fb)
            k = max(ia.shape[-1], ib.shape[-1])
            ia, va = _pad_ell_k(ia, va, k)
            ib, vb = _pad_ell_k(ib, vb, k)
            shards[name] = dataclasses.replace(
                fa,
                indices=np.concatenate([ia, ib]),
                values=np.concatenate([va, vb]),
                ell_axis=-1,
            )
        else:
            na, nb = np.asarray(fa), np.asarray(fb)
            if na.shape[-1] != nb.shape[-1]:
                raise ValueError(
                    f"shard {name!r}: dims differ "
                    f"({na.shape[-1]} vs {nb.shape[-1]})"
                )
            shards[name] = np.concatenate([na, nb])
    return GameDataset.build(
        shards,
        np.concatenate([np.asarray(a.labels), np.asarray(b.labels)]),
        offsets=np.concatenate([np.asarray(a.offsets), np.asarray(b.offsets)]),
        weights=np.concatenate([np.asarray(a.weights), np.asarray(b.weights)]),
        id_tags={
            k: np.concatenate([np.asarray(a.id_tags[k]), np.asarray(b.id_tags[k])])
            for k in a.id_tags
        },
    )


def _pad_ell_k(idx: np.ndarray, val: np.ndarray, k: int):
    """Widen (N, K0) ELL planes to K columns with inert padding slots."""
    if idx.shape[-1] == k:
        return idx, val
    pad = ((0, 0), (0, k - idx.shape[-1]))
    return (
        np.pad(idx, pad, constant_values=0),
        np.pad(val, pad, constant_values=0.0),
    )


def _row_priorities(codes: np.ndarray, n: int) -> np.ndarray:
    """Deterministic per-(entity, row) reservoir priorities, vectorized.

    splitmix64-style mix of the entity code and the row index — the
    vectorized equivalent of the reference's byteswap64-keyed reservoir
    ordering (RandomEffectDataset.scala:375-384): each over-cap entity keeps
    the `cap` rows with the smallest priorities, a choice that is uniform,
    deterministic per entity, and independent of other entities."""
    x = codes.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    x += np.arange(n, dtype=np.uint64) * np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(30)
    x *= np.uint64(0xBF58476D1CE4E5B9)
    x ^= x >> np.uint64(27)
    x *= np.uint64(0x94D049BB133111EB)
    x ^= x >> np.uint64(31)
    return x


class EntityBlocks:
    """One padded bucket of entities with equal block capacity."""

    def __init__(self, gather: np.ndarray, mask: np.ndarray, entity_rows: np.ndarray):
        self.gather = jnp.asarray(gather, jnp.int32)  # (E, S) sample rows
        self.mask = jnp.asarray(mask, jnp.float32)  # (E, S)
        self.entity_rows = jnp.asarray(entity_rows, jnp.int32)  # (E,)

    @property
    def num_entities(self) -> int:
        return int(self.gather.shape[0])

    @property
    def capacity(self) -> int:
        return int(self.gather.shape[1])


@dataclasses.dataclass
class RandomEffectDataset:
    """Entity-blocked view of a GameDataset for one random-effect coordinate.

    - `entity_index`: host map entity key -> row in the coefficient matrix.
    - `buckets`: padded gather blocks for training (active data only).
    - `sample_entity_rows`: per-sample coefficient row for scoring; unseen
      entities point at row `num_entities` (the pinned zero row).
    """

    config: RandomEffectDataConfig
    entity_index: Dict[object, int]
    buckets: List[EntityBlocks]
    sample_entity_rows: Array  # (N,) int32
    num_active_samples: int
    num_passive_samples: int
    # (num_entities + 1, D) 0/1 multipliers when Pearson feature selection is
    # on; None otherwise. The +1 row (unseen entities) is all-ones. Training
    # multiplies gathered blocks by the owning entity's row, so deselected
    # features contribute no data signal and their (zero-init) coefficients
    # stay exactly zero under L2 — scoring with full features is then safe.
    feature_mask: Optional[Array] = None

    @property
    def num_entities(self) -> int:
        return len(self.entity_index)

    @property
    def feature_shard(self) -> str:
        return self.config.feature_shard


def build_random_effect_dataset(
    dataset: GameDataset, config: RandomEffectDataConfig
) -> RandomEffectDataset:
    """Stage-timed entry: records the build under the `re_build` stage of
    the ambient scope (GameEstimator's fit breakdown) wherever it runs —
    main thread or a prepare-pipeline worker."""
    from photon_ml_tpu.utils.observability import stage_timer

    with stage_timer("re_build"):
        return _build_random_effect_dataset(dataset, config)


def _build_random_effect_dataset(
    dataset: GameDataset, config: RandomEffectDataConfig
) -> RandomEffectDataset:
    """Host-side one-time construction of the entity-blocked layout.

    Replaces RandomEffectDataset builder + partitioner + reservoir
    (RandomEffectDataset.scala:230-447, RandomEffectDatasetPartitioner
    .scala:118-136): bucketing by padded size is the load-balancing here —
    within a bucket every entity costs identical FLOPs, so there is no
    straggler problem to partition around.
    """
    tag = config.random_effect_type
    if tag not in dataset.id_tags:
        raise ValueError(f"id tag {tag!r} not present in dataset")
    keys = dataset.id_tags[tag]
    n = len(keys)

    # Group sample rows by entity: ONE unique pass yields both the sorted
    # entity vocabulary and each sample's entity code — everything after
    # this runs as bulk argsort/segment ops (the former per-entity Python
    # loop was a large share of e2e prepare wall; VERDICT r04 item 2).
    # Ingest-factorized columns (tag_codes) shortcut the n-string sort:
    # only the small value table is sorted, then codes remap through it.
    ct = getattr(dataset, "tag_codes", {}).get(tag)
    if ct is not None:
        raw_codes, tbl = ct
        used = np.zeros(len(tbl), bool)
        used[raw_codes] = True
        remap = np.cumsum(used) - 1
        uniq = tbl[used]
        codes = remap[raw_codes]
    else:
        uniq, codes = np.unique(keys, return_inverse=True)
    num_entities = len(uniq)
    counts = np.bincount(codes, minlength=num_entities)
    entity_index: Dict[object, int] = {
        (k.item() if hasattr(k, "item") else k): i for i, k in enumerate(uniq)
    }
    entity_rows_of_sample = codes.astype(np.int64)

    lower = config.active_lower_bound or 0
    cap = config.active_upper_bound

    # Active rows per entity, sorted by (entity, row). Over-cap entities
    # keep the `cap` rows with the smallest deterministic hash priorities
    # (see _row_priorities) — the reference's keyed-reservoir semantics,
    # vectorized.
    a_counts = counts.copy()
    if lower:
        a_counts[counts < lower] = 0
    if cap is not None:
        np.minimum(a_counts, cap, out=a_counts)
    need_reservoir = cap is not None and bool((counts > cap).any())
    num_active = int(a_counts.sum())

    kept = np.nonzero(a_counts > 0)[0]  # entity code per kept entity
    kept_sizes = a_counts[kept]

    # Device-resident assembly (data/device_assemble.py): the n-sized sort/
    # rank/scatter sequence runs as XLA programs and the gather blocks are
    # BORN on the device that trains from them; the host path below stays
    # the bitwise-identical fallback (and the only path when the Pearson
    # feature selection needs host per-entity row lists).
    from photon_ml_tpu.data import device_assemble
    from photon_ml_tpu.utils.observability import record_stage, set_stage_note

    use_device = (
        device_assemble.enabled()
        and config.num_features_to_samples_ratio_upper_bound is None
        and n < 2**31
        and len(kept) > 0
    )
    t_assembly = time.perf_counter()
    assembler = None
    active_rows = None
    if use_device:
        assembler = device_assemble.BlockAssembler(
            codes,
            a_counts,
            counts,
            num_active,
            _row_priorities(codes, n) if need_reservoir else None,
        )
    else:
        if need_reservoir:
            order = np.lexsort((_row_priorities(codes, n), codes))
        else:
            order = np.argsort(codes, kind="stable")  # row-ascending per entity
        if need_reservoir or lower or cap is not None:
            starts1 = np.zeros(num_entities + 1, np.int64)
            np.cumsum(counts, out=starts1[1:])
            rank = np.arange(n, dtype=np.int64) - starts1[codes[order]]
            active_rows = order[rank < a_counts[codes[order]]]
            if need_reservoir:
                # Restore row-ascending order within each entity for the
                # gathers.
                active_rows = active_rows[
                    np.lexsort((active_rows, codes[active_rows]))
                ]
        else:
            active_rows = order

    # Bucket by padded capacity (power of two >= size, floor min_bucket).
    min_b = max(config.min_bucket, 1)
    pows = min_b * (1 << np.arange(0, 40, dtype=np.int64))
    pows = pows[pows < (1 << 40)]
    cap_of_kept = pows[np.searchsorted(pows, kept_sizes)]

    # Per-active-row bookkeeping: owning kept-entity ordinal and position
    # within that entity's active rows. (E-sized planning is host either
    # way; only the num_active-sized expansions stay host-path-only.)
    a_starts = np.zeros(len(kept) + 1, np.int64)
    np.cumsum(kept_sizes, out=a_starts[1:])
    if assembler is None:
        row_kept_ord = np.repeat(
            np.arange(len(kept), dtype=np.int64), kept_sizes
        )
        row_pos = np.arange(num_active, dtype=np.int64) - a_starts[row_kept_ord]

    buckets = []
    for capacity in np.unique(cap_of_kept) if len(kept) else []:
        members = np.nonzero(cap_of_kept == capacity)[0]
        e = len(members)
        local = np.full(len(kept), -1, np.int64)
        local[members] = np.arange(e)
        ent_rows = kept[members]
        max_e = max(1, int(config.max_block_cells) // int(capacity))
        # Canonical entity counts: each chunk holds either max_e entities
        # or the next power of two >= its entity count, padded with inert
        # dummies (gather row 0, mask 0, entity row = the pinned zero row
        # num_entities). Every (capacity, E) bucket shape then comes from a
        # SMALL discrete set, so the per-bucket train programs compile once
        # and are reused across buckets, chunks, and coordinates (each XLA
        # compile costs seconds on the chip's compiler; a GLMix fit
        # had ~70). Dummy scatters land on the zero row, which training
        # re-zeroes at the end.
        n_chunks = -(-e // max_e)
        if n_chunks == 1:
            target = 8
            while target < e:
                target *= 2
            target = min(target, max_e)
        else:
            target = max_e
        pad_e = n_chunks * target - e
        if assembler is not None:
            # One scatter program per bucket shape, padded rows included —
            # the blocks materialize directly in device memory.
            gather, mask = assembler.bucket_blocks(
                a_starts, local, e + pad_e, int(capacity)
            )
        else:
            in_bucket = local[row_kept_ord] >= 0
            gather = np.zeros((e, int(capacity)), np.int64)
            mask = np.zeros((e, int(capacity)), np.float32)
            li = local[row_kept_ord[in_bucket]]
            pj = row_pos[in_bucket]
            gather[li, pj] = active_rows[in_bucket]
            mask[li, pj] = 1.0
            if pad_e:
                gather = np.concatenate(
                    [gather, np.zeros((pad_e, int(capacity)), np.int64)]
                )
                mask = np.concatenate(
                    [mask, np.zeros((pad_e, int(capacity)), np.float32)]
                )
        if pad_e:
            ent_rows = np.concatenate(
                [ent_rows, np.full(pad_e, num_entities, np.int64)]
            )
        for c in range(n_chunks):
            sl = slice(c * target, (c + 1) * target)
            buckets.append(EntityBlocks(gather[sl], mask[sl], ent_rows[sl]))
    record_stage(
        "re_device" if assembler is not None else "re_host",
        time.perf_counter() - t_assembly,
    )
    set_stage_note("re_path", "device" if assembler is not None else "host")

    feature_mask = None
    if config.num_features_to_samples_ratio_upper_bound is not None:
        # The Pearson path iterates per entity anyway; materialize the
        # per-entity row lists only here.
        active_lists = np.split(active_rows, a_starts[1:-1])
        feature_mask = _pearson_feature_masks(
            dataset,
            config,
            active_lists,
            list(kept),
            num_entities,
        )

    return RandomEffectDataset(
        config=config,
        entity_index=entity_index,
        buckets=buckets,
        sample_entity_rows=jnp.asarray(entity_rows_of_sample, jnp.int32),
        num_active_samples=num_active,
        num_passive_samples=n - num_active,
        feature_mask=feature_mask,
    )


def _pearson_feature_masks(
    dataset: GameDataset,
    config: RandomEffectDataConfig,
    active_lists: List[np.ndarray],
    kept_entities: List[int],
    num_entities: int,
) -> Array:
    """Per-entity 0/1 feature masks by |Pearson corr(feature, label)|.

    Mirrors featureSelectionOnActiveData (RandomEffectDataset.scala:447-465):
    keep ceil(ratio * n_rows) features per entity, ranked by |Pearson|;
    constant-one columns (the intercept pseudo-feature) score 1.0 so they are
    always retained, as in stableComputePearsonCorrelationScore's intercept
    handling.
    """
    ratio = config.num_features_to_samples_ratio_upper_bound
    # Peek (ShardDict.host_view): the sparse branch reads host_ell planes
    # and needs only dim/isinstance — never force the raw ELL upload here.
    features = (
        dataset.peek_shard(config.feature_shard)
        if hasattr(dataset, "peek_shard")
        else dataset.shards[config.feature_shard]
    )
    labels_np = np.asarray(dataset.labels)
    if isinstance(features, SparseFeatures):
        # Moments straight from the ELL (indices, values) entries — absent
        # entries are zeros, so column sums over nnz entries give the full
        # statistics without materializing an (n_rows, dim) matrix (the
        # reference's stableComputePearsonCorrelationScore likewise streams
        # over sparse entries; densifying at dim ~ 1e5-1e6 would allocate
        # gigabytes per entity).
        dim = features.dim
        planes = getattr(dataset, "host_ell", {}).get(config.feature_shard)
        if planes is not None:  # ingest host copy: no device pull
            ell_idx = planes[0]
            ell_val = np.asarray(planes[1], np.float64)
        else:
            ell_idx = np.asarray(features.indices)
            ell_val = np.asarray(features.values, np.float64)

        def entity_corr(rows: np.ndarray, y: np.ndarray) -> np.ndarray:
            n_rows = len(rows)
            idx = ell_idx[rows].ravel()
            val = ell_val[rows]
            # Padding entries are (index 0, value 0): inert in the value sums;
            # the nnz count masks them out of presence-based terms.
            present = (val != 0).ravel().astype(np.float64)
            sum_x = np.bincount(idx, weights=val.ravel(), minlength=dim)
            cnt = np.bincount(idx, weights=present, minlength=dim)
            mean_x = sum_x / n_rows
            # Centered (two-pass) moments, matching the dense branch's
            # numerics (the reference's stableComputePearsonCorrelationScore
            # exists precisely to avoid raw-moment cancellation):
            #   x_ss = sum_nz (x - mx)^2 + (n - nnz) * mx^2
            #   cov  = sum_nz (x - mx) yc + mx * sum_nz yc
            # (absent entries contribute (0 - mx) yc, and sum_all yc = 0
            # folds their total into + mx * sum_nz yc analytically).
            yc = y - y.mean()
            y_ss = float(yc @ yc)
            dev = (val.ravel() - mean_x[idx]) * present
            x_ss = np.bincount(idx, weights=dev * dev, minlength=dim)
            x_ss = x_ss + (n_rows - cnt) * mean_x * mean_x
            ycb = np.broadcast_to(yc[:, None], val.shape).ravel()
            cov = np.bincount(
                idx, weights=dev * ycb, minlength=dim
            ) + mean_x * np.bincount(idx, weights=ycb * present, minlength=dim)
            denom = np.sqrt(x_ss * y_ss)
            with np.errstate(invalid="ignore", divide="ignore"):
                corr = np.where(denom > 0, np.abs(cov) / np.where(denom > 0, denom, 1.0), 0.0)
            # Intercept: constant-one column (value 1 in every row) scores 1.0.
            is_ones = (cnt == n_rows) & (sum_x == n_rows)
            return np.where(is_ones & (x_ss <= 1e-9 * n_rows), 1.0, corr)

    else:
        feats_np = np.asarray(features)
        dim = feats_np.shape[-1]

        def entity_corr(rows: np.ndarray, y: np.ndarray) -> np.ndarray:
            X = feats_np[rows].astype(np.float64)
            Xc = X - X.mean(axis=0)
            yc = y - y.mean()
            x_std = np.sqrt((Xc * Xc).sum(axis=0))
            y_std = np.sqrt((yc * yc).sum())
            denom = x_std * y_std
            with np.errstate(invalid="ignore", divide="ignore"):
                corr = np.where(
                    denom > 0, np.abs(Xc.T @ yc) / np.where(denom > 0, denom, 1.0), 0.0
                )
            # Intercept: constant-one column scores 1.0 (always kept).
            return np.where(
                (x_std == 0) & (X[0] == 1.0) & (np.ptp(X, axis=0) == 0), 1.0, corr
            )

    masks = np.ones((num_entities + 1, dim), np.float32)
    for rows, row_id in zip(active_lists, kept_entities):
        n_rows = len(rows)
        keep = int(np.ceil(ratio * n_rows))
        if keep >= dim:
            continue
        corr = entity_corr(rows, labels_np[rows].astype(np.float64))
        keep_idx = np.argpartition(corr, -keep)[-keep:]
        row_mask = np.zeros(dim, np.float32)
        row_mask[keep_idx] = 1.0
        masks[row_id] = row_mask
    return jnp.asarray(masks)


def gather_block_features(features: Features, gather: Array) -> Features:
    """Materialize per-bucket feature blocks: (E, S, D) dense or (E, K, S)
    transposed ELL.

    Sparse blocks are built in the TRANSPOSED layout (ell_axis=-2): the
    gather runs over the per-sample planes' transpose, so no (E, S, K)
    array — whose K-minor dimension XLA pads to 128 lanes, a measured
    14.2x expansion at MovieLens-20M scale — ever materializes.
    """
    if isinstance(features, SparseFeatures):
        if features.ell_axis == -2:
            # Projected shards are stored (K, N) already — gather directly.
            idx_t, val_t = features.indices, features.values
        else:
            idx_t = features.indices.T  # (K, N); minor axis = sample axis
            val_t = features.values.T
        return SparseFeatures(
            jnp.swapaxes(jnp.take(idx_t, gather, axis=1), 0, 1),
            jnp.swapaxes(jnp.take(val_t, gather, axis=1), 0, 1),
            features.dim,
            ell_axis=-2,
        )
    return jnp.take(features, gather, axis=0)


def block_layout(features: Features, entities: int, capacity: int) -> str:
    """How the solves of a bucket of `entities` blocks of `capacity` rows
    read them: "dense" (a dense shard's (E, S, D) rows, or an ELL shard's
    laid as a `DenseBlock` where `dense_block_admitted`) or "sparse" (the ELL
    block, (E, K, S)). From static shapes alone, so the scan sweep, the
    bucket loop and a count on the host decide alike."""
    if not isinstance(features, SparseFeatures):
        return "dense"
    planes = features.indices.shape[features.ell_axis]
    admitted = dense_block_admitted(entities, planes, capacity, features.dim)
    return "dense" if admitted else "sparse"


def block_layout_note(features: Features, buckets: Sequence["EntityBlocks"]) -> dict:
    """A random effect's buckets by layout, for the run profile (stage note
    `re_block_layout`): each layout's bucket count, the distinct (entities,
    capacity, layout) shapes, and the bytes of its widest `DenseBlock` (0
    where no ELL bucket is laid dense)."""
    layouts = [block_layout(features, b.num_entities, b.capacity) for b in buckets]
    shapes = sorted({(b.num_entities, b.capacity, lay) for b, lay in zip(buckets, layouts)})
    widest = 0
    if isinstance(features, SparseFeatures):
        dense = [(e, s) for e, s, lay in shapes if lay == "dense"]
        widest = max((dense_block_bytes(e, features.dim, s) for e, s in dense), default=0)
    return {
        "layout": {lay: layouts.count(lay) for lay in sorted(set(layouts))},
        "shapes": [list(shape) for shape in shapes],
        "dense_bytes": widest,
    }


def gather_block_arrays(
    features: Features,
    labels: Array,
    weights: Array,
    offs: Array,
    gather: Array,
    mask: Array,
    ent_rows: Array,
    feature_mask: Optional[Array],
) -> LabeledData:
    """Array-level core of `gather_block_data`: build one bucket's
    (E, S, ...) LabeledData from raw (possibly traced) arrays. Trace-safe —
    the scan-dispatched sweep (game/coordinate.py) runs it INSIDE its scan
    body, so both code paths share one definition and cannot drift.

    An ELL bucket whose shapes admit it (`block_layout`) is laid dense
    here, once a bucket: the solve's every pass then reads the (E, D, S)
    block once, where the ELL block costs a gather (or a scatter-add) a
    plane."""
    feats = gather_block_features(features, gather)
    if isinstance(feats, SparseFeatures) and block_layout(features, *gather.shape) == "dense":
        feats = dense_block(feats)
    if feature_mask is not None:
        block_mask = jnp.take(feature_mask, ent_rows, axis=0)  # (E, D)
        if isinstance(feats, DenseBlock):
            feats = DenseBlock(feats.xt * block_mask[..., None])
        elif isinstance(feats, SparseFeatures):
            mult = jax.vmap(lambda m, idx: m[idx])(block_mask, feats.indices)
            feats = dataclasses.replace(feats, values=feats.values * mult)
        else:
            feats = feats * block_mask[:, None, :]
    return LabeledData(
        features=feats,
        labels=jnp.take(labels, gather, axis=0),
        offsets=jnp.take(offs, gather, axis=0),
        weights=jnp.take(weights, gather, axis=0) * mask,
    )


def gather_block_data(
    dataset: GameDataset,
    shard: str,
    blocks: EntityBlocks,
    offsets: Optional[Array] = None,
    feature_mask: Optional[Array] = None,
) -> LabeledData:
    """Build the (E, S, ...) LabeledData blocks for one bucket. Offsets default
    to the dataset's; pass per-sample residual-adjusted offsets during
    coordinate descent. Padding slots get weight 0 (mask folded into weights).

    `feature_mask` is the RandomEffectDataset's per-entity (E_total+1, D)
    Pearson-selection matrix; the bucket's rows are gathered and multiplied
    into the features so deselected columns carry no data signal.
    """
    return gather_block_arrays(
        dataset.shards[shard],
        dataset.labels,
        dataset.weights,
        dataset.offsets if offsets is None else offsets,
        blocks.gather,
        blocks.mask,
        blocks.entity_rows,
        feature_mask,
    )
