"""Device-resident random-effect assembly: entity blocks and index-map
projection as stable-sort + segment-offset + scatter XLA programs.

Why: at MovieLens-20M scale the prepare wall (BENCH_r05: 468.9 s against
197.9 s of solve) is dominated by two host loops over the 20M-row sample
axis — the entity-block build in `data/game_dataset.py` (argsort/lexsort
of the entity codes, per-bucket boolean masks and fancy-indexing
scatters) and the `game/projector.py` index-map pass (np.unique over the
~160M packed (entity, feature) keys plus a searchsorted rewrite of every
ELL entry). Each step is a primitive the accelerator streams at HBM rate,
and it is the SAME counting-sort/scatter machinery `data/device_pack.py`
shipped for the bucketed pack (PR 6): stable sort by an integer key,
rank = index - segment start, scatter to unique destinations. So the
assembly moves where the data is going anyway — the gather blocks and
projected planes are produced ON the device the training programs consume
them from, and the 20M-row host passes disappear from prepare.

Placement parity (the contract every mode of this repo holds): stable
sorts are uniquely determined permutations, segment offsets are integer
arithmetic, and every scatter destination is unique — so the device
arrays are BITWISE identical to the host path's, which stays as the
fallback (tests/test_device_assemble.py pins device == host on reservoir
caps, lower bounds, chunked buckets, and unseen-entity projection).

Backend gate: `enabled()` is auto-on when an accelerator backend is
attached (same policy as device_pack — a CPU "device" is the host by
another name). PHOTON_DEVICE_ASSEMBLY=1 forces it on any backend (tests
run the CPU jit path), =0 disables. The index-map programs additionally
require the packed (entity, feature) key space to fit int32 addressing
(`projector_supported`); shapes beyond it keep the host path.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

_INT32_LIMIT = 2**31 - 1


def enabled() -> bool:
    """Planned quantity (ISSUE 14): explicit PHOTON_DEVICE_ASSEMBLY wins,
    else the installed plan's assembly_routing (adopted from the
    profile's measured re_path), else the backend auto policy — the
    device and host assembly paths are bitwise-identical either way."""
    from photon_ml_tpu import planner

    routing = str(planner.planned_value("assembly_routing"))
    if routing == "host":
        return False
    if routing == "device":
        return True
    return jax.default_backend() in ("tpu", "gpu")


# ---------------------------------------------------------------------------
# Entity-block assembly (device counterpart of the host loops in
# data/game_dataset._build_random_effect_dataset)


@functools.partial(
    jax.jit, static_argnames=("num_active", "reservoir", "select")
)
def _active_rows_device(
    codes: Array,
    prio_hi: Array,
    prio_lo: Array,
    a_counts: Array,
    starts1: Array,
    *,
    num_active: int,
    reservoir: bool,
    select: bool,
) -> Array:
    """Active sample rows in (entity, row-ascending) order — the device
    re-expression of the host order/rank/boolean-filter sequence.

    np.lexsort((prio, codes)) == stable sort by prio then stable sort by
    codes (LSD passes); the uint64 priorities ride as (hi, lo) uint32
    planes so the program never needs x64. Compaction to the statically
    known `num_active` uses the stable-argsort-of-the-drop-flag trick
    (actives keep their relative order, exactly like boolean indexing).
    """
    n = codes.shape[0]
    if reservoir:
        o = jnp.argsort(prio_lo, stable=True)
        o = o[jnp.argsort(prio_hi[o], stable=True)]
        order = o[jnp.argsort(codes[o], stable=True)]
    else:
        order = jnp.argsort(codes, stable=True)
    if not select:
        return order.astype(jnp.int32)
    codes_s = codes[order]
    rank = jnp.arange(n, dtype=jnp.int32) - starts1[codes_s]
    drop = rank >= a_counts[codes_s]
    active = order[jnp.argsort(drop, stable=True)[:num_active]]
    if reservoir:
        # Restore row-ascending order within each entity for the gathers
        # (the host's lexsort((active_rows, codes[active_rows]))).
        s1 = jnp.argsort(active, stable=True)
        active = active[s1][jnp.argsort(codes[active[s1]], stable=True)]
    return active.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("e_pad", "capacity"))
def _bucket_scatter_device(
    active: Array,
    a_starts: Array,
    local: Array,
    *,
    e_pad: int,
    capacity: int,
) -> Tuple[Array, Array]:
    """One capacity bucket's (e_pad, capacity) gather/mask blocks.

    row_kept_ord comes from a searchsorted over the kept-entity segment
    starts (== np.repeat over the segment lengths), row positions from the
    segment offsets, and the placement is one scatter to unique
    destinations; pad rows (inert dummies) stay all-zero, as on host.
    """
    a = active.shape[0]
    seg = (
        jnp.searchsorted(a_starts, jnp.arange(a, dtype=jnp.int32), side="right")
        - 1
    ).astype(jnp.int32)
    pos = jnp.arange(a, dtype=jnp.int32) - a_starts[seg]
    li = local[seg]
    in_bucket = li >= 0
    oob = jnp.int32(e_pad * capacity)
    dst = jnp.where(in_bucket, li * jnp.int32(capacity) + pos, oob)
    gather = (
        jnp.zeros((e_pad * capacity,), jnp.int32)
        .at[dst]
        .set(active, mode="drop")
        .reshape(e_pad, capacity)
    )
    mask = (
        jnp.zeros((e_pad * capacity,), jnp.float32)
        .at[dst]
        .set(1.0, mode="drop")
        .reshape(e_pad, capacity)
    )
    return gather, mask


class BlockAssembler:
    """Device-side assembly context for one random-effect coordinate.

    Holds the active-row array on device; `bucket_blocks` scatters each
    capacity bucket's padded gather/mask blocks from it. All heavy inputs
    ship once (codes + optional priority planes); per-bucket programs read
    only the (num_active,) active array plus E-sized planning arrays.
    """

    def __init__(
        self,
        codes: np.ndarray,
        a_counts: np.ndarray,
        counts: np.ndarray,
        num_active: int,
        need_reservoir: bool,
        priorities: Optional[np.ndarray],
    ):
        n = len(codes)
        if n >= _INT32_LIMIT:  # pragma: no cover - 2^31-row dataset
            raise ValueError("device assembly requires n < 2^31 rows")
        starts1 = np.zeros(len(counts) + 1, np.int64)
        np.cumsum(counts, out=starts1[1:])
        select = num_active != n
        if priorities is not None:
            hi = (priorities >> np.uint64(32)).astype(np.uint32)
            lo = priorities.astype(np.uint32)
        else:
            hi = lo = np.zeros(0, np.uint32)
        self.active = _active_rows_device(
            jnp.asarray(codes, jnp.int32),
            jnp.asarray(hi),
            jnp.asarray(lo),
            jnp.asarray(a_counts, jnp.int32),
            jnp.asarray(starts1, jnp.int32),
            num_active=int(num_active),
            reservoir=need_reservoir,
            select=select or need_reservoir,
        )

    def bucket_blocks(
        self,
        a_starts: np.ndarray,
        local: np.ndarray,
        e_pad: int,
        capacity: int,
    ) -> Tuple[Array, Array]:
        return _bucket_scatter_device(
            self.active,
            jnp.asarray(a_starts, jnp.int32),
            jnp.asarray(local, jnp.int32),
            e_pad=int(e_pad),
            capacity=int(capacity),
        )


# ---------------------------------------------------------------------------
# Index-map projection (device counterpart of game/projector.py's
# IndexMapProjector.build + project_arrays host sweeps)


def projector_supported(num_entities: int, dim: int) -> bool:
    """The packed (entity, feature) key — ent * (dim + 1) + idx, with the
    unseen-entity row included — must fit int32 (x64 is off on every
    backend this runs on). Shapes beyond it keep the host path."""
    return (num_entities + 1) * (dim + 1) <= _INT32_LIMIT


@functools.partial(jax.jit, static_argnames=("dimw", "num_entities"))
def _sort_pair_keys(
    idx: Array,
    val: Array,
    ent: Array,
    *,
    dimw: int,
    num_entities: int,
):
    """Sort the packed (entity, feature) keys of every nonzero ELL entry;
    masked entries (zero value / out-of-range entity) sort last as the
    sentinel key. Returns (sorted keys, first-occurrence flags, n_unique).

    The (N, K) planes are flattened through their (K, N) transposes (the
    keys are sorted next, so the order they are listed in is immaterial):
    XLA's TPU compiler takes minutes over a reshape that flattens a long,
    narrow (N, K) array, and under a second over its transpose.
    """
    idx_t, val_t = idx.T, val.T
    ent_b = jnp.broadcast_to(ent[None, :], idx_t.shape).reshape(-1)
    idx_f = idx_t.reshape(-1).astype(jnp.int32)
    val_f = val_t.reshape(-1)
    keep = (val_f != 0.0) & (ent_b < num_entities)
    sentinel = jnp.int32(num_entities * dimw)
    keys = jnp.where(
        keep, ent_b.astype(jnp.int32) * jnp.int32(dimw) + idx_f, sentinel
    )
    skeys = jnp.sort(keys)
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), skeys[:-1]])
    first = (skeys != prev) & (skeys != sentinel)
    return skeys, first, jnp.sum(first.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("n_unique", "num_entities", "dimw"))
def _compact_pairs(
    skeys: Array, first: Array, *, n_unique: int, num_entities: int, dimw: int
):
    """Compact the sorted keys to the (statically known) unique set, in
    order, plus the per-entity distinct-feature counts."""
    keys_u = skeys[jnp.argsort(~first, stable=True)[:n_unique]]
    pair_ent = keys_u // jnp.int32(dimw)
    counts = jax.ops.segment_sum(
        jnp.ones((n_unique,), jnp.int32), pair_ent, num_segments=num_entities
    )
    return keys_u, counts


@functools.partial(
    jax.jit, static_argnames=("num_entities", "d_proj", "dimw")
)
def _build_tables(
    keys_u: Array, *, num_entities: int, d_proj: int, dimw: int
) -> Array:
    """Scatter the sorted unique pairs into the (E + 1, d_proj) slot
    tables (slot j of entity e = its j-th distinct global index)."""
    pair_ent = keys_u // jnp.int32(dimw)
    pair_idx = keys_u - pair_ent * jnp.int32(dimw)
    starts = jnp.searchsorted(
        pair_ent, jnp.arange(num_entities, dtype=jnp.int32)
    ).astype(jnp.int32)
    slot = jnp.arange(keys_u.shape[0], dtype=jnp.int32) - starts[pair_ent]
    return (
        jnp.full((num_entities + 1, d_proj), -1, jnp.int32)
        .at[pair_ent, slot]
        .set(pair_idx)
    )


@functools.partial(jax.jit, static_argnames=("dimw",))
def _project_entries(
    keys_u: Array,
    offsets: Array,
    idx: Array,
    val: Array,
    ent: Array,
    *,
    dimw: int,
) -> Tuple[Array, Array]:
    """Rewrite global ELL indices to per-entity local slots — the device
    twin of IndexMapProjector.project_arrays: one searchsorted of every
    entry's packed key into the sorted unique-pair keys; misses (value-0
    padding, unseen entities) zero out exactly as on host.

    Works on the (K, N) transposes of the planes and transposes the
    results back — the same integer ops on the same entries, so the output
    is unchanged. Flattening or gathering through a long, narrow (N, K)
    array costs XLA's TPU compiler minutes (271 s for this program at
    2M x 9 on the v5e); through (K, N) it costs a second."""
    idx_t, val_t = idx.T, val.T
    entry_keys = ent[None, :].astype(jnp.int32) * jnp.int32(dimw) + idx_t.astype(
        jnp.int32
    )
    u = keys_u.shape[0]
    pos = jnp.searchsorted(keys_u, entry_keys.reshape(-1)).reshape(
        entry_keys.shape
    )
    pos_c = jnp.minimum(pos, max(u - 1, 0))
    if u:
        hit = (keys_u[pos_c] == entry_keys) & (val_t != 0.0)
    else:
        hit = jnp.zeros(entry_keys.shape, bool)
    local = pos_c - offsets[ent][None, :]
    out = jnp.where(hit, local, 0).astype(jnp.int32)
    vout = jnp.where(hit, val_t, 0.0).astype(val.dtype)
    return out.T, vout.T


@functools.partial(jax.jit, static_argnames=("int16_idx",))
def _transpose_planes(out: Array, vout: Array, *, int16_idx: bool):
    """(N, K) projected planes -> contiguous (K, N) block layout (the
    orientation gather_block_features consumes), int16 indices when the
    projected space fits."""
    idx_t = out.T
    if int16_idx:
        idx_t = idx_t.astype(jnp.int16)
    return idx_t, vout.T


class DeviceIndexMapper:
    """Device-side state of one IndexMapProjector: the sorted unique pair
    keys and per-entity segment offsets, kept on device so every later
    projection (training shard, validation data) is one program."""

    def __init__(self, keys_u: Array, offsets: Array, dimw: int, d_proj: int):
        self.keys_u = keys_u
        self.offsets = offsets  # (E + 2,) int32: per-entity starts + total
        self.dimw = dimw
        self.d_proj = d_proj
        # The build's device-resident source planes, held ONCE for the
        # immediately-following training-shard projection (a second
        # host->device copy of ~160M entries at MovieLens scale would give
        # back part of the win). take_planes() pops them so the projector
        # object never pins the raw ELL in device memory afterwards.
        self._pending_planes: Optional[Tuple[Array, Array]] = None

    def take_planes(self) -> Optional[Tuple[Array, Array]]:
        planes = self._pending_planes
        self._pending_planes = None
        return planes


def build_index_mapper(
    idx: np.ndarray,
    val: np.ndarray,
    ent: np.ndarray,
    num_entities: int,
    dim: int,
    *,
    pad_multiple: int = 8,
    want_stats: bool = False,
):
    """Device build of the index-map projector. Returns (slot_tables
    HOST int64 — downstream consumers save/score through them on host —,
    DeviceIndexMapper, stats-or-None), or None when unsupported.

    Two small host syncs: the unique-pair count (shapes the compaction)
    and the per-entity counts (shape the tables); everything nnz-sized
    stays on device.
    """
    if not projector_supported(num_entities, dim):
        return None
    dimw = dim + 1
    idx_d = jnp.asarray(idx)
    val_d = jnp.asarray(val)
    ent_d = jnp.asarray(ent, jnp.int32)
    stats_arrays = None
    if want_stats:
        # Fused auxiliary pass: the feature summary reads the SAME
        # device-resident planes the key sort just shipped — one upload
        # and one sweep feed both the projector build and the
        # normalization statistics. The ops are stats.summarize's own
        # (eagerly dispatched, not re-fused into the sort program), so
        # the result is bitwise-identical to a standalone summarize —
        # an in-jit fusion changes XLA's division lowering by ~1e-9 and
        # would break the bitwise-mode contract.
        from photon_ml_tpu.data.stats import sparse_summary_arrays

        stats_arrays = sparse_summary_arrays(idx_d, val_d, dim)
    skeys, first, n_unique = _sort_pair_keys(
        idx_d, val_d, ent_d, dimw=dimw, num_entities=num_entities
    )
    u = int(n_unique)
    keys_u, counts = _compact_pairs(
        skeys, first, n_unique=u, num_entities=num_entities, dimw=dimw
    )
    counts_h = np.asarray(counts)
    d_proj = max(1, int(counts_h.max()) if len(counts_h) else 1)
    if pad_multiple > 1:
        d_proj = ((d_proj + pad_multiple - 1) // pad_multiple) * pad_multiple
    tables = _build_tables(
        keys_u, num_entities=num_entities, d_proj=d_proj, dimw=dimw
    )
    offsets_h = np.zeros(num_entities + 2, np.int64)
    np.cumsum(counts_h, out=offsets_h[1 : num_entities + 1])
    offsets_h[num_entities + 1] = offsets_h[num_entities] = u
    mapper = DeviceIndexMapper(
        keys_u, jnp.asarray(offsets_h, jnp.int32), dimw, d_proj
    )
    mapper._pending_planes = (idx_d, val_d)
    return np.asarray(tables).astype(np.int64), mapper, stats_arrays


def project_ell_device(
    mapper: DeviceIndexMapper, idx, val, ent
) -> Tuple[Array, Array]:
    """Project ELL planes through a device mapper; returns (N, K) device
    planes bitwise-equal to IndexMapProjector.project_arrays."""
    return _project_entries(
        mapper.keys_u,
        mapper.offsets,
        jnp.asarray(idx),
        jnp.asarray(val),
        jnp.asarray(ent, jnp.int32),
        dimw=mapper.dimw,
    )


def transpose_planes_device(out, vout, d_proj: int) -> Tuple[Array, Array]:
    """Projected (N, K) -> (K, N) block-layout planes on device (int16
    indices when d_proj fits, matching the host path's cast)."""
    return _transpose_planes(out, vout, int16_idx=d_proj < (1 << 15))
