"""Bucketed sparse layout: the TPU-native sparse design matrix.

Why this exists: the padded-ELL layout (`containers.SparseFeatures`) expresses
`X @ w` as an XLA gather and `X^T u` as an XLA scatter-add, and both serialize
on TPU (measured ~0.5-0.8 s per pass at 1M rows x 64 nnz into dim 16k on
v5e). The reference's hot loop streams the same entries once per pass inside
Spark executors (photon-lib function/glm/ValueAndGradientAggregator.scala:
137-161); matching it on TPU needs a layout the hardware can gather/scatter
natively.

The only fast data-dependent addressing primitive Mosaic exposes is the
within-vreg `dynamic_gather`: a 128-lane table gathered per sublane row. So
the layout makes every gather a 128-wide one:

* rows are grouped into **tiles** (2048 rows at level 1);
* the feature space is cut into **buckets** of 128 consecutive ids;
* within a tile, entries are sorted by bucket and each (tile, bucket)
  **segment** is padded to one fixed width `SP` (a multiple of 1024 so the
  kernels' (SP/128, 128) blocks satisfy the 8-sublane rule).

Inside a segment every entry hits the same 128-wide slice of `w` (forward:
one dynamic_gather per vreg) and the same 128-wide slice of the gradient
(backward: one-hot contraction on the MXU). Row indices are tile-local, so
the z-scatter / u-gather side stays within a VMEM-resident (16, 128) tile
accumulator. Per entry the layout stores one packed int32
(`row_local << 7 | lane`) and one f32 value.

**Two levels + COO spill.** A fixed SP wastes padding: segment sizes vary
(and skew hard on power-law features). Level 1 sizes SP near the *mean*
segment size and spills the excess; spilled entries are re-bucketed at level
2 with 8x coarser row tiles (16384 rows), whose segments pool 8 tiles' spill
and so stay well-filled; anything past level 2's cap lands in a plain COO
list evaluated by XLA scatter/gather. Uniform data: level 1 carries ~99%,
blowup ~1.0-1.2x. Skewed data trades kernel speed for correctness
gracefully. The pack runs once per dataset (the sparsity pattern is static
across every optimizer iteration, reg-weight sweep and coordinate-descent
pass).

**Placement paths (r06).** The placement itself — histogram, rank, scatter
— has one semantics and four interchangeable implementations, tried in
order by `_pack_level`: the DEVICE pack (data/device_pack.py: stable sort
+ scatter as one XLA program, auto-on with an accelerator — the 12 s
host pass of BENCH_r05 becomes milliseconds where the planes live
anyway), the core-SHARDED native counting sort (bucketed_pack.cc, row-tile
cuts over sorted rows), the serial native sort, and the numpy oracle. All
four are bitwise identical (rank within a segment = input order
everywhere), so tests can pin any against any. Level-1's slot layout is
planned per workload by `choose_layout` (PHOTON_SPARSE_LAYOUT, Poisson
collision economics); the chosen path and its device/host walls land in
the ambient stage scope (`pack_path`, `pack_device`/`pack_host`).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.data.containers import SparseFeatures

Array = jax.Array

BUCKET = 128  # feature ids per bucket == the dynamic_gather table width
_ROW_SHIFT = 7  # packed = row_local << 7 | lane

# Level-1 layout planner (see choose_layout): row-aligned wins the forward
# scatter and the backward u-select but pays per-lane collision padding that
# scales the whole entry stream; above this estimated blowup the grouped
# (feature-lane) layout streams fewer bytes than alignment saves. The r06
# wide-operand kernels (ops/pallas_sparse.py) amortize the surviving
# feature-side one-hot, which is what makes the aligned layout profitable
# for the fused objective at all — r05's per-segment-row contractions lost
# its forward win to dispatch and padding together.
ROWALIGN_MAX_BLOWUP = 1.35

L1_TILE_ROWS = 2048  # level-1 tile: row_local fits 11 bits, z-acc (16, 128)
L2_TILE_ROWS = 16384  # level-2 tile: pools 8 L1 tiles' spill, z-acc (128, 128)
# Hard cap on segment width (entries): the kernels statically unroll SP/128
# iterations per segment, so wider segments would explode compile time.
# Anything past the cap lands in the COO overflow.
MAX_SP = 8192
# ... and the floor: the kernels' (SP/128, 128) blocks need 8 sublanes, so no
# segment is narrower than this however few entries it holds.
MIN_SP = 1024


def min_level1_slots(n_rows: int, dim: int) -> int:
    """The fewest slots level 1 can hold for these shapes: one segment of at
    least MIN_SP slots per (row tile, feature bucket), whatever the pattern
    and whichever layout `choose_layout` picks. The floor under
    `pad_blowup` that `ops/pallas_sparse.pack_can_pay` decides by."""
    B = max(1, -(-dim // BUCKET))
    T1 = max(1, -(-n_rows // L1_TILE_ROWS))
    return T1 * B * MIN_SP


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BucketedLevel:
    """One fixed-SP level. Arrays are (T * B * spv, 128); see module doc."""

    packed: Array  # int32
    values: Array  # f32
    tile_rows: int = dataclasses.field(metadata=dict(static=True))
    spv: int = dataclasses.field(metadata=dict(static=True))  # SP // 128
    # Row-lane-aligned layout: entry at slot lane row_local & 127, payload
    # (row_local >> 7) << 7 | feature_lane. The kernels' z-accumulate /
    # u-select sides are then alignment-free (no 128-wide one-hot); only
    # the gradient's feature-side scatter keeps one (ops/pallas_sparse.py).
    row_aligned: bool = dataclasses.field(
        default=False, metadata=dict(static=True)
    )

    def num_tiles(self, n_rows: int) -> int:
        return -(-n_rows // self.tile_rows)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BucketedSparseFeatures:
    """Device-resident bucketed sparse matrix (two levels + COO spill)."""

    level1: BucketedLevel
    level2: Optional[BucketedLevel]
    overflow_rows: Array
    overflow_cols: Array
    overflow_vals: Array
    n_rows: int = dataclasses.field(metadata=dict(static=True))
    dim: int = dataclasses.field(metadata=dict(static=True))

    @property
    def num_buckets(self) -> int:
        return -(-self.dim // BUCKET)

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.n_rows, self.dim)

    def density_report(self) -> dict:
        nnz1 = float(np.asarray((self.level1.values != 0).sum()))
        nnz2 = (
            float(np.asarray((self.level2.values != 0).sum()))
            if self.level2 is not None
            else 0.0
        )
        onnz = float(self.overflow_vals.shape[0])
        total = max(nnz1 + nnz2 + onnz, 1.0)
        cap1 = float(self.level1.packed.size)
        cap2 = float(self.level2.packed.size) if self.level2 is not None else 0.0
        return {
            "sp1": self.level1.spv * 128,
            "sp2": self.level2.spv * 128 if self.level2 is not None else 0,
            "level1_fraction": nnz1 / total,
            "level2_fraction": nnz2 / total,
            "overflow_fraction": onnz / total,
            "pad_blowup": (cap1 + cap2) / total,
        }


def upload(bf: BucketedSparseFeatures) -> BucketedSparseFeatures:
    """Move a host-packed layout (pack_bucketed(host_only=True)) to device —
    the one-time upload of the packed planes, split out so the host pack can
    run on a background thread during ingest and the upload at first use.
    Recorded under the `upload` stage of the ambient timing scope."""
    from photon_ml_tpu.utils.observability import stage_timer

    with stage_timer("upload"):
        return _upload(bf)


def _upload(bf: BucketedSparseFeatures) -> BucketedSparseFeatures:
    def _lvl(level: Optional[BucketedLevel]) -> Optional[BucketedLevel]:
        if level is None or isinstance(level.packed, jax.Array):
            return level
        return dataclasses.replace(
            level,
            packed=jnp.asarray(level.packed),
            values=jnp.asarray(level.values),
        )

    return BucketedSparseFeatures(
        level1=_lvl(bf.level1),
        level2=_lvl(bf.level2),
        overflow_rows=jnp.asarray(bf.overflow_rows),
        overflow_cols=jnp.asarray(bf.overflow_cols),
        overflow_vals=jnp.asarray(bf.overflow_vals),
        n_rows=bf.n_rows,
        dim=bf.dim,
    )


def _sort_by_segment(seg: np.ndarray, n_seg: int):
    """Stable sort by segment id.

    Returns (order, pos, counts): `order` lists entry indices
    segment-by-segment and `pos[j]` is the rank of entry `order[j]` within
    its segment. numpy's stable argsort on int32 keys is a radix sort —
    effectively O(nnz); `pos` comes from a sequential repeat rather than a
    random gather (2-3x faster at ~1e8 entries).
    """
    counts = np.bincount(seg, minlength=n_seg)
    starts = np.zeros(n_seg + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    order = np.argsort(seg, kind="stable")
    pos = np.arange(len(seg), dtype=np.int64) - np.repeat(starts[:-1], counts)
    return order, pos, counts


def _pack_level(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n_rows: int,
    dim: int,
    tile_rows: int,
    sp: int,
    dtype,
    host_only: bool = False,
    row_aligned: bool = False,
    allow_device: bool = True,
) -> Tuple[BucketedLevel, np.ndarray]:
    """Pack entries that fit segment width `sp`; return (level, spill mask).

    `host_only=True` keeps the packed planes as host numpy arrays (no
    device upload) — the benchmark's isolated host-cost measurement.

    Returns (level, spill mask, path) where `path` names the placement
    implementation that ran: "device" (XLA counting sort + scatter, planes
    born device-resident), "native-sharded"/"native" (bucketed_pack.cc),
    or "numpy" (the no-compiler oracle)."""
    from photon_ml_tpu.utils.observability import stage_timer

    _dev = (lambda x: x) if host_only else jnp.asarray
    B = max(1, -(-dim // BUCKET))
    T = max(1, -(-n_rows // tile_rows))
    # tile_rows and BUCKET are powers of two: shifts keep the hot O(nnz)
    # passes in cheap int32 ops.
    tile_shift = tile_rows.bit_length() - 1
    rows32 = rows.astype(np.int32, copy=False)
    cols32 = cols.astype(np.int32, copy=False)
    spv = sp // 128

    # Device pack (data/device_pack.py): the O(nnz) placement runs as one
    # XLA program where the packed planes will live anyway; only the spill
    # mask returns to host. host_only (the bench's isolated host-cost
    # measurement) keeps the host implementations; allow_device=False is
    # the level-2 call (the spill tail's nnz is data-dependent, so a
    # device pack there would compile a fresh sort program per fit for ~1%
    # of the entries — the host pass costs milliseconds instead).
    if not host_only and allow_device:
        from photon_ml_tpu.data import device_pack

        if device_pack.enabled():
            with stage_timer("pack_device"):
                dev = device_pack.pack_level_device(
                    rows32, cols32, vals, T, B, tile_shift, sp, row_aligned
                )
            if dev is not None:
                packed_d, values_d, spill_idx = dev
                level = BucketedLevel(
                    packed=packed_d.reshape(-1, 128),
                    values=values_d.reshape(-1, 128),
                    tile_rows=tile_rows,
                    spv=spv,
                    row_aligned=row_aligned,
                )
                spill_mask = np.zeros(len(rows32), dtype=bool)
                spill_mask[spill_idx] = True
                return level, spill_mask, "device"

    # Native counting-sort packer (photon_ml_tpu/native/bucketed_pack.cc):
    # one linear pass vs numpy's argsort + three gather/scatter passes;
    # core-sharded over row-tile ranges when the rows arrive sorted (the
    # CSR-derived data plane always does).
    from photon_ml_tpu.native import bucketed_pack as native_pack

    with stage_timer("pack_host"):
        native = native_pack.pack_level_native(
            rows32, cols32, vals, T, B, tile_shift, sp, row_aligned
        )
    if native is not None:
        packed_n, values_n, spill_idx, native_path = native
        level = BucketedLevel(
            packed=_dev(packed_n.reshape(-1, 128)),
            values=_dev(values_n.reshape(-1, 128)),
            tile_rows=tile_rows,
            spv=spv,
            row_aligned=row_aligned,
        )
        spill_mask = np.zeros(len(rows32), dtype=bool)
        spill_mask[spill_idx] = True
        return level, spill_mask, native_path

    with stage_timer("pack_host"):
        seg = (rows32 >> tile_shift) * np.int32(B) + (cols32 >> 7)
        n_seg = T * B
        if row_aligned:
            rl = rows32 & np.int32(tile_rows - 1)
            lane = rl & np.int32(127)
            seg_lane = seg.astype(np.int64) * 128 + lane
            payload = ((rl >> 7) << _ROW_SHIFT) | (cols32 & np.int32(BUCKET - 1))
            order, pos, _ = _sort_by_segment(seg_lane, n_seg * 128)
            fits = pos < spv
            sel = order[fits]
            dst = (
                seg[sel].astype(np.int64) * sp
                + pos[fits] * 128
                + lane[sel].astype(np.int64)
            )
            packed = np.zeros(n_seg * sp, np.int32)
            values = np.zeros(n_seg * sp, dtype)
            packed[dst] = payload[sel]
            values[dst] = vals[sel]
            level = BucketedLevel(
                packed=_dev(packed.reshape(n_seg * spv, 128)),
                values=_dev(values.reshape(n_seg * spv, 128)),
                tile_rows=tile_rows,
                spv=spv,
                row_aligned=True,
            )
            spill_mask = np.zeros(len(seg), dtype=bool)
            spill_mask[order[~fits]] = True
            return level, spill_mask, "numpy"
        # Pack the per-entry payload BEFORE sorting so only two arrays need
        # the (random-access) reorder gather.
        payload = ((rows32 & np.int32(tile_rows - 1)) << _ROW_SHIFT) | (
            cols32 & np.int32(BUCKET - 1)
        )
        order, pos, _ = _sort_by_segment(seg, n_seg)
        fits = pos < sp
        sel = order[fits]  # entry indices that fit, in segment order
        # Destinations are monotone in the sorted order -> sequential writes.
        dst = seg[sel].astype(np.int64) * sp + pos[fits]
        packed = np.zeros(n_seg * sp, np.int32)
        values = np.zeros(n_seg * sp, dtype)
        packed[dst] = payload[sel]
        values[dst] = vals[sel]
        level = BucketedLevel(
            packed=_dev(packed.reshape(n_seg * spv, 128)),
            values=_dev(values.reshape(n_seg * spv, 128)),
            tile_rows=tile_rows,
            spv=spv,
        )
        spill_mask = np.zeros(len(seg), dtype=bool)
        spill_mask[order[~fits]] = True
        return level, spill_mask, "numpy"


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _poisson_excess_fraction(lam: float, cap: int) -> float:
    """E[max(X - cap, 0)] / lam for X ~ Poisson(lam): the expected fraction
    of entries a per-lane capacity `cap` spills under uniform placement.
    Hot buckets violate the Poisson model, but their excess lands in the
    level-2/COO tail either way — the estimate only has to rank layouts.

    Each tail term is computed in log space (lgamma): the naive recurrence
    seeds with exp(-lam), which underflows to exactly 0 for lam >~ 746 and
    would report ZERO spill for precisely the dense shapes that spill
    almost everything."""
    import math

    if lam <= 0.0:
        return 0.0
    hi = int(cap + lam + 10.0 * math.sqrt(lam) + 20.0)
    log_lam = math.log(lam)
    excess = 0.0
    for j in range(cap + 1, hi + 1):
        lp = j * log_lam - lam - math.lgamma(j + 1)
        if lp > -745.0:  # below this exp() underflows; the term is 0
            excess += (j - cap) * math.exp(lp)
    return min(excess / lam, 1.0)


def _aligned_sp(mean1: float) -> Tuple[int, float, float]:
    """Poisson-adaptive row-aligned segment width: the smallest in-contract
    SP whose expected per-lane collision spill stays under 5%, plus the
    estimated (level-1 pad blowup, spill fraction) at that width. Replaces
    r05's fixed 2x-mean sizing (measured pad_blowup 2.13 on the bench
    shape) with a width derived from the collision distribution itself.
    When even MAX_SP cannot hold the tail the returned frac stays high and
    `choose_layout` declines; forced-rowalign callers get the best-effort
    width and let level 2 carry the spill."""
    lam = mean1 / 128.0
    spv, frac = 8, 0.0
    for spv in range(8, MAX_SP // 128 + 1, 8):
        frac = _poisson_excess_fraction(lam, spv)
        if frac <= 0.05:
            break
    sp = spv * 128
    kept = max(mean1 * (1.0 - frac), 1e-9)
    return sp, sp / kept, frac


def choose_layout(
    nnz: int, n_rows: int, dim: int, workload: str = "training"
) -> Tuple[bool, Optional[int]]:
    """Level-1 layout plan: (row_aligned, sp1 override or None).

    PHOTON_SPARSE_LAYOUT=rowalign|grouped forces; auto picks per the
    measured economics (ops/pallas_sparse.py r05/r06 notes): the aligned layout
    removes the forward z-scatter one-hot AND the backward u-select
    gather, but its per-lane collision padding scales the whole entry
    stream, so it engages only when the Poisson-estimated blowup stays
    under ROWALIGN_MAX_BLOWUP (training: fused fwd+bwd both stream) or
    2.25 for matvec-dominated scoring workloads (aligned matvec measured
    2.01x even at blowup 2.13). Level 2 always stays grouped: its rt=128
    coarse tiles would pay the very 128-row one-hot alignment avoids.
    """
    # Planned quantity (ISSUE 14): explicit PHOTON_SPARSE_LAYOUT wins,
    # else the installed plan's sparse_layout (the layout the profile's
    # run measured on this hardware), else the Poisson economics below.
    # planned_value normalizes the layout spellings to
    # auto|rowalign|grouped.
    from photon_ml_tpu import planner

    env = str(planner.planned_value("sparse_layout")).strip().lower()
    if env == "rowalign":
        return True, None
    if env == "grouped":
        return False, None
    B = max(1, -(-dim // BUCKET))
    T1 = max(1, -(-n_rows // L1_TILE_ROWS))
    mean1 = nnz / max(T1 * B, 1)
    sp_ra, blowup_ra, frac_ra = _aligned_sp(mean1)
    limit = ROWALIGN_MAX_BLOWUP if workload == "training" else 2.25
    # Both gates must pass: low padding AND a realized spill within the
    # sizing target — dense shapes whose lane load exceeds MAX_SP would
    # otherwise show a deceptively low blowup on the sliver that fits
    # while >90% of entries fall through to level 2.
    if blowup_ra <= limit and frac_ra <= 0.05:
        return True, sp_ra
    return False, None


def pack_bucketed(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n_rows: int,
    dim: int,
    *,
    dtype=np.float32,
    host_only: bool = False,
    row_aligned: Optional[bool] = None,
    workload: str = "training",
) -> BucketedSparseFeatures:
    """Pack COO triplets into the two-level bucketed layout.

    `row_aligned=None` defers the level-1 layout to `choose_layout` (env
    override + Poisson collision economics, per `workload`); True/False
    forces. See BucketedLevel.row_aligned and the r05/r06 notes in
    ops/pallas_sparse.py.

    `host_only=True` skips every device upload (planes stay numpy) — used
    by the benchmark to time the host pack cost in isolation without
    monkeypatching this module's array namespace. The chosen placement
    implementation lands in the ambient stage scope as the `pack_path`
    note plus `pack_device`/`pack_host` stage walls."""
    from photon_ml_tpu.utils.observability import set_stage_note

    _dev = (lambda x: x) if host_only else jnp.asarray
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    vals = np.asarray(vals, dtype)
    keep = vals != 0
    rows, cols, vals = rows[keep], cols[keep], vals[keep]
    nnz = len(vals)

    B = max(1, -(-dim // BUCKET))
    T1 = max(1, -(-n_rows // L1_TILE_ROWS))
    # Level-1 SP near the mean segment size (1024-granular): padding stays
    # ~1x and the spill tail (mean-crossing segments) goes to level 2.
    mean1 = nnz / max(T1 * B, 1)
    sp1_hint = None
    if row_aligned is None:
        row_aligned, sp1_hint = choose_layout(nnz, n_rows, dim, workload)
    if row_aligned and sp1_hint is None:
        # Forced-aligned callers get the same Poisson-adaptive width the
        # planner would have chosen (r05's fixed 2x-mean sizing measured
        # pad_blowup 2.13; the adaptive width sizes to the collision tail).
        sp1_hint, _, _ = _aligned_sp(mean1)
    sp1 = (
        sp1_hint
        if sp1_hint is not None
        else min(max(MIN_SP, _round_up(int(mean1), 1024)), MAX_SP)
    )
    level1, spill, pack_path = _pack_level(
        rows, cols, vals, n_rows, dim, L1_TILE_ROWS, sp1, dtype, host_only,
        row_aligned,
    )
    set_stage_note("pack_path", pack_path)
    # The level-1 layout decision, for the run profile's dispatch block —
    # the evidence the adaptive planner (ISSUE 14) adopts next run. A fit
    # whose packs disagree records "mixed": forcing one layout is
    # results-affecting (rowalign vs grouped are allclose-, not bitwise-,
    # equivalent), so the planner only ever adopts a UNIFORM choice.
    # merge_note is atomic under the registry lock — per-shard packs run
    # concurrently on background threads, and a check-then-set here would
    # let two disagreeing packs each record their own layout.
    from photon_ml_tpu.utils.observability import current_stage_registry

    registry = current_stage_registry()
    if registry is not None:
        registry.merge_note(
            "sparse_layout",
            "rowalign" if row_aligned else "grouped",
            "mixed",
        )

    level2 = None
    o_rows = rows[spill]
    o_cols = cols[spill]
    o_vals = vals[spill]
    if len(o_vals):
        T2 = max(1, -(-n_rows // L2_TILE_ROWS))
        mean2 = len(o_vals) / max(T2 * B, 1)
        # Generous width (4x mean) — level-2 feeds from the variance tail, so
        # its own segment sizes are lumpy; what still spills goes to COO.
        sp2 = min(max(1024, _round_up(int(4 * mean2), 1024)), MAX_SP)
        # Level 2 stays on the feature-lane layout regardless: its coarse
        # tiles have rt = 128, so a row-aligned sublane-block select would
        # cost exactly the 128-row one-hot the alignment exists to avoid.
        # It also stays on the HOST paths (allow_device=False): the spill
        # tail is ~1% of entries and its nnz varies per dataset, so the
        # host pass costs milliseconds where a device pack would compile a
        # fresh sort program per fit.
        level2, spill2, _ = _pack_level(
            o_rows, o_cols, o_vals, n_rows, dim, L2_TILE_ROWS, sp2, dtype,
            host_only, False, allow_device=False,
        )
        o_rows, o_cols, o_vals = o_rows[spill2], o_cols[spill2], o_vals[spill2]

    return BucketedSparseFeatures(
        level1=level1,
        level2=level2,
        overflow_rows=_dev(o_rows.astype(np.int32)),
        overflow_cols=_dev(o_cols.astype(np.int32)),
        overflow_vals=_dev(o_vals),
        n_rows=int(n_rows),
        dim=int(dim),
    )


def pack_from_ell(sp: SparseFeatures, **kwargs) -> BucketedSparseFeatures:
    """Convert a padded-ELL matrix (2-D) to the bucketed layout."""
    if sp.indices.ndim != 2:
        raise ValueError("pack_from_ell takes per-problem (N, K) ELL data")
    n, k = sp.indices.shape
    idx = np.asarray(sp.indices)
    val = np.asarray(sp.values)
    rows = np.repeat(np.arange(n, dtype=np.int64), k)
    return pack_bucketed(
        rows, idx.reshape(-1).astype(np.int64), val.reshape(-1), n, sp.dim, **kwargs
    )


def level_entries(level: BucketedLevel, n_rows: int, dim: int):
    """Decode one level back to COO triplets (host side, tests)."""
    B = max(1, -(-dim // BUCKET))
    sp = level.spv * 128
    pk = np.asarray(level.packed).reshape(-1, sp)
    vv = np.asarray(level.values).reshape(-1, sp)
    seg = np.arange(pk.shape[0])
    t, b = seg // B, seg % B
    nz = vv != 0
    ent_seg, ent_pos = np.nonzero(nz)
    pkx = pk[ent_seg, ent_pos]
    if level.row_aligned:
        # slot lane IS row_local & 127; payload carries (row_local>>7)<<7
        # in its high bits and the feature lane in its low 7.
        row_local = (pkx >> _ROW_SHIFT << 7) | (ent_pos & (BUCKET - 1))
        rows = t[ent_seg] * level.tile_rows + row_local
    else:
        rows = t[ent_seg] * level.tile_rows + (pkx >> _ROW_SHIFT)
    cols = b[ent_seg] * BUCKET + (pkx & (BUCKET - 1))
    return rows.astype(np.int64), cols.astype(np.int64), vv[ent_seg, ent_pos]


def to_coo(bf: BucketedSparseFeatures):
    """Full COO decode (host side, tests)."""
    parts = [level_entries(bf.level1, bf.n_rows, bf.dim)]
    if bf.level2 is not None:
        parts.append(level_entries(bf.level2, bf.n_rows, bf.dim))
    parts.append(
        (
            np.asarray(bf.overflow_rows, np.int64),
            np.asarray(bf.overflow_cols, np.int64),
            np.asarray(bf.overflow_vals),
        )
    )
    rows = np.concatenate([p[0] for p in parts])
    cols = np.concatenate([p[1] for p in parts])
    vals = np.concatenate([p[2] for p in parts])
    return rows, cols, vals
