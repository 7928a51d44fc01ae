"""Device mesh + sharding layout for GAME training.

Counterpart of the reference's distribution machinery (SURVEY.md §2.7): Spark
treeAggregate/broadcast/co-partitioned joins become XLA collectives over a
`jax.sharding.Mesh`. The layout (SURVEY §2.6 mapping):

  * data parallelism ("data" axis): the fixed-effect coordinate shards the
    SAMPLE axis of (features, labels, offsets, weights); coefficients stay
    replicated. Gradient reductions inside the jitted optimizer become
    psum/all-reduce over ICI — the treeAggregate equivalent
    (ValueAndGradientAggregator.scala:248-252) with no driver in the loop.
  * entity sharding (expert-parallel analog, same mesh axis): random-effect
    buckets shard the ENTITY axis of their (E, S, ...) blocks; each device
    solves its own entities' independent problems, no collectives needed in
    the solve at all (the reference's co-partitioned join,
    RandomEffectCoordinate.scala:100-103).
  * residual exchange: per-sample score vectors share the fixed-effect
    sample sharding; entity-block gathers cross shard boundaries and XLA
    lowers them to all-gathers on ICI — replacing the by-uid RDD joins.

Most of it goes through jit with sharded inputs (GSPMD propagation). The
collectives the framework writes itself are where propagation would not
place one well: the fixed effect's objective over sample-sharded rows
(`shard_map` + one `psum` an evaluation: `ShardedDispatch`, dense in
ops/pallas_glm.py, sparse ELL in ops/objective.py) and the ring and
broadcast gathers of a row-sharded coefficient store below. Multi-host (DCN)
uses the same code: initialize jax.distributed and build the mesh over all
processes' devices with the batch axis laid out so sample shards stay within
a slice.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.data.containers import LabeledData, SparseFeatures
from photon_ml_tpu.data.game_dataset import EntityBlocks, GameDataset, RandomEffectDataset
from photon_ml_tpu.utils import faults

DATA_AXIS = "data"


def shard_map_compat(f, *, mesh, in_specs, out_specs, check=False):
    """`jax.shard_map` with replication checking off by default — the one
    spelling every shard_map in the tree goes through."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check
    )


def make_mesh(devices: Optional[Sequence] = None, axis_name: str = DATA_AXIS) -> Mesh:
    """1-D mesh over all (or given) devices — DP+entity sharding share it."""
    devs = np.asarray(devices if devices is not None else jax.devices())
    return Mesh(devs, (axis_name,))


def surviving_mesh(
    n_devices: int, axis_name: str = DATA_AXIS
) -> Optional[Mesh]:
    """Mesh over the first `n_devices` healthy local devices — the elastic
    shrink/regrow helper (serving/reshard.py targets, mid-fit mesh-loss
    rebuilders). Returns None for n <= 1: a one-device layout is the
    REPLICATED storage mode everywhere in the tree, not a 1-mesh."""
    devs = jax.devices()
    n = max(1, min(int(n_devices), len(devs)))
    if n <= 1:
        return None
    return make_mesh(devs[:n], axis_name)


def batch_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Shard the leading (sample or entity) axis; replicate the rest."""
    return NamedSharding(mesh, P(mesh.axis_names[0], *([None] * (ndim - 1))))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _sample_axis(feats) -> int:
    """The axis of a shard's arrays that counts samples: leading, but
    trailing in the transposed (K, N) ELL layout."""
    return 1 if isinstance(feats, SparseFeatures) and feats.ell_axis == -2 else 0


def _map_feature_arrays(feats, fn):
    """`fn(array, sample_axis)` over a shard's arrays (an ELL shard's two planes)."""
    axis = _sample_axis(feats)
    return jax.tree.map(lambda a: fn(a, axis), feats)


def _pad_tag(v: np.ndarray, rem: int) -> np.ndarray:
    if v.dtype.kind == "i":
        fill = np.full(rem, np.iinfo(v.dtype).min, dtype=v.dtype)
    elif v.dtype.kind == "u":
        fill = np.full(rem, np.iinfo(v.dtype).max, dtype=v.dtype)
    elif v.dtype.kind == "f":
        fill = np.full(rem, -np.inf, dtype=v.dtype)
    else:
        fill = np.full(rem, "\x00__pad__", dtype=v.dtype)
    return np.concatenate([v, fill])


def pad_game_dataset(dataset: GameDataset, multiple: int) -> GameDataset:
    """Pad the sample axis to a multiple with weight-0 rows (inert everywhere).

    Run BEFORE building random-effect datasets so entity indices refer to the
    padded layout. Padding rows get a sentinel id-tag value (dtype-correct
    extreme / reserved string) so they group into their OWN pseudo-entity: its
    rows have weight 0, so its trained model is exactly zero and it never
    competes with real entities for reservoir caps. Real data using the
    sentinel value itself is the only (pathological) collision case.
    """
    return _pad_rows(dataset, (-dataset.num_samples) % multiple)


def _pad_rows(dataset: GameDataset, rem: int) -> GameDataset:
    """`rem` rows of index 0, value 0, label 0 and weight 0 after the last,
    each array padded where it lies."""
    if rem == 0:
        return dataset

    def pad(a, axis):
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, rem)
        return jnp.pad(a, widths)

    # host_csr / bucketed_cache are deliberately NOT carried over: the
    # stash's row indices would be inconsistent with the padded sample
    # count, and the sharded path declines the bucketed pack anyway
    # (maybe_pack rejects multi-device arrays). Dropping them here is the
    # explicit decision, not an oversight.
    return GameDataset(
        shards={k: _map_feature_arrays(v, pad) for k, v in dataset.shards.items()},
        labels=pad(dataset.labels, 0),
        offsets=pad(dataset.offsets, 0),
        weights=pad(dataset.weights, 0),  # zeros: inert
        id_tags={k: _pad_tag(v, rem) for k, v in dataset.id_tags.items()},
        pad_rows=dataset.pad_rows + rem,
    )


def shard_game_dataset(dataset: GameDataset, mesh: Mesh) -> GameDataset:
    """A data set that fits one device (or the host), its sample axis cut
    into one contiguous part a device (padding first if needed) and handed
    to `sample_sharded_dataset`. The transfers record under the `upload`
    stage of the ambient timing scope (the multi-device counterpart of
    ShardDict's lazy upload)."""
    ndev = mesh.devices.size
    dataset = pad_game_dataset(dataset, ndev)
    per = dataset.num_samples // ndev

    def part(i):
        def cut(a, axis):
            return jax.lax.slice_in_dim(a, i * per, (i + 1) * per, axis=axis)

        return GameDataset(
            shards={k: _map_feature_arrays(v, cut) for k, v in dataset.shards.items()},
            labels=cut(dataset.labels, 0),
            offsets=cut(dataset.offsets, 0),
            weights=cut(dataset.weights, 0),
            id_tags={k: v[i * per : (i + 1) * per] for k, v in dataset.id_tags.items()},
        )

    sharded = sample_sharded_dataset([part(i) for i in range(ndev)], mesh)
    return dataclasses.replace(sharded, pad_rows=dataset.pad_rows)


def sample_sharded_dataset(parts: Sequence[GameDataset], mesh: Mesh) -> GameDataset:
    """The sample-sharded GameDataset whose i-th device holds `parts[i]`:
    the way in for rows that fit no single device.

    Each part is one device's contiguous run of samples (a `GameDataset` of
    its own, `GameDataset.build` on arrays that device already holds; arrays
    from elsewhere are moved there). A part shorter than the longest gets
    zero-weight pad rows (index 0, value 0) after its last, on its own
    device, and every global array is then made of the per-device arrays as
    they lie (`jax.make_array_from_single_device_arrays`): nothing is
    gathered, and no whole copy exists anywhere. Coefficients stay
    replicated; `FixedEffectCoordinate` reads the sharding and reduces its
    objective over the mesh once an evaluation. Records under the `upload`
    stage of the ambient timing scope."""
    from photon_ml_tpu.utils.observability import stage_timer

    devices = list(mesh.devices.flat)
    if len(parts) != len(devices):
        raise ValueError(f"{len(parts)} parts for a mesh of {len(devices)} devices")
    per = max(p.num_samples for p in parts)
    with stage_timer("upload"):
        placed = []
        for p, device in zip(parts, devices):
            p = _pad_rows(p, per - p.num_samples)
            put = lambda a, _axis=None: jax.device_put(a, device)
            placed.append(dataclasses.replace(
                p,
                shards={k: _map_feature_arrays(v, put) for k, v in p.shards.items()},
                labels=put(p.labels), offsets=put(p.offsets), weights=put(p.weights),
            ))

    def whole(arrays, axis=0):
        shape = list(arrays[0].shape)
        shape[axis] *= len(arrays)
        spec = [None] * len(shape)
        spec[axis] = mesh.axis_names[0]
        return jax.make_array_from_single_device_arrays(
            tuple(shape), NamedSharding(mesh, P(*spec)), list(arrays)
        )

    def whole_shard(name):
        feats = [p.shards[name] for p in placed]
        return jax.tree.map(lambda *arrays: whole(arrays, _sample_axis(feats[0])), *feats)

    first = placed[0]
    return GameDataset(
        shards={name: whole_shard(name) for name in first.shards},
        labels=whole([p.labels for p in placed]),
        offsets=whole([p.offsets for p in placed]),
        weights=whole([p.weights for p in placed]),
        id_tags={k: np.concatenate([p.id_tags[k] for p in placed]) for k in first.id_tags},
        pad_rows=sum(p.pad_rows for p in placed),
    )


import functools
import threading
from contextlib import contextmanager


# --------------------------------------------------- collective failure domain
#
# The `collective` fault site (utils/faults.py, ISSUE 10): every HOST-side
# dispatch of a ring/bcast collective program goes through
# `dispatch_collective`, which fires the fault point and re-dispatches a
# transient failure a bounded number of times (PHOTON_COLLECTIVE_RETRIES,
# counted in COUNTERS["collective_retries"]). Collective programs are
# deterministic, so a re-dispatch reproduces the same bits. The wrappers
# below are ALSO called while tracing (inside the scan sweep and the
# serving pjit programs) — tracing must stay pure (analysis/jit_purity),
# so tracer arguments bypass the failure domain entirely; the enclosing
# host dispatch (game/coordinate.py's scan-group dispatch) carries the
# fault site for those programs instead.

_COLLECTIVE_STATE = threading.local()


@contextmanager
def collective_faults_suppressed():
    """Scope marking the DEGRADED tier: the per-bucket fallback loop a
    failed scan sweep retreats to must not be re-killed by the same armed
    `collective` plan (the FE-only-tier precedent: a degradation path
    keeps working precisely while the primary path is broken)."""
    prev = getattr(_COLLECTIVE_STATE, "suppressed", False)
    _COLLECTIVE_STATE.suppressed = True
    try:
        yield
    finally:
        _COLLECTIVE_STATE.suppressed = prev


def collective_retry_policy():
    """Bounded re-dispatch policy for failed collective programs: 1 +
    PHOTON_COLLECTIVE_RETRIES attempts under the standard backoff."""
    from photon_ml_tpu.utils.knobs import get_knob

    return faults.bounded_policy(int(get_knob("PHOTON_COLLECTIVE_RETRIES")))


def dispatch_collective(fn, *, label: str):
    """Run one host-side collective program dispatch under the `collective`
    fault site + bounded re-dispatch. Exhausted retries propagate (the
    caller owns the degraded fallback — e.g. the sweep's bucket loop)."""
    if getattr(_COLLECTIVE_STATE, "suppressed", False):
        return fn()

    def attempt():
        faults.fault_point("collective")
        return fn()

    return faults.retry(
        attempt,
        collective_retry_policy(),
        label=f"collective dispatch {label}",
        counter="collective_retries",
    )


def _is_tracing(*arrays) -> bool:
    return any(isinstance(a, jax.core.Tracer) for a in arrays)


def matrix_row_sharding(mesh: Mesh) -> NamedSharding:
    """Shard matrix ROWS (entities) over the mesh; feature axis replicated."""
    return NamedSharding(mesh, P(mesh.axis_names[0], None))


def mesh_spans_processes(mesh: Mesh) -> bool:
    """True when the mesh places devices in more than one OS process —
    the multi-host production mode (parallel/hostmesh.py), where plain
    `jax.device_put` onto mesh shardings is unavailable (the CPU/gloo
    backend refuses cross-process transfers) and global arrays must be
    assembled per-process via `jax.make_array_from_callback`."""
    return len({d.process_index for d in mesh.devices.flat}) > 1


def put_row_sharded(matrix, sharding: NamedSharding):
    """`jax.device_put(matrix, sharding)` that also works when the mesh
    spans multiple processes: every process holds the full host value (the
    warm-start matrices are replicated by construction), so each builds
    its addressable shards locally via `make_array_from_callback` — no
    cross-process transfer. Single-process meshes keep the plain
    device_put (identical placement, zero behavior change)."""
    if getattr(matrix, "sharding", None) == sharding:
        return matrix
    if not mesh_spans_processes(sharding.mesh):
        return jax.device_put(matrix, sharding)
    arr = np.asarray(matrix)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx]
    )


def feature_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the FEATURE axis of the fixed-effect design matrix (columns)
    and its coefficient vector over the mesh — the wide-FE option the
    reference does not have (SURVEY §2.6 TP row: the Breeze coefficient
    vector is driver-resident, so its feature dim never shards).

    Use when the coefficient state no longer fits one device's HBM: with
    X placed as P(None, axis) and every D-vector (w0, and transparently the
    optimizer's L-BFGS history/TRON CG state) as P(axis), GSPMD partitions
    the XLA objective's matmuls — `z = X @ w` becomes per-device partial
    products + an ICI all-reduce, `g = X^T u` stays device-local — and the
    vector algebra of the solver runs elementwise on shards with psums only
    at dot products. No solver code changes: this is sharding annotation +
    compiler, per the scaling-book recipe (tested for parity against the
    replicated path in tests/test_parallel.py).

    Capacity math this unlocks (PARITY.md §wide-FE): one v5e core holds
    ~16 GB HBM; a replicated f32 coefficient vector with L-BFGS m=10
    history costs D * 4 B * ~23 (w, g, direction, 2x10 history, line-search
    temporaries), capping D at ~180M replicated. Feature sharding divides
    that state by the mesh size: a 256-chip v5e pod reaches ~46B f32
    coefficients, and the reference's "hundreds of billions" claim
    (README.md:60) is reachable with bf16 state + larger pods — with X
    row-streamed, the coefficient state is the only per-device scaling
    limit."""
    return NamedSharding(mesh, P(None, mesh.axis_names[0]))


def feature_vector_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for D-vectors (coefficients/gradients) paired with
    `feature_sharding`."""
    return NamedSharding(mesh, P(mesh.axis_names[0]))


def leading_axis_mesh(array, *, require_divisible: bool = False) -> Optional[Mesh]:
    """The 1-D mesh `array` is sharded over along its leading axis, if any.

    The single inspector behind both the coordinate's entity-mesh inference
    and the transformer's sharded-matrix detection (they must agree on when
    the sharded paths engage). `require_divisible` additionally demands the
    leading dim split evenly (the ring collectives' contract for matrices).
    """
    try:
        sh = array.sharding
        if (
            isinstance(sh, NamedSharding)
            and len(sh.mesh.axis_names) == 1
            and len(sh.device_set) > 1
            and sh.spec
            and sh.spec[0] == sh.mesh.axis_names[0]
        ):
            if require_divisible and array.shape[0] % sh.mesh.devices.size != 0:
                return None
            return sh.mesh
    except Exception:
        return None
    return None


@functools.lru_cache(maxsize=64)
def _sharded_zeros_fn(shape, dtype, sharding):
    return jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sharding)


def sharded_zeros(shape, dtype, sharding: NamedSharding):
    """Allocate directly in sharded form (no replicated intermediate)."""
    return _sharded_zeros_fn(tuple(shape), np.dtype(dtype), sharding)()


def pad_rows_for_mesh(n_rows: int, mesh: Mesh) -> int:
    ndev = mesh.devices.size
    return -(-n_rows // ndev) * ndev


@functools.lru_cache(maxsize=64)
def _ring_gather_fn(mesh: Mesh, rows_ndim: int):
    """Build (once per mesh/rank — jit caches by callable identity, so a
    fresh closure per call would retrace and recompile every invocation)."""
    axis = mesh.axis_names[0]
    ndev = mesh.devices.size
    perm = [(i, (i - 1) % ndev) for i in range(ndev)]

    def per_device(m_loc, rows_loc):
        my = jax.lax.axis_index(axis)
        chunk_rows = m_loc.shape[0]

        def step(s, carry):
            out, chunk = carry
            owner = jax.lax.rem(my + s, ndev)
            base = owner * chunk_rows
            mask = (rows_loc >= base) & (rows_loc < base + chunk_rows)
            local = jnp.clip(rows_loc - base, 0, chunk_rows - 1)
            out = out + jnp.where(mask[..., None], chunk[local], 0.0)
            chunk = jax.lax.ppermute(chunk, axis, perm)
            return out, chunk

        out = jnp.zeros((*rows_loc.shape, m_loc.shape[1]), m_loc.dtype)
        out, _ = jax.lax.fori_loop(0, ndev, step, (out, m_loc))
        return out

    spec_rows = P(axis, *([None] * (rows_ndim - 1)))
    return jax.jit(
        shard_map_compat(
            per_device,
            mesh=mesh,
            in_specs=(P(axis, None), spec_rows),
            out_specs=P(axis, *([None] * rows_ndim)),
        )
    )


def ring_gather_rows(matrix: jax.Array, rows: jax.Array, mesh: Mesh) -> jax.Array:
    """out[i] = matrix[rows[i]] where `matrix` is row-sharded and `rows` is
    sharded along its own leading axis — without ever materializing the full
    matrix on one device.

    The row-sharded matrix chunk rotates around the ring (ppermute over ICI,
    the ring-attention access pattern): at step s device d holds the chunk of
    device (d+s) %% ndev and serves the requests that fall in that row range.
    Peak per-device footprint is two chunks (resident + in flight) — this is
    what lets the random-effect coefficient store exceed single-device HBM
    (the reference's RDD[(REId, model)] partitioning,
    photon-api model/RandomEffectModel.scala:36-239).
    """
    fn = _ring_gather_fn(mesh, rows.ndim)
    if _is_tracing(matrix, rows):
        return fn(matrix, rows)
    return dispatch_collective(
        lambda: fn(matrix, rows), label="ring_gather_rows"
    )


@functools.lru_cache(maxsize=64)
def _ring_scatter_fn(mesh: Mesh, rows_ndim: int, vals_ndim: int):
    axis = mesh.axis_names[0]
    ndev = mesh.devices.size
    perm = [(i, (i - 1) % ndev) for i in range(ndev)]

    def per_device(m_loc, rows_loc, vals_loc):
        my = jax.lax.axis_index(axis)
        chunk_rows = m_loc.shape[0]
        r_flat = rows_loc.reshape(-1)
        v_flat = vals_loc.reshape(-1, vals_loc.shape[-1])

        def step(s, carry):
            m, r, v = carry
            # After s ppermute hops the payload in hand originated s devices
            # to the right; its origin does not matter — only the row range.
            base = my * chunk_rows
            mask = (r >= base) & (r < base + chunk_rows)
            # Masked-out updates are routed to a dummy extra row so they
            # cannot clobber in-range rows.
            local = jnp.where(mask, r - base, chunk_rows)
            m_ext = jnp.concatenate(
                [m, jnp.zeros((1, m.shape[1]), m.dtype)], axis=0
            )
            m = m_ext.at[local].set(v)[:chunk_rows]
            r = jax.lax.ppermute(r, axis, perm)
            v = jax.lax.ppermute(v, axis, perm)
            return m, r, v

        m, _, _ = jax.lax.fori_loop(0, ndev, step, (m_loc, r_flat, v_flat))
        return m

    spec_rows = P(axis, *([None] * (rows_ndim - 1)))
    spec_vals = P(axis, *([None] * (vals_ndim - 1)))
    return jax.jit(
        shard_map_compat(
            per_device,
            mesh=mesh,
            in_specs=(P(axis, None), spec_rows, spec_vals),
            out_specs=P(axis, None),
        )
    )


def ring_scatter_rows(
    matrix: jax.Array, rows: jax.Array, values: jax.Array, mesh: Mesh
) -> jax.Array:
    """matrix.at[rows].set(values) for a row-sharded matrix with sharded
    (rows, values) — the inverse ring of `ring_gather_rows`: the update
    payload rotates; each device applies the updates that land in its chunk.

    Duplicate rows must carry equal values (the padded-entity contract:
    padding entities all write the zero solution to the pinned row).
    """
    fn = _ring_scatter_fn(mesh, rows.ndim, values.ndim)
    if _is_tracing(matrix, rows, values):
        return fn(matrix, rows, values)
    return dispatch_collective(
        lambda: fn(matrix, rows, values), label="ring_scatter_rows"
    )


@functools.lru_cache(maxsize=64)
def _bcast_gather_fn(mesh: Mesh, rows_ndim: int):
    axis = mesh.axis_names[0]

    def per_device(m_loc, rows):
        my = jax.lax.axis_index(axis)
        chunk = m_loc.shape[0]
        base = my * chunk
        mask = (rows >= base) & (rows < base + chunk)
        local = jnp.clip(rows - base, 0, chunk - 1)
        part = jnp.where(mask[..., None], m_loc[local], 0.0)
        return jax.lax.psum(part, axis)

    return jax.jit(
        shard_map_compat(
            per_device,
            mesh=mesh,
            in_specs=(P(axis, None), P()),
            out_specs=P(),
        )
    )


def bcast_gather_rows(matrix: jax.Array, rows: jax.Array, mesh: Mesh) -> jax.Array:
    """out[i] = matrix[rows[i]] for a row-sharded matrix and REPLICATED row
    indices: every shard contributes the rows it owns (others contribute
    exact zeros) and one psum returns the gathered block everywhere.

    This is the sharded-gather dispatch for SMALL request batches — the
    serving engine's padded buckets and per-coordinate validation scoring —
    where replicating the (N, D) gathered block is cheaper than resharding
    the requests onto the ring (`ring_gather_rows` stays the high-volume
    path for sample-sharded scoring). Exact row movement: every requested
    row is owned by exactly one shard, and x + 0.0 is exact in IEEE float,
    so the psum reproduces matrix[rows] BITWISE — which is what lets the
    sharded serving path stay bitwise-equal to the replicated one."""
    fn = _bcast_gather_fn(mesh, rows.ndim)
    if _is_tracing(matrix, rows):
        return fn(matrix, rows)
    return dispatch_collective(
        lambda: fn(matrix, rows), label="bcast_gather_rows"
    )


def ring_gather_wire_bytes(mesh: Mesh, n_rows_padded: int, dim: int, itemsize: int = 4) -> int:
    """Analytic ICI/DCN wire bytes of one `ring_gather_rows` call: each of
    the ndev devices ppermutes its (n_rows_padded/ndev, dim) matrix chunk
    ndev times, so total bytes on the wire = ndev * matrix_bytes."""
    ndev = mesh.devices.size
    return int(ndev) * int(n_rows_padded) * int(dim) * int(itemsize)


def ring_scatter_wire_bytes(
    mesh: Mesh, n_updates_padded: int, dim: int, itemsize: int = 4
) -> int:
    """Analytic wire bytes of one `ring_scatter_rows` call: the
    (rows int32, values (., dim)) payload rotates ndev steps across ndev
    devices."""
    ndev = mesh.devices.size
    return int(ndev) * int(n_updates_padded) * (4 + int(dim) * int(itemsize))


def bcast_gather_wire_bytes(mesh: Mesh, n_rows: int, dim: int, itemsize: int = 4) -> int:
    """Analytic wire bytes of one `bcast_gather_rows` call: a ring
    all-reduce of the (n_rows, dim) partial block moves
    2 * (ndev - 1) / ndev * bytes per device across ndev devices."""
    ndev = mesh.devices.size
    return 2 * (ndev - 1) * int(n_rows) * int(dim) * int(itemsize)


def shard_random_effect_dataset(
    red: RandomEffectDataset, mesh: Mesh, *, replicate_sample_rows: bool = False
) -> RandomEffectDataset:
    """Shard each bucket's entity axis; pad entity counts to the device count.

    Padding entities gather row 0 with mask 0 and write their (zero) solution
    into the pinned unseen row — harmless by construction (weight-0 data plus
    L2 keeps a zero warm start at zero). Transfers record under the
    `upload` stage of the ambient timing scope.

    `replicate_sample_rows=True` keeps `sample_entity_rows` replicated
    instead of batch-sharded — for callers whose SAMPLE axis stays
    replicated on the mesh (the sweep executor's shard groups), where
    batch-sharding it would both demand mesh-divisible sample counts and
    leak sample sharding into downstream fixed-effect solves.
    """
    from photon_ml_tpu.utils.observability import stage_timer

    with stage_timer("upload"):
        return _shard_random_effect_dataset(
            red, mesh, replicate_sample_rows=replicate_sample_rows
        )


def _shard_random_effect_dataset(
    red: RandomEffectDataset, mesh: Mesh, *, replicate_sample_rows: bool = False
) -> RandomEffectDataset:
    ndev = mesh.devices.size
    s1 = batch_sharding(mesh, 1)
    s2 = batch_sharding(mesh, 2)
    pinned_row = red.num_entities

    buckets = []
    for b in red.buckets:
        e = b.num_entities
        rem = (-e) % ndev
        gather = jnp.pad(b.gather, ((0, rem), (0, 0)))
        mask = jnp.pad(b.mask, ((0, rem), (0, 0)))
        entity_rows = jnp.pad(b.entity_rows, (0, rem), constant_values=pinned_row)
        nb = EntityBlocks.__new__(EntityBlocks)
        nb.gather = jax.device_put(gather, s2)
        nb.mask = jax.device_put(mask, s2)
        nb.entity_rows = jax.device_put(entity_rows, s1)
        buckets.append(nb)

    rows_sh = replicated(mesh) if replicate_sample_rows else s1
    return dataclasses.replace(
        red,
        buckets=buckets,
        sample_entity_rows=jax.device_put(red.sample_entity_rows, rows_sh),
    )
