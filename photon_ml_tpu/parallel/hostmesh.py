"""Multi-host production mode over DCN (ISSUE 17).

`parallel/multihost.py` is the dryrun: it PROVES the cross-process SPMD
recipe (jax.distributed over virtual CPU devices, global arrays through
`make_array_from_callback`, ring collectives riding DCN) on a synthetic
problem. This module PROMOTES that recipe to a production mode with
whole-host loss as a first-class, injectable, survivable failure domain:

- `bringup()` forms the process group from supervisor-provided flags and
  returns a `HostMesh` — the global 1-D mesh over every host's devices
  plus the `g_put` assembler every global array goes through (the
  CPU/gloo backend refuses cross-process `jax.device_put`).
- `exchange_ingest()` is the per-host disjoint file-set ingest: each
  host Avro-decodes only ITS byte-balanced slice of the input files,
  publishes one npz of decoded row planes PER FILE to the rendezvous
  directory (the filesystem standing in for DCN), and every host then
  assembles ALL files in sorted-file order. Assembly order is a property
  of the FILE LIST, not the host count — so a 4-host, 2-host and
  1-host run build bit-identical sample arrays, which is what makes
  multi-host fits bitwise-comparable to the single-process baseline.
- `HostHeartbeat` is the liveness domain: every host beats a counter
  file; a peer whose counter stalls `MISS_THRESHOLD` consecutive
  periods is declared lost with a typed `faults.HostLoss`. Recovery is
  NOT in-process (jax.distributed cannot shrink a live process group):
  the worker journals `host_loss` and exits `EXIT_HOST_LOSS`, and the
  SUPERVISOR (`supervise()`, driven by `cli/train --multihost`)
  relaunches the survivor set, which resumes from the multi-host
  checkpoint — a host loss costs one sweep, not the job.
- `MultihostCheckpoint` makes the PR 10 elastic checkpoint multi-host:
  each host writes only its OWN addressable shards (global shard
  indices, so any host count reassembles), and the state.json commit
  goes behind a cross-host barrier — host 0 refuses to name another
  host's shard until that host's marker proves the shard is durable,
  so a torn multi-host checkpoint is detected and NAMED, never loaded.

Compute layout (the bitwise-parity contract): fixed-effect coordinates
train on REPLICATED global arrays — every device runs the identical
full solve, no collectives, so FE is bitwise by construction. Random
effects shard the ENTITY axis (the dryrun recipe) with sample arrays
REPLICATED (`mesh._shard_random_effect_dataset(replicate_sample_rows=
True)`'s layout, certified single-process by PR 10): row k's per-entity
solve runs on whichever device owns row k with the same replicated
sample inputs regardless of which PROCESS that device lives in, and the
ring collectives move rows without reducing — so any topology with the
same GLOBAL device count (1x8, 2x4, 4x2) produces bit-identical
coefficients. The Spark parity (PARITY.md): executor loss + YARN
relaunch + lineage recovery, here as process loss + supervisor relaunch
+ checkpoint resume, with the commit barrier playing the role of
Spark's v2 commit protocol.
"""

from __future__ import annotations

import dataclasses
import json
import os
import socket
import subprocess
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from photon_ml_tpu.utils import faults, telemetry
from photon_ml_tpu.utils.faults import HostLoss
from photon_ml_tpu.utils.knobs import get_knob

# Worker self-exit code after a detected host loss: the surviving
# processes cannot shrink the jax.distributed group in-flight, so they
# journal `host_loss` and exit with this code — the supervisor reads it
# as "relaunch me on the survivor set", distinct from both success (0)
# and a real failure (anything else).
EXIT_HOST_LOSS = 76

# Consecutive heartbeat periods a peer's beat counter may stall before
# it is declared lost. Deliberately generous: a host deep in an XLA
# compile can hold the GIL long enough to miss several beats, and a
# false loss costs a whole relaunch. Operators tune DETECTION LATENCY
# through the PHOTON_HOST_HEARTBEAT_MS period, not this threshold.
MISS_THRESHOLD = 20

# Knobs whose leakage into a worker would change its behavior out from
# under the supervisor (an armed fault plan firing inside every worker,
# a stale runtime plan, a tracer fighting over one trace file). The
# supervisor constructs worker envs through `worker_env`, which scrubs
# these; anything a worker SHOULD see is passed back in explicitly.
_SCRUBBED_KNOBS = (
    "PHOTON_FAULTS",
    "PHOTON_FAULTS_SEED",
    "PHOTON_PLAN",
    "PHOTON_PLAN_PROFILE",
    "PHOTON_TRACE",
    "PHOTON_MULTIHOST",
    "PHOTON_MH_DATA",
)


# ------------------------------------------------------------ process group


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def await_port_released(port: int, timeout_s: float = 10.0) -> None:
    """Block until `port` binds again — a killed coordinator can hold its
    socket through kernel teardown, and the next attempt's bind must not
    flake (the dryrun launcher's lesson, ISSUE 13)."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            with socket.socket() as s:
                s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                s.bind(("127.0.0.1", port))
                return
        except OSError:
            time.sleep(0.1)


def worker_env(
    num_hosts: int,
    devices_per_host: int,
    *,
    extra: Optional[Dict[str, str]] = None,
) -> Dict[str, str]:
    """The deliberately-constructed environment one worker process runs
    under: inherited env minus the scrubbed volatile knobs, CPU platform
    pinned with `devices_per_host` virtual devices, the repo importable,
    and PHOTON_MULTIHOST telling the worker's own knob readers the mode
    is on. `extra` lands last (the supervisor's explicit choices win)."""
    env = dict(os.environ)
    for leaked in _SCRUBBED_KNOBS:
        env.pop(leaked, None)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    # Strip any inherited device-count forcing before adding ours.
    kept = [
        f
        for f in flags.split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    kept.append(f"--xla_force_host_platform_device_count={devices_per_host}")
    env["XLA_FLAGS"] = " ".join(kept).strip()
    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env["PHOTON_MULTIHOST"] = str(num_hosts)
    if extra:
        env.update(extra)
    return env


@dataclasses.dataclass
class HostMesh:
    """One worker's handle on the formed process group: the global mesh,
    this host's identity, and the rendezvous directory every cross-host
    filesystem exchange (barriers, heartbeats, ingest npz) lives under."""

    mesh: object  # jax.sharding.Mesh over every host's devices
    axis: str
    host_id: int
    num_hosts: int
    devices_per_host: int
    rendezvous: str

    def g_put(self, arr, spec):
        """Assemble one GLOBAL array: every process holds the full host
        value and serves its addressable shards through
        `make_array_from_callback` — the multi-host path `device_put`
        cannot take (non-addressable devices)."""
        import jax
        from jax.sharding import NamedSharding

        arr_np = np.asarray(arr)
        return jax.make_array_from_callback(
            arr_np.shape,
            NamedSharding(self.mesh, spec),
            lambda idx: arr_np[idx],
        )

    def replicate(self, arr):
        from jax.sharding import PartitionSpec as P

        return self.g_put(arr, P())

    def barrier(self, name: str, timeout_s: float = 600.0) -> float:
        return fs_barrier(self, name, timeout_s=timeout_s)


def bringup(
    coordinator: str,
    num_hosts: int,
    host_id: int,
    devices_per_host: int,
    rendezvous: str,
) -> HostMesh:
    """Form the process group and the global mesh. Must run before any
    other JAX usage in the process; the supervisor's `worker_env` has
    already pinned the CPU platform and virtual device count."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    # Cross-process computations on the CPU backend require an explicit
    # collectives implementation (default: none — every dispatch over a
    # multi-process mesh fails with "Multiprocess computations aren't
    # implemented on the CPU backend"). Gloo is the one compiled into
    # jaxlib; on TPU the ICI/DCN fabric makes this a no-op knob.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_hosts,
        process_id=host_id,
    )
    if jax.process_count() != num_hosts:
        raise RuntimeError(
            f"process group formed with {jax.process_count()} processes, "
            f"expected {num_hosts}"
        )
    if jax.local_device_count() != devices_per_host:
        raise RuntimeError(
            f"host {host_id} sees {jax.local_device_count()} local devices, "
            f"expected {devices_per_host} — XLA_FLAGS not applied?"
        )
    from photon_ml_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    os.makedirs(rendezvous, exist_ok=True)
    return HostMesh(
        mesh=mesh,
        axis=mesh.axis_names[0],
        host_id=host_id,
        num_hosts=num_hosts,
        devices_per_host=devices_per_host,
        rendezvous=rendezvous,
    )


# ------------------------------------------------------------------ barriers


def _atomic_write_text(path: str, text: str) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(text)
    os.replace(tmp, path)


def fs_barrier(hm: HostMesh, name: str, *, timeout_s: float = 600.0) -> float:
    """Filesystem barrier over the host set: every host publishes a
    marker under `rendezvous/barriers/<name>/` and waits for all peers'.
    Emits a `multihost_barrier` journal event with the wait time; a
    timeout raises a typed `HostLoss` NAMING the hosts that never
    arrived (the heartbeat usually fires first — this is the backstop
    for losses during the exchange phases the heartbeat doesn't cover)."""
    d = os.path.join(hm.rendezvous, "barriers", name)
    os.makedirs(d, exist_ok=True)
    _atomic_write_text(os.path.join(d, f"host{hm.host_id}.ok"), "1")
    t0 = time.monotonic()
    deadline = t0 + timeout_s
    want = set(range(hm.num_hosts))
    while True:
        have = {
            int(f[len("host") : -len(".ok")])
            for f in os.listdir(d)
            if f.startswith("host") and f.endswith(".ok")
        }
        if want <= have:
            break
        if time.monotonic() > deadline:
            missing = sorted(want - have)
            raise HostLoss(
                f"barrier {name!r}: hosts {missing} never arrived within "
                f"{timeout_s:.0f}s ({len(have)}/{hm.num_hosts} present)"
            )
        time.sleep(0.05)
    seconds = time.monotonic() - t0
    telemetry.emit_event(
        "multihost_barrier",
        name=name,
        host=hm.host_id,
        num_hosts=hm.num_hosts,
        seconds=round(seconds, 6),
    )
    return seconds


# ------------------------------------------------------------------- ingest


def partition_files(
    files: Sequence[str], num_hosts: int
) -> List[List[str]]:
    """Per-host disjoint file sets: the reader's deterministic
    byte-balanced split (`avro_data._balanced_slice`), one slice per
    host. Every host can compute every slice (pure function of the file
    list), so no coordination is needed to agree on ownership."""
    from photon_ml_tpu.io.avro_data import _balanced_slice

    return [
        list(_balanced_slice(list(files), k, num_hosts))
        for k in range(num_hosts)
    ]


def _dataset_to_npz_arrays(ds) -> Dict[str, np.ndarray]:
    """One ingested file's GameDataset as flat npz-ready host arrays."""
    from photon_ml_tpu.data.containers import SparseFeatures
    from photon_ml_tpu.data.game_dataset import _ell_row_planes

    out: Dict[str, np.ndarray] = {
        "labels": np.asarray(ds.labels),
        "offsets": np.asarray(ds.offsets),
        "weights": np.asarray(ds.weights),
    }
    for name in sorted(ds.shards):
        feats = ds.peek_shard(name)
        if isinstance(feats, SparseFeatures):
            idx, val = _ell_row_planes(feats)
            out[f"shard__{name}__indices"] = idx
            out[f"shard__{name}__values"] = val
            out[f"shard__{name}__dim"] = np.asarray(feats.dim)
        else:
            out[f"dense__{name}"] = np.asarray(feats)
    for tag, col in ds.id_tags.items():
        out[f"tag__{tag}"] = np.asarray(col).astype(str)
    return out


def _dataset_from_npz(path: str):
    from photon_ml_tpu.data.containers import SparseFeatures
    from photon_ml_tpu.data.game_dataset import GameDataset

    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    shards: Dict[str, object] = {}
    id_tags: Dict[str, np.ndarray] = {}
    for key, arr in arrays.items():
        if key.startswith("shard__") and key.endswith("__indices"):
            name = key[len("shard__") : -len("__indices")]
            shards[name] = SparseFeatures(
                indices=arr,
                values=arrays[f"shard__{name}__values"],
                dim=int(arrays[f"shard__{name}__dim"]),
                ell_axis=-1,
            )
        elif key.startswith("dense__"):
            shards[key[len("dense__") :]] = arr
        elif key.startswith("tag__"):
            id_tags[key[len("tag__") :]] = arr
    return GameDataset.build(
        shards,
        arrays["labels"],
        offsets=arrays["offsets"],
        weights=arrays["weights"],
        id_tags=id_tags,
    )


def exchange_ingest(
    hm: HostMesh,
    files: Sequence[str],
    shard_configs,
    *,
    timeout_s: float = 600.0,
    **reader_kwargs,
):
    """Per-host disjoint ingest with a full row exchange.

    Each host Avro-decodes only ITS slice of `files` (one
    `read_game_dataset` call PER FILE, with the shared index maps every
    multi-host read requires), publishes one npz of decoded row planes
    per file under `rendezvous/xch/`, barriers, then assembles ALL
    files' planes in SORTED-FILE order via the delta-path concat
    (`game_dataset.concat_datasets`, which re-pads ELL planes to the
    widest K — identical to what a monolithic read would produce).

    The per-FILE exchange granularity is the bitwise-parity keystone:
    the byte-balanced host slices are NOT contiguous, so concatenating
    per-HOST blocks would reorder rows relative to the monolithic read
    and change floating-point summation order in every FE solve.
    Sorted-file assembly makes row order a property of the file list
    alone — every host count (including 1) builds the same dataset.

    Returns (dataset, files_read_by_this_host).
    """
    from photon_ml_tpu.data.game_dataset import concat_datasets
    from photon_ml_tpu.io.avro_data import read_game_dataset

    files = sorted(files)
    if len(files) < hm.num_hosts:
        raise ValueError(
            f"multi-host ingest needs at least one file per host "
            f"({len(files)} files for {hm.num_hosts} hosts)"
        )
    mine = partition_files(files, hm.num_hosts)[hm.host_id]
    xch = os.path.join(hm.rendezvous, "xch")
    os.makedirs(xch, exist_ok=True)
    for path in mine:
        ds_f, _ = read_game_dataset([path], shard_configs, **reader_kwargs)
        arrays = _dataset_to_npz_arrays(ds_f)
        out = os.path.join(xch, os.path.basename(path) + ".npz")
        tmp = out + f".tmp{hm.host_id}"
        with open(tmp, "wb") as f:
            np.savez(f, **arrays)
        os.replace(tmp, out)
    hm.barrier("ingest-exchange", timeout_s=timeout_s)
    merged = None
    for path in files:
        part = _dataset_from_npz(
            os.path.join(xch, os.path.basename(path) + ".npz")
        )
        merged = part if merged is None else concat_datasets(merged, part)
    return merged, mine


# ------------------------------------------------------- global array builds


def replicate_dataset_global(ds, hm: HostMesh):
    """The fixed-effect compute layout: every sample column REPLICATED
    onto the global mesh through `g_put`. Each device then runs the
    identical full FE solve — wasteful by design, bitwise by
    construction (no collectives, no reduction-order freedom). Entity
    stores are where multi-host capacity scaling lives (the paper's
    claim); sample replication is the price of exact FE parity."""
    from jax.sharding import PartitionSpec as P

    from photon_ml_tpu.data.containers import SparseFeatures
    from photon_ml_tpu.data.game_dataset import GameDataset, _ell_row_planes

    shards: Dict[str, object] = {}
    for name in ds.shards:
        feats = ds.peek_shard(name)
        if isinstance(feats, SparseFeatures):
            idx, val = _ell_row_planes(feats)
            shards[name] = SparseFeatures(
                indices=hm.g_put(idx, P()),
                values=hm.g_put(val, P()),
                dim=feats.dim,
                ell_axis=-1,
            )
        else:
            shards[name] = hm.g_put(np.asarray(feats), P())
    return GameDataset(
        shards=shards,
        labels=hm.g_put(np.asarray(ds.labels), P()),
        offsets=hm.g_put(np.asarray(ds.offsets), P()),
        weights=hm.g_put(np.asarray(ds.weights), P()),
        id_tags=dict(ds.id_tags),
    )


def shard_random_effect_global(red, hm: HostMesh):
    """The dryrun's entity-shard recipe as a production builder: bucket
    gather/mask/entity-row planes padded to the GLOBAL device count
    (pinned-row fill, `mesh._shard_random_effect_dataset`'s exact
    layout) and placed with the entity axis sharded over the whole
    mesh; sample-row maps REPLICATED (`replicate_sample_rows=True`'s
    layout, certified single-process by PR 10) so RE scores come out
    replicated and mix with FE scores without resharding collectives."""
    from jax.sharding import PartitionSpec as P

    from photon_ml_tpu.data.game_dataset import EntityBlocks

    n_devices = hm.mesh.devices.size
    pinned = red.num_entities
    axis = hm.axis
    buckets_g = []
    for b in red.buckets:
        rem = (-b.num_entities) % n_devices
        gather = np.pad(np.asarray(b.gather), ((0, rem), (0, 0)))
        mask = np.pad(np.asarray(b.mask), ((0, rem), (0, 0)))
        entity_rows = np.pad(
            np.asarray(b.entity_rows), (0, rem), constant_values=pinned
        )
        nb = EntityBlocks.__new__(EntityBlocks)
        nb.gather = hm.g_put(gather, P(axis, None))
        nb.mask = hm.g_put(mask, P(axis, None))
        nb.entity_rows = hm.g_put(entity_rows, P(axis))
        buckets_g.append(nb)
    return dataclasses.replace(
        red,
        buckets=buckets_g,
        sample_entity_rows=hm.g_put(np.asarray(red.sample_entity_rows), P()),
    )


# ---------------------------------------------------------------- heartbeat


class HostHeartbeat:
    """Host-liveness over the rendezvous filesystem: every host bumps a
    counter file each period; the same thread watches every peer's
    counter and declares a peer LOST after `MISS_THRESHOLD` consecutive
    stalled periods — incrementing `host_heartbeat_misses` per stalled
    period and `host_losses` once, journaling the typed `host_loss`
    event, and invoking `on_loss` (the worker's escalation: close the
    journal, exit `EXIT_HOST_LOSS` so the supervisor relaunches the
    survivor set). The `host_loss` fault site is planted in the monitor
    loop, so chaos drills can inject a synthetic loss without killing
    anything."""

    def __init__(
        self,
        hm: HostMesh,
        on_loss: Callable[[HostLoss], None],
        *,
        period_ms: Optional[int] = None,
        miss_threshold: int = MISS_THRESHOLD,
    ):
        self.hm = hm
        self.on_loss = on_loss
        self.period_s = (
            int(get_knob("PHOTON_HOST_HEARTBEAT_MS"))
            if period_ms is None
            else period_ms
        ) / 1000.0
        self.miss_threshold = miss_threshold
        self._dir = os.path.join(hm.rendezvous, "hb")
        os.makedirs(self._dir, exist_ok=True)
        self._stop = threading.Event()
        self._beat = 0
        self._last_seen: Dict[int, int] = {}
        self._misses: Dict[int, int] = {}
        self._thread = threading.Thread(
            target=self._run,
            daemon=True,
            name=f"photon-hostmesh-heartbeat-h{hm.host_id}",
        )

    def start(self) -> "HostHeartbeat":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=10.0)

    def _beat_path(self, host: int) -> str:
        return os.path.join(self._dir, f"host{host}.beat")

    def _run(self) -> None:
        hm = self.hm
        while not self._stop.is_set():
            _atomic_write_text(self._beat_path(hm.host_id), str(self._beat))
            self._beat += 1
            try:
                faults.fault_point("host_loss")
            except faults.InjectedFault:
                self._declare_loss(hm.host_id, 0, source="injected")
                return
            for peer in range(hm.num_hosts):
                if peer == hm.host_id:
                    continue
                try:
                    with open(self._beat_path(peer)) as f:
                        seen = int(f.read().strip() or "0")
                except (OSError, ValueError):
                    seen = -1  # not yet beating / torn read: counts stalled
                if seen > self._last_seen.get(peer, -2):
                    self._last_seen[peer] = seen
                    self._misses[peer] = 0
                    continue
                misses = self._misses.get(peer, 0) + 1
                self._misses[peer] = misses
                telemetry.METRICS.increment("host_heartbeat_misses")
                if misses >= self.miss_threshold:
                    self._declare_loss(peer, misses, source="heartbeat")
                    return
            self._stop.wait(self.period_s)

    def _declare_loss(self, host: int, missed: int, *, source: str) -> None:
        telemetry.METRICS.increment("host_losses")
        telemetry.emit_event(
            "host_loss",
            host=host,
            missed_beats=missed,
            num_hosts=self.hm.num_hosts,
            source=source,
        )
        loss = HostLoss(
            f"host {host} lost ({source}: {missed} stalled heartbeat "
            f"periods) out of {self.hm.num_hosts} hosts"
        )
        self.on_loss(loss)


# ----------------------------------------------------------- serving rejoin


def restage_host_rows(
    host_id: int, num_hosts: int, restaged_rows: int
) -> int:
    """A lost host rejoining the serving fleet restages its row
    partition from the artifact (the two-tier store's promotion path).
    The `host_join` fault site gates the restage — an injected failure
    leaves the fleet exactly as it was (the host's rows keep answering
    FE-only through the survivors), the same contract as PR 10 shard
    loss. Emits the typed `host_join` journal event on success."""
    faults.fault_point("host_join")
    telemetry.emit_event(
        "host_join",
        host=host_id,
        num_hosts=num_hosts,
        restaged_rows=restaged_rows,
    )
    return restaged_rows


# --------------------------------------------------------------- checkpoint


def _global_row_blocks(matrix):
    """This process's addressable row blocks of a mesh-sharded matrix,
    with GLOBAL shard indices — (blocks, n_global_shards) or (None, 0)
    when the matrix is not row-sharded over a >1-device mesh. The
    multi-host sibling of checkpoint._sharded_row_blocks, which indexes
    only what it can see and requires the blocks to cover the matrix
    (never true when peers hold the rest)."""
    from photon_ml_tpu.parallel.mesh import leading_axis_mesh

    try:
        mesh = leading_axis_mesh(matrix, require_divisible=True)
    except Exception:  # noqa: BLE001 - host arrays have no sharding
        return None, 0
    if mesh is None or mesh.devices.size < 2:
        return None, 0
    n = mesh.devices.size
    rows_per = matrix.shape[0] // n
    blocks: Dict[int, Tuple[int, int, np.ndarray]] = {}
    try:
        for s in matrix.addressable_shards:
            start = int(s.index[0].start or 0)
            k = start // rows_per
            if k not in blocks:
                blocks[k] = (k, start, np.asarray(s.data))
    except Exception:  # noqa: BLE001 - fall back to the single-blob layout
        return None, 0
    ordered = [blocks[k] for k in sorted(blocks)]
    if any(b.shape[0] != rows_per for _, _, b in ordered):
        return None, 0
    return ordered, n


from photon_ml_tpu.game.checkpoint import (  # noqa: E402 - after helpers
    CoordinateDescentCheckpoint as _BaseCheckpoint,
)


class MultihostCheckpoint(_BaseCheckpoint):
    """`CoordinateDescentCheckpoint` for the multi-host process group.

    Write side: random-effect models sharded over the global mesh write
    only THIS host's addressable shards (global `shard<k>of<n>` names,
    so the existing any-shape reassembly loads them at any host count);
    replicated models (fixed effects) are written by host 0 alone.
    Every host tracks the FULL global shard list, so each host's
    bookkeeping names the same files.

    Commit side (the cross-host barrier): every host publishes a marker
    with its checksums under the step directory; host 0 waits for ALL
    markers, merges the checksums, records which host wrote each shard
    (`multihost.shard_hosts`), and only then writes state.json — the
    single commit point. Peers wait for host 0's commit receipt before
    returning, so no host races ahead of a step that never committed.
    A marker that never arrives raises a typed `HostLoss` naming the
    host (the heartbeat usually fires first; this is the backstop).

    Load side: before the base loader touches any file, the manifest is
    verified against the filesystem — a referenced-but-missing shard
    raises `CheckpointIntegrityError` NAMING the host that wrote it, so
    a torn multi-host checkpoint (a host lost between its shard write
    and the commit barrier, with state.json hand-rolled or corrupted)
    is detected and named, never silently part-loaded."""

    def __init__(self, directory: str, hm: HostMesh, *, attempt: int = 0):
        super().__init__(directory)
        self.hm = hm
        # Marker/receipt names carry the supervisor attempt: a torn
        # attempt leaves stale commit files in the step directory it
        # died in, and the relaunch re-saves the SAME step number — the
        # nonce keeps those stale files from satisfying this attempt's
        # barrier (stale checksums would vanish in the live-rel filter,
        # but a stale receipt would let peers run ahead of the commit).
        self.attempt = int(attempt)
        self.barrier_timeout_s = 600.0

    # -- write hooks ------------------------------------------------------

    def _write_model_files(self, rel: str, model):
        from photon_ml_tpu.game import checkpoint as ckpt_mod
        from photon_ml_tpu.game.model import RandomEffectModel

        if isinstance(model, RandomEffectModel):
            blocks, n_shards = _global_row_blocks(model.coefficients_matrix)
            if blocks is not None:
                if model.variances_matrix is not None:
                    raise NotImplementedError(
                        "multi-host checkpointing of coefficient variances "
                        "is not supported — variance computation is outside "
                        "the restricted multi-host fit surface"
                    )
                stem = rel[: -len(".npz")]
                rels = [
                    f"{stem}.shard{k}of{n_shards}.npz"
                    for k in range(n_shards)
                ]
                checksums: Dict[str, str] = {}
                for k, start, block in blocks:
                    arrays = {
                        "kind": np.asarray("random_shard"),
                        "matrix": block,
                        "shard_index": np.asarray(k),
                        "n_shards": np.asarray(n_shards),
                        "row_start": np.asarray(start),
                    }
                    if model.n_entities is not None:
                        arrays["n_entities"] = np.asarray(model.n_entities)
                    checksums[rels[k]] = ckpt_mod._write_model_bytes(
                        os.path.join(self.directory, rels[k]),
                        ckpt_mod._npz_bytes(arrays),
                    )
                return rels, checksums
        if self.hm.host_id == 0:
            return ckpt_mod._save_model_files(self.directory, rel, model)
        # Replicated model, non-zero host: host 0 owns the single blob;
        # everyone still records the same rel so manifests agree.
        return rel, {}

    # -- commit barrier ---------------------------------------------------

    def _commit(self, state: dict) -> None:
        from photon_ml_tpu.game import checkpoint as ckpt_mod

        hm = self.hm
        step = int(state["completed_steps"])
        step_dir = os.path.join(
            self.directory, ckpt_mod.STEPS_DIR, str(step)
        )
        os.makedirs(step_dir, exist_ok=True)
        marker = {"host": hm.host_id, "checksums": dict(state["checksums"])}
        a = self.attempt
        _atomic_write_text(
            os.path.join(step_dir, f"commit-a{a}-host{hm.host_id}.ok"),
            json.dumps(marker),
        )
        receipt = os.path.join(step_dir, f"commit-a{a}.ok")
        if hm.host_id != 0:
            self._await_files(
                [receipt], f"step {step} commit receipt from host 0"
            )
            return
        marker_paths = [
            os.path.join(step_dir, f"commit-a{a}-host{k}.ok")
            for k in range(hm.num_hosts)
        ]
        self._await_files(
            marker_paths, f"step {step} commit markers"
        )
        merged: Dict[str, str] = {}
        shard_hosts: Dict[str, int] = {}
        for path in marker_paths:
            with open(path) as f:
                doc = json.load(f)
            merged.update(doc["checksums"])
            for r in doc["checksums"]:
                shard_hosts[r] = int(doc["host"])
        live = set(
            ckpt_mod._flat_rels(state["model_files"].values())
        ) | set(ckpt_mod._flat_rels(state["best_files"].values()))
        state["checksums"] = {
            r: c for r, c in merged.items() if r in live
        }
        state["multihost"] = {
            "num_hosts": hm.num_hosts,
            "shard_hosts": {
                r: h for r, h in shard_hosts.items() if r in live
            },
        }
        super()._commit(state)
        _atomic_write_text(receipt, "1")

    def _await_files(self, paths: List[str], what: str) -> None:
        deadline = time.monotonic() + self.barrier_timeout_s
        while True:
            missing = [p for p in paths if not os.path.exists(p)]
            if not missing:
                return
            if time.monotonic() > deadline:
                names = ", ".join(os.path.basename(p) for p in missing)
                raise HostLoss(
                    f"checkpoint commit barrier: {what} missing after "
                    f"{self.barrier_timeout_s:.0f}s ({names}) — a host was "
                    "lost between its shard write and the commit point"
                )
            time.sleep(0.05)

    # -- torn-checkpoint detection ---------------------------------------

    def load(self, task, *, config_key: Optional[str] = None):
        from photon_ml_tpu.game import checkpoint as ckpt_mod

        state_path = os.path.join(self.directory, ckpt_mod.STATE_FILE)
        with open(state_path) as f:
            state = json.load(f)
        shard_hosts = (state.get("multihost") or {}).get("shard_hosts", {})
        referenced = set(
            ckpt_mod._flat_rels(state.get("model_files", {}).values())
        ) | set(ckpt_mod._flat_rels(state.get("best_files", {}).values()))
        missing = sorted(
            r
            for r in referenced
            if not os.path.exists(os.path.join(self.directory, r))
        )
        if missing:
            owners = ", ".join(
                f"{r} (written by host {shard_hosts[r]})"
                if r in shard_hosts
                else r
                for r in missing
            )
            raise ckpt_mod.CheckpointIntegrityError(
                f"torn multi-host checkpoint at {self.directory}: state.json "
                f"references missing files — {owners}. A host's shards never "
                "reached the commit barrier; restore them or delete the "
                "checkpoint directory to start fresh."
            )
        return super().load(task, config_key=config_key)


# --------------------------------------------------------------- supervisor


@dataclasses.dataclass
class SuperviseResult:
    """What the relaunch loop did: worker attempts run (1 = no loss),
    whole-host losses absorbed, and the host count the final successful
    attempt ran with. Each loss costs exactly one repeated sweep — the
    relaunched fit resumes from the last committed step, so
    `host_losses` doubles as the supervisor-side repeated-sweep count."""

    attempts: int
    host_losses: int
    final_hosts: int
    worker_logs: List[str]


def classify_exit(returncode: int) -> str:
    """Supervisor-side exit triage: 'ok', 'host_loss' (a worker was
    signal-killed, or a survivor self-exited EXIT_HOST_LOSS after
    detecting the loss), or 'failed' (a real error — never relaunch)."""
    if returncode == 0:
        return "ok"
    if returncode < 0 or returncode == EXIT_HOST_LOSS:
        return "host_loss"
    return "failed"


def supervise(
    build_argv: Callable[[int, str, int, int], List[str]],
    *,
    num_hosts: int,
    devices_per_host: int,
    rendezvous: str,
    env_extra: Optional[Dict[str, str]] = None,
    max_host_losses: Optional[int] = None,
    attempt_timeout_s: float = 900.0,
) -> SuperviseResult:
    """The production relaunch loop behind `cli/train --multihost` (and
    the serve chaos drills): spawn one worker process per host,
    classify exits, and on a whole-host loss relaunch the SURVIVOR set —
    each attempt gets a fresh coordinator port and a fresh
    `rendezvous/attempt<k>/` namespace (barriers, heartbeats, ingest
    exchange all restart cleanly; only the checkpoint directory is
    durable across attempts).

    `build_argv(attempt, coordinator, hosts, host_id)` produces one
    worker's argv. Losses beyond PHOTON_HOST_LOSS_RETRIES (or
    `max_host_losses`) re-raise as a hard failure with the noisiest
    worker's stderr tail."""
    if max_host_losses is None:
        max_host_losses = int(get_knob("PHOTON_HOST_LOSS_RETRIES"))
    hosts = int(num_hosts)
    losses = 0
    attempt = 0
    logs: List[str] = []
    while True:
        port = free_port()
        coordinator = f"127.0.0.1:{port}"
        attempt_dir = os.path.join(rendezvous, f"attempt{attempt}")
        log_dir = os.path.join(attempt_dir, "logs")
        os.makedirs(log_dir, exist_ok=True)
        env = worker_env(hosts, devices_per_host, extra=env_extra)
        procs = []
        for k in range(hosts):
            out_path = os.path.join(log_dir, f"host{k}.out")
            err_path = os.path.join(log_dir, f"host{k}.err")
            logs.extend([out_path, err_path])
            of = open(out_path, "w")
            ef = open(err_path, "w")
            p = subprocess.Popen(
                build_argv(attempt, coordinator, hosts, k),
                env=env,
                stdout=of,
                stderr=ef,
            )
            procs.append((k, p, of, ef))

        def _reap_all() -> None:
            for _, q, _, _ in procs:
                if q.poll() is None:
                    q.terminate()
            deadline_t = time.monotonic() + 5.0
            for _, q, _, _ in procs:
                if q.poll() is None:
                    try:
                        q.wait(
                            timeout=max(0.1, deadline_t - time.monotonic())
                        )
                    except subprocess.TimeoutExpired:
                        pass
            for _, q, _, _ in procs:
                if q.poll() is None:
                    q.kill()
            for _, q, of, ef in procs:
                q.wait()
                of.close()
                ef.close()

        def _err_tail(k: int, lines: int = 30) -> str:
            try:
                with open(os.path.join(log_dir, f"host{k}.err")) as f:
                    return "\n".join(f.read().splitlines()[-lines:])
            except OSError:
                return "<no stderr captured>"

        verdict: Optional[Tuple[str, int, int]] = None  # (kind, host, rc)
        deadline = time.monotonic() + attempt_timeout_s
        try:
            while verdict is None:
                running = 0
                for k, p, _, _ in procs:
                    rc = p.poll()
                    if rc is None:
                        running += 1
                        continue
                    kind = classify_exit(rc)
                    if kind != "ok":
                        verdict = (kind, k, rc)
                        break
                else:
                    if running == 0:
                        verdict = ("ok", -1, 0)
                    elif time.monotonic() > deadline:
                        verdict = ("timeout", -1, 0)
                    else:
                        time.sleep(0.2)
        finally:
            _reap_all()
            await_port_released(port)

        kind, bad_host, rc = verdict
        if kind == "ok":
            return SuperviseResult(
                attempts=attempt + 1,
                host_losses=losses,
                final_hosts=hosts,
                worker_logs=logs,
            )
        if kind == "failed":
            raise RuntimeError(
                f"multi-host worker {bad_host} failed (exit {rc}) on "
                f"attempt {attempt} — not a host loss, not relaunching.\n"
                f"stderr tail:\n{_err_tail(bad_host)}"
            )
        if kind == "timeout":
            raise RuntimeError(
                f"multi-host attempt {attempt} exceeded "
                f"{attempt_timeout_s:.0f}s with workers still running — "
                f"reaped. stderr tail of host 0:\n{_err_tail(0)}"
            )
        # Whole-host loss: relaunch on the survivor set. The supervisor
        # journals the loss too — a SIGKILLed worker never wrote its own
        # host_loss line, and the survivors are usually reaped before
        # their heartbeats reach the miss threshold.
        losses += 1
        telemetry.METRICS.increment("host_losses")
        telemetry.emit_event(
            "host_loss",
            host=bad_host,
            missed_beats=0,
            num_hosts=hosts,
            source="supervisor",
        )
        if losses > max_host_losses:
            raise RuntimeError(
                f"host loss #{losses} exceeds the retry budget "
                f"(PHOTON_HOST_LOSS_RETRIES={max_host_losses}) — giving "
                f"up.\nstderr tail of host {max(0, bad_host)}:\n"
                f"{_err_tail(max(0, bad_host))}"
            )
        if hosts <= 1:
            raise RuntimeError(
                "host loss with a single remaining host — nothing to "
                f"relaunch on.\nstderr tail:\n{_err_tail(0)}"
            )
        hosts -= 1
        attempt += 1
