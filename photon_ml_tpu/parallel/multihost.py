"""Multi-host (multi-process) mesh validation harness.

`parallel/mesh.py` claims multi-host works unchanged: initialize
`jax.distributed`, build the mesh over all processes' devices, and the same
GSPMD programs run with collectives riding DCN between hosts. This module
PROVES it without TPU pods: `dryrun_multihost(n)` launches n separate Python
processes on this machine, each initializing `jax.distributed` against a
shared coordinator with its own virtual CPU devices, builds the global mesh,
and runs a real data-parallel fixed-effect training step whose gradient
reductions cross process boundaries. Every process checks numeric parity
against a single-process solve of the same global problem.

This mirrors how the reference tests "multi-node" behavior with Spark
local-cluster threads (SparkTestUtils.scala:61-75) — same code paths,
process-local execution — except here the processes really are separate OS
processes exchanging collectives, one level stronger than the 8-device
single-process mesh the test suite uses.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import time
from typing import Optional

from photon_ml_tpu.utils.knobs import get_knob

_WORKER_FLAG = "--multihost-worker"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker(coordinator: str, num_processes: int, process_id: int, devices_per_proc: int) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={devices_per_proc}"
    ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    assert jax.process_count() == num_processes, jax.process_count()
    assert jax.local_device_count() == devices_per_proc

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from photon_ml_tpu.data.containers import LabeledData
    from photon_ml_tpu.ops.losses import LOGISTIC
    from photon_ml_tpu.optimize.config import L2, CoordinateOptimizationConfig, OptimizerConfig
    from photon_ml_tpu.optimize.problem import solve
    from photon_ml_tpu.parallel.mesh import make_mesh

    n_devices = num_processes * devices_per_proc
    mesh = make_mesh()  # global mesh spanning every process's devices
    assert mesh.devices.size == n_devices

    s2 = NamedSharding(mesh, P(mesh.axis_names[0], None))
    s1 = NamedSharding(mesh, P(mesh.axis_names[0]))

    data_dir = str(get_knob("PHOTON_MH_DATA"))  # written by the launcher
    if not data_dir:
        raise RuntimeError(
            "PHOTON_MH_DATA is unset — _worker must be spawned by the "
            "multihost launcher, which writes the scratch-dir handshake"
        )
    d = 16

    def densify(dataset):
        """ELL shard -> dense host matrix (padding values are exact zeros)."""
        sp = dataset.shards["g"]
        m = dataset.num_samples
        out = np.zeros((m, d), np.float32)
        idx, val = np.asarray(sp.indices), np.asarray(sp.values)
        np.add.at(
            out,
            (np.repeat(np.arange(m), idx.shape[1]), idx.ravel()),
            val.ravel(),
        )
        return out

    # The full pod-scale ingest loop: each process reads ITS byte-balanced
    # slice of the Avro files (read_game_dataset process slicing) with a
    # shared deterministic index map, then promotes the process-local
    # columns to ONE global sharded array — the
    # make_array_from_process_local_data step the single-host driver
    # deliberately leaves to multi-host pipelines (cli/train.py).
    import photon_ml_tpu.io.avro_data as ad
    from photon_ml_tpu.data.index_map import IndexMap

    imap = IndexMap.from_feature_names(f"f{i}" for i in range(d))
    cfgs = {"g": ad.FeatureShardConfig(("features",), False)}
    ds, _ = ad.read_game_dataset(
        data_dir,
        cfgs,
        index_maps={"g": imap},
        process_index=process_id,
        process_count=num_processes,
    )
    n_loc = ds.num_samples
    X_loc = densify(ds)
    y_loc = np.asarray(ds.labels)
    # The global sample count is num_processes * n_loc ONLY when every
    # host's slice has the same row count — allgather and check, so a
    # skewed file split fails loudly here instead of silently misassembling
    # inside make_array_from_process_local_data.
    from jax.experimental import multihost_utils

    counts = np.asarray(
        multihost_utils.process_allgather(np.asarray([n_loc], np.int64))
    ).reshape(-1)
    if not (counts == n_loc).all():
        raise ValueError(
            f"per-process row counts differ across hosts ({counts.tolist()}) "
            "— the even-shard global assembly below requires row-balanced "
            "file slices; rebalance the input files"
        )
    n = n_loc * num_processes
    Xs = jax.make_array_from_process_local_data(s2, X_loc, (n, d))
    ys = jax.make_array_from_process_local_data(s1, y_loc, (n,))
    zeros = jax.make_array_from_process_local_data(
        s1, np.zeros(n_loc, np.float32), (n,)
    )
    ones = jax.make_array_from_process_local_data(
        s1, np.ones(n_loc, np.float32), (n,)
    )
    # Global problem for the on-host optimality check: every worker can
    # cheaply re-read ALL files (tiny fixture) without slicing.
    ds_all, _ = ad.read_game_dataset(data_dir, cfgs, index_maps={"g": imap})
    X = densify(ds_all)
    y = np.asarray(ds_all.labels)
    ingest_note = f"ingested {n_loc} rows/process from Avro slices, "

    cfg = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=30, tolerance=1e-8),
        regularization=L2,
        reg_weight=0.5,
    )

    @jax.jit
    def train(features, labels, offsets, weights):
        data = LabeledData(features, labels, offsets, weights)
        return solve(
            LOGISTIC, data, cfg, jnp.zeros((d,), jnp.float32), None, use_pallas=False
        ).coefficients

    w_dist = train(Xs, ys, zeros, ones)
    # The solution is replicated (coefficients replicate under DP); pull the
    # addressable replica to host.
    w_dist_host = np.asarray(jax.device_get(w_dist.addressable_data(0)))

    # Single-process reference solve of the SAME global problem.
    import numpy.linalg as npl

    def obj_grad(w):
        z = X.astype(np.float64) @ w
        p = 1 / (1 + np.exp(-z))
        g = (p - y) @ X.astype(np.float64) + 0.5 * 2 * 0.5 * w  # l2=0.5
        return g

    # Verify first-order optimality of the distributed solution instead of
    # re-running an optimizer: ||grad|| small at w_dist.
    gnorm = npl.norm(obj_grad(w_dist_host.astype(np.float64)))
    g0 = npl.norm(obj_grad(np.zeros(d)))
    assert gnorm < 1e-2 * g0, (gnorm, g0)

    # ---- entity-sharded random-effect variant (ISSUE 7) ------------------
    # Not just data-parallel FE: the random-effect coefficient store shards
    # its ENTITY axis across the processes' devices, warm-start gathers and
    # coefficient scatters ride the ring collectives over DCN, and every
    # process checks the rows IT owns against a process-local replicated
    # solve of the same problem. The per-bucket ring loop is used (scan off)
    # — eager dispatch of the shard_map programs is the conservative SPMD
    # shape for cross-process meshes; the scan fusion itself is certified
    # single-process (tests/test_parallel.py) and by MULTICHIP.
    import dataclasses as _dc

    from photon_ml_tpu.data.game_dataset import (
        EntityBlocks,
        GameDataset,
        RandomEffectDataConfig,
        build_random_effect_dataset,
    )
    from photon_ml_tpu.game.coordinate import RandomEffectCoordinate
    from photon_ml_tpu.parallel.mesh import (
        ring_gather_wire_bytes,
        ring_scatter_wire_bytes,
    )
    from photon_ml_tpu.types import TaskType

    axis = mesh.axis_names[0]
    rng_re = np.random.default_rng(5)
    d_re = 4
    e_re = 8 * n_devices
    rows_each = 4
    n_re = e_re * rows_each
    Xe = rng_re.normal(size=(n_re, d_re)).astype(np.float32)
    ent = np.repeat(np.arange(e_re), rows_each)
    y_re = (rng_re.uniform(size=n_re) > 0.5).astype(np.float32)
    cfg_re = CoordinateOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=10, tolerance=1e-7),
        regularization=L2,
        reg_weight=1.0,
    )

    # Process-local replicated reference (identical on every process: the
    # problem is seeded, tiny, and solved on local devices only).
    ds_loc = GameDataset.build(
        {"re": jnp.asarray(Xe)}, y_re, id_tags={"e": ent}
    )
    red_loc = build_random_effect_dataset(
        ds_loc, RandomEffectDataConfig("e", "re", min_bucket=4)
    )
    # photon-lint: disable=knob-registry — save/restore of the process env
    # around a forced-off window (the restore must reproduce the exact
    # inherited string, including unset), not a config read; the decision
    # readers all go through get_knob.
    prev_scan = os.environ.get("PHOTON_SWEEP_SCAN")
    os.environ["PHOTON_SWEEP_SCAN"] = "0"
    try:
        coord_loc = RandomEffectCoordinate(
            ds_loc, red_loc, cfg_re, TaskType.LOGISTIC_REGRESSION
        )
        W_ref = np.asarray(coord_loc.train(ds_loc.offsets)[0].coefficients_matrix)

        # Global sharded build: every process holds the full host arrays and
        # serves its mesh-local shards through make_array_from_callback —
        # the multi-host path device_put cannot take (non-addressable
        # devices).
        def g_put(arr, spec):
            arr_np = np.asarray(arr)
            return jax.make_array_from_callback(
                arr_np.shape,
                NamedSharding(mesh, spec),
                lambda idx: arr_np[idx],
            )

        pinned = red_loc.num_entities
        buckets_g = []
        for b in red_loc.buckets:
            e_b = b.num_entities
            rem = (-e_b) % n_devices
            gather = np.pad(np.asarray(b.gather), ((0, rem), (0, 0)))
            mask = np.pad(np.asarray(b.mask), ((0, rem), (0, 0)))
            entity_rows = np.pad(
                np.asarray(b.entity_rows), (0, rem), constant_values=pinned
            )
            nb = EntityBlocks.__new__(EntityBlocks)
            nb.gather = g_put(gather, P(axis, None))
            nb.mask = g_put(mask, P(axis, None))
            nb.entity_rows = g_put(entity_rows, P(axis))
            buckets_g.append(nb)
        red_g = _dc.replace(
            red_loc,
            buckets=buckets_g,
            sample_entity_rows=g_put(
                np.asarray(red_loc.sample_entity_rows), P(axis)
            ),
        )
        ds_g = GameDataset(
            shards={"re": g_put(Xe, P(axis, None))},
            labels=g_put(y_re, P(axis)),
            offsets=g_put(np.zeros(n_re, np.float32), P(axis)),
            weights=g_put(np.ones(n_re, np.float32), P(axis)),
            id_tags={"e": ent},
        )
        coord_g = RandomEffectCoordinate(
            ds_g, red_g, cfg_re, TaskType.LOGISTIC_REGRESSION
        )
        assert coord_g._entity_mesh is not None, "entity mesh did not engage"
        m_g, _ = coord_g.train(ds_g.offsets)
    finally:
        if prev_scan is None:
            os.environ.pop("PHOTON_SWEEP_SCAN", None)
        else:
            os.environ["PHOTON_SWEEP_SCAN"] = prev_scan

    # Every process vets the coefficient rows IT hosts (parity against the
    # replicated local solve; cross-process rows are someone else's check).
    W_g = m_g.coefficients_matrix
    max_d_re = 0.0
    n_log = W_ref.shape[0]
    for s in W_g.addressable_shards:
        lo = s.index[0].start or 0
        rows_here = np.asarray(s.data)
        for j in range(rows_here.shape[0]):
            if lo + j < n_log:
                max_d_re = max(
                    max_d_re,
                    float(np.abs(rows_here[j] - W_ref[lo + j]).max()),
                )
    scale_re = float(np.abs(W_ref).max()) + 1e-12
    assert max_d_re < 5e-3 * scale_re + 1e-5, (max_d_re, scale_re)
    # Analytic per-batch (per-bucket) collective bytes over DCN.
    n_rows_pad = W_g.shape[0]
    re_bytes = sum(
        ring_gather_wire_bytes(mesh, n_rows_pad, d_re)
        + ring_scatter_wire_bytes(mesh, b.num_entities, d_re)
        for b in red_g.buckets
    )
    re_per_batch = re_bytes // max(1, len(red_g.buckets))

    if process_id == 0:
        print(
            f"dryrun_multihost OK: {num_processes} processes x "
            f"{devices_per_proc} devices, {ingest_note}{n} samples, "
            f"grad-norm ratio {gnorm / g0:.2e}; entity-sharded RE: "
            f"{e_re} entities over {n_devices} devices, "
            f"max|dW|={max_d_re:.2e}, {re_per_batch} B/batch collective",
            flush=True,
        )


def dryrun_multihost(
    n_processes: int = 2,
    devices_per_proc: int = 2,
    *,
    timeout_s: int = 600,
) -> None:
    """Launch `n_processes` OS processes that form one jax.distributed
    cluster over virtual CPU devices and train a sharded fixed-effect GLM
    whose gradient all-reduces cross process boundaries."""
    port = _free_port()
    coordinator = f"127.0.0.1:{port}"
    env = dict(os.environ)
    # The dryrun's workers must see a CLEAN knob surface: a caller that
    # runs the dryrun under an armed fault plan / installed runtime plan /
    # tracing would otherwise leak those into every worker, where an
    # injected fault or plan decision makes the optimality check a flake
    # (ISSUE 17 satellite — the production supervisor in cli/train owns
    # deliberate worker-env construction instead).
    for leaked in (
        "PHOTON_FAULTS",
        "PHOTON_FAULTS_SEED",
        "PHOTON_PLAN",
        "PHOTON_PLAN_PROFILE",
        "PHOTON_TRACE",
    ):
        env.pop(leaked, None)
    env["JAX_PLATFORMS"] = "cpu"
    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    # Workers write stdout/stderr to temp files rather than pipes: the parent
    # polls returncodes without draining anything, so a chatty worker (XLA
    # dump flags, distributed-runtime logging) can never block on a full pipe
    # buffer, and crash diagnostics survive kills.
    import tempfile

    with tempfile.TemporaryDirectory(prefix="photon_multihost_") as logdir:
        # Pre-write one Avro file per process (equal row counts, dense 16
        # features per record): workers ingest their round-robin slice and
        # assemble the global sharded arrays — the pod-scale ingest loop,
        # end to end. Generation stays deterministic so every worker can
        # rebuild the global problem for the optimality check.
        data_dir = os.path.join(logdir, "data")
        os.makedirs(data_dir)
        import numpy as np

        import photon_ml_tpu.io.avro_data as avro_data

        d = 16
        rows_per_proc = 64 * devices_per_proc
        rng = np.random.default_rng(0)
        w_true = rng.normal(size=d).astype(np.float32)
        for pid in range(n_processes):
            Xp = rng.normal(size=(rows_per_proc, d)).astype(np.float32)
            yp = (
                rng.uniform(size=rows_per_proc)
                < 1 / (1 + np.exp(-(Xp @ w_true)))
            ).astype(np.float64)
            feats = [
                [(f"f{j}", float(Xp[i, j])) for j in range(d)]
                for i in range(rows_per_proc)
            ]
            avro_data.write_training_examples(
                os.path.join(data_dir, f"part-{pid}.avro"), feats, yp
            )
        env["PHOTON_MH_DATA"] = data_dir

        def _read(f) -> str:
            f.flush()
            f.seek(0)
            return f.read()

        # Child cleanup (ISSUE 13 satellite): the old reaper SIGKILLed
        # stragglers and returned immediately — on a worker timeout the
        # killed coordinator (worker 0 owns the jax.distributed
        # coordinator socket) could still hold the port through kernel
        # teardown, so a back-to-back invocation that drew the same port
        # from _free_port flaked on bind. Now EVERY exit path reaps every
        # child (terminate -> bounded wait -> kill -> wait, files closed)
        # and then blocks until the coordinator port actually binds again.
        procs = []

        def _reap_all() -> None:
            for q, _, _ in procs:
                if q.poll() is None:
                    q.terminate()
            deadline_t = time.monotonic() + 5.0
            for q, _, _ in procs:
                if q.poll() is None:
                    try:
                        q.wait(timeout=max(0.1, deadline_t - time.monotonic()))
                    except subprocess.TimeoutExpired:
                        pass
            for q, _, _ in procs:
                if q.poll() is None:
                    q.kill()
            for q, of, ef in procs:
                q.wait()
                of.close()
                ef.close()

        def _await_port_released() -> None:
            deadline_p = time.monotonic() + 10.0
            while time.monotonic() < deadline_p:
                try:
                    with socket.socket() as s:
                        # SO_REUSEADDR: the probe must see through the
                        # TIME_WAIT entries a CLEAN run's closed worker
                        # connections leave behind — only a socket still
                        # actively bound (a surviving coordinator) should
                        # hold the poll, never a 10 s tax on success.
                        s.setsockopt(
                            socket.SOL_SOCKET, socket.SO_REUSEADDR, 1
                        )
                        s.bind(("127.0.0.1", port))
                    return
                except OSError:
                    time.sleep(0.1)
            # Diagnostic only: the next invocation draws a fresh port, so
            # a lingering TIME_WAIT here must not fail THIS run.
            print(
                f"dryrun_multihost: coordinator port {port} still bound "
                "after reap",
                file=sys.stderr,
            )

        # Poll all workers rather than wait() in order: if a later process
        # crashes, the earlier ones hang in the collective, and a sequential
        # wait would time out with a generic message while the crashed
        # worker's stderr (the actual explanation) is discarded.
        deadline = time.monotonic() + timeout_s
        try:
            for pid in range(n_processes):
                out_f = open(os.path.join(logdir, f"w{pid}.out"), "w+")
                err_f = open(os.path.join(logdir, f"w{pid}.err"), "w+")
                procs.append(
                    (
                        subprocess.Popen(
                            [
                                sys.executable,
                                os.path.abspath(__file__),
                                _WORKER_FLAG,
                                coordinator,
                                str(n_processes),
                                str(pid),
                                str(devices_per_proc),
                            ],
                            env=env,
                            stdout=out_f,
                            stderr=err_f,
                            cwd=repo_root,
                        ),
                        out_f,
                        err_f,
                    )
                )
            while True:
                states = [q.poll() for q, _, _ in procs]
                crashed = [i for i, s in enumerate(states) if s not in (None, 0)]
                if crashed:
                    errs = [
                        f"worker {i} (exit {states[i]}):\n{_read(procs[i][2])[-2000:]}"
                        for i in crashed
                    ]
                    raise RuntimeError(
                        "dryrun_multihost worker failed:\n" + "\n---\n".join(errs)
                    )
                if all(s == 0 for s in states):
                    break
                if time.monotonic() > deadline:
                    raise RuntimeError("dryrun_multihost timed out")
                time.sleep(0.2)
            outs = [_read(of) for _, of, _ in procs]
        finally:
            _reap_all()
            _await_port_released()
    ok_lines = [line for out in outs for line in out.splitlines() if "dryrun_multihost OK" in line]
    if not ok_lines:
        raise RuntimeError(f"no OK line from workers: {outs}")
    print(ok_lines[0])


if __name__ == "__main__":
    if _WORKER_FLAG in sys.argv:
        i = sys.argv.index(_WORKER_FLAG)
        _worker(
            sys.argv[i + 1],
            int(sys.argv[i + 2]),
            int(sys.argv[i + 3]),
            int(sys.argv[i + 4]),
        )
    else:
        dryrun_multihost()
