"""Multi-host production mode (ISSUE 17): `cli/train --multihost` /
`cli/serve --multihost` with whole-host loss as a survivable failure
domain.

What is certified here, each against the reference semantics Photon ML
got from Spark/YARN for free (PARITY.md "Mesh failure semantics"):

* a 2-process fit is bitwise-equal to the single-process fit on the
  same data (mirrored sample arrays + entity-sharded buckets over the
  cross-process mesh change the topology, never the floats);
* per-host disjoint file-set ingest partitions the corpus exactly —
  no file read twice, none dropped, merged arrays equal the monolithic
  read's;
* SIGKILLing a whole host mid-fit costs exactly one repeated sweep:
  the supervisor journals the typed `host_loss`, relaunches on the
  survivor set, and the fit resumes from the last committed step;
* a torn multi-host checkpoint (a host's shards never reached the
  commit barrier) is refused loudly, NAMING the host that wrote the
  missing shards;
* SIGKILLing a serving host mid-replay fails ZERO requests: the lost
  host's rows degrade to the pinned-zero FE-only tier through the
  survivors (PR 10 shard-loss semantics), every resident row stays
  bitwise-identical to the single-process serve.

Every test spawns OS processes that bring up their own jax runtime. The
deterministic ones (parity, disjoint ingest, the torn checkpoint) run in
tier-1; the two SIGKILL drills are timing drills and stay slow-marked —
their recovery logic is held in tier-1 in one process by
tests/test_elastic_mesh.py::TestMeshLossResume and
tests/test_serving_two_tier.py::TestShardLossDegradation.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

pytestmark = pytest.mark.multihost

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SHARD_DSL = "name=globalShard,feature.bags=features,intercept=true"
COORD_DSLS = [
    "name=global,feature.shard=globalShard,optimizer=LBFGS,"
    "tolerance=1e-7,max.iter=25,regularization=L2,reg.weights=0.1",
    "name=per-member,random.effect.type=memberId,feature.shard=globalShard,"
    "optimizer=LBFGS,max.iter=15,regularization=L2,reg.weights=1,"
    "min.bucket=4,projector=IDENTITY",
]
FILE_SIZES = (120, 80, 100, 60)
N_ENTITIES = 10


def _subprocess_env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra)
    return env


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """Four Avro part files (360 rows, 10 entities) + the prebuilt
    off-heap feature index — one corpus for every fit/serve below."""
    from photon_ml_tpu.cli import build_index
    from photon_ml_tpu.io.avro_data import write_training_examples

    root = tmp_path_factory.mktemp("mh_corpus")
    data = root / "data"
    data.mkdir()
    w_true = np.random.default_rng(99).normal(size=4)
    b_true = np.random.default_rng(98).normal(size=(N_ENTITIES, 2))
    for seed, n in enumerate(FILE_SIZES):
        rng = np.random.default_rng(seed)
        X = rng.normal(size=(n, 4))
        entity = rng.integers(0, N_ENTITIES, size=n)
        margins = X @ w_true + np.einsum(
            "nd,nd->n", X[:, :2], b_true[entity]
        )
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margins))).astype(
            np.float32
        )
        write_training_examples(
            str(data / f"part-{seed}.avro"),
            [[(f"f{j}", float(X[i, j])) for j in range(4)] for i in range(n)],
            y.tolist(),
            uids=[f"uid{seed}_{i}" for i in range(n)],
            id_tags={"memberId": [f"m{e}" for e in entity]},
        )
    idx = root / "index"
    build_index.main([
        "--input-data-directories", str(data),
        "--feature-shard-configurations", SHARD_DSL,
        "--output-dir", str(idx),
    ])
    return {"data": str(data), "index": str(idx)}


def _train_argv(corpus, out, n_hosts, iterations):
    return [
        sys.executable, "-m", "photon_ml_tpu.cli.train",
        "--training-task", "LOGISTIC_REGRESSION",
        "--input-data-directories", corpus["data"],
        "--root-output-directory", str(out),
        "--feature-shard-configurations", SHARD_DSL,
        "--coordinate-configurations", *COORD_DSLS,
        "--coordinate-descent-iterations", str(iterations),
        "--offheap-indexmap-dir", corpus["index"],
        "--checkpoint-directory", os.path.join(str(out), "ckpt"),
        "--multihost", str(n_hosts),
        "--multihost-devices-per-host", str(8 // n_hosts),
        "--random-seed", "7",
    ]


def _run_fit(corpus, out, n_hosts, iterations=2):
    r = subprocess.run(
        _train_argv(corpus, out, n_hosts, iterations),
        env=_subprocess_env(),
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert r.returncode == 0, (
        f"--multihost {n_hosts} fit failed:\n{r.stderr[-4000:]}\n"
        + _worker_errs(out)
    )
    with open(os.path.join(str(out), "training-summary.json")) as f:
        return json.load(f)


def _worker_errs(out) -> str:
    chunks = []
    for dirpath, _, files in os.walk(str(out)):
        for fn in files:
            if fn.endswith(".err") or fn == "worker.err":
                body = open(os.path.join(dirpath, fn)).read()
                if body.strip():
                    chunks.append(f"--- {dirpath}/{fn} ---\n{body[-3000:]}")
    return "\n".join(chunks)


def _model_records(out):
    """models/best as comparable blobs: Avro files at the PARSED-record
    level (container files embed a random sync marker, raw bytes differ
    on every write), everything else raw."""
    from photon_ml_tpu.io import avro as avro_io

    blobs = {}
    mdir = os.path.join(str(out), "models", "best")
    for dirpath, _, files in os.walk(mdir):
        for fn in sorted(files):
            p = os.path.join(dirpath, fn)
            rel = os.path.relpath(p, mdir)
            if fn.endswith(".avro"):
                _, recs = avro_io.read_container(p)
                blobs[rel] = repr(recs)
            else:
                with open(p, "rb") as f:
                    blobs[rel] = f.read()
    return blobs


@pytest.fixture(scope="module")
def fit_single(corpus, tmp_path_factory):
    out = tmp_path_factory.mktemp("fit1")
    return out, _run_fit(corpus, out, 1)


@pytest.fixture(scope="module")
def fit_two_host(corpus, tmp_path_factory):
    out = tmp_path_factory.mktemp("fit2")
    return out, _run_fit(corpus, out, 2)


def test_two_process_fit_bitwise_parity(fit_single, fit_two_host):
    """The acceptance contract: same data, same seed, same GLOBAL device
    count — one process vs two processes over DCN produce the SAME model
    artifact, record for record."""
    out1, s1 = fit_single
    out2, s2 = fit_two_host
    assert s1["multihost"]["num_hosts"] == 1
    assert s2["multihost"]["num_hosts"] == 2
    assert s2["multihost"]["host_losses"] == 0
    b1, b2 = _model_records(out1), _model_records(out2)
    assert set(b1) == set(b2), set(b1) ^ set(b2)
    differing = [k for k in b1 if b1[k] != b2[k]]
    assert not differing, f"artifact diverged across host counts: {differing}"


def test_disjoint_ingest_partition(corpus):
    """The exchange_ingest mechanism, piecewise: the byte-balanced host
    slices (`_balanced_slice`, the mapred-input-split analogue) are
    disjoint and cover every file, and per-FILE reads reassembled in
    sorted-file order (`concat_datasets`) reproduce the monolithic read
    bitwise — row order is a property of the file list, never of which
    host decoded what."""
    from photon_ml_tpu.cli.config import parse_feature_shard_config
    from photon_ml_tpu.data.game_dataset import concat_datasets
    from photon_ml_tpu.io import avro as avro_io
    from photon_ml_tpu.io.avro_data import _balanced_slice, read_game_dataset
    from photon_ml_tpu.io.paldb import resolve_offheap_index_maps

    shard_configs = dict([parse_feature_shard_config(SHARD_DSL)])
    index_maps = resolve_offheap_index_maps(corpus["index"], shard_configs)
    files = sorted(avro_io.list_container_files(corpus["data"]))

    def _read(paths):
        ds, _ = read_game_dataset(
            paths,
            shard_configs,
            index_maps=index_maps,
            id_tag_fields=["memberId"],
        )
        return ds

    mine = {k: _balanced_slice(files, k, 2) for k in (0, 1)}
    assert not (set(mine[0]) & set(mine[1])), "hosts decode a file twice"
    assert set(mine[0]) | set(mine[1]) == set(files), "a file was dropped"
    assert mine[0] and mine[1], "a host got no files"

    whole = _read(files)
    per_file = {f: _read([f]) for f in files}  # who decodes is irrelevant
    assert (
        sum(d.num_samples for d in per_file.values()) == whole.num_samples
    )
    merged = per_file[files[0]]
    for f in files[1:]:
        merged = concat_datasets(merged, per_file[f])
    np.testing.assert_array_equal(
        np.asarray(merged.labels), np.asarray(whole.labels)
    )
    np.testing.assert_array_equal(
        np.asarray(merged.offsets), np.asarray(whole.offsets)
    )
    for s in whole.shards:
        np.testing.assert_array_equal(
            np.asarray(merged.shards[s].values),
            np.asarray(whole.shards[s].values),
        )


@pytest.mark.slow
def test_sigkill_midfit_costs_one_sweep(corpus, tmp_path):
    """SIGKILL a whole worker process after the first checkpoint commit:
    the supervisor journals the typed `host_loss`, relaunches on the
    survivor set, and the fit completes having repeated exactly ONE
    sweep — the YARN-relaunch semantics, one level stronger (bitwise
    checkpointed resume instead of lineage recompute)."""
    out = tmp_path / "chaos"
    env = _subprocess_env()
    sup = subprocess.Popen(
        _train_argv(corpus, out, 2, iterations=8),
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )
    state = os.path.join(str(out), "ckpt", "state.json")
    pid_file = os.path.join(str(out), "hosts", "attempt0-host1", "pid")
    deadline = time.time() + 300
    try:
        while time.time() < deadline and not os.path.exists(state):
            assert sup.poll() is None, (
                f"supervisor exited early rc={sup.returncode}:\n"
                f"{sup.communicate()[1][-4000:]}\n{_worker_errs(out)}"
            )
            time.sleep(0.05)
        assert os.path.exists(state), "no checkpoint commit within timeout"
        os.kill(int(open(pid_file).read()), signal.SIGKILL)
        so, se = sup.communicate(timeout=600)
    finally:
        if sup.poll() is None:
            sup.kill()
    assert sup.returncode == 0, f"{se[-4000:]}\n{_worker_errs(out)}"

    with open(os.path.join(str(out), "training-summary.json")) as f:
        mh = json.load(f)["multihost"]
    assert mh["host_losses"] == 1, mh
    assert mh["repeated_sweeps"] == 1, mh
    assert mh["attempts"] == 2, mh
    assert mh["final_hosts"] == 1, mh
    # The supervisor's journal carries the schema-validated host_loss
    # event (a SIGKILLed worker never writes its own).
    with open(os.path.join(str(out), "journal.jsonl")) as f:
        events = [json.loads(ln) for ln in f if ln.strip()]
    losses = [e for e in events if e.get("type") == "host_loss"]
    assert len(losses) == 1, events
    assert losses[0]["host"] == 1 and losses[0]["num_hosts"] == 2, losses
    from photon_ml_tpu.utils.contracts import JOURNAL_EVENT_SCHEMAS

    for field in JOURNAL_EVENT_SCHEMAS["host_loss"]:
        assert field in losses[0], (field, losses[0])
    assert os.path.isfile(
        os.path.join(str(out), "models", "best", "model-metadata.json")
    )


def test_torn_multihost_checkpoint_refused(fit_two_host):
    """Delete one host's committed shard out from under state.json: the
    load refuses before touching any file, naming the host that wrote
    the missing shard — a torn checkpoint is never silently part-loaded."""
    import types

    from photon_ml_tpu.game.checkpoint import CheckpointIntegrityError
    from photon_ml_tpu.parallel.hostmesh import MultihostCheckpoint

    out, _ = fit_two_host
    ckpt_dir = os.path.join(str(out), "ckpt")
    with open(os.path.join(ckpt_dir, "state.json")) as f:
        state = json.load(f)
    shard_hosts = state["multihost"]["shard_hosts"]
    victim = sorted(r for r in shard_hosts if shard_hosts[r] == 1)[0]
    os.remove(os.path.join(ckpt_dir, victim))
    hm = types.SimpleNamespace(
        host_id=0, num_hosts=2, devices_per_host=4, mesh=None, rendezvous=""
    )
    ckpt = MultihostCheckpoint(ckpt_dir, hm, attempt=0)
    with pytest.raises(CheckpointIntegrityError, match="host 1"):
        ckpt.load("LOGISTIC_REGRESSION")


# ----------------------------------------------------------------- serving


def _serve_argv(corpus, model_dir, out):
    return [
        sys.executable, "-m", "photon_ml_tpu.cli.serve",
        "--model-input-directory", str(model_dir),
        "--requests", corpus["data"],
        "--root-output-directory", str(out),
        "--feature-shard-configurations", SHARD_DSL,
        "--offheap-indexmap-dir", corpus["index"],
        "--model-id", "m1",
    ]


def _read_scores(out):
    from photon_ml_tpu.io import avro as avro_io

    recs = {}
    for p in sorted(
        avro_io.list_container_files(os.path.join(str(out), "scores"))
    ):
        for r in avro_io.read_container(p)[1]:
            recs[r["uid"]] = r["predictionScore"]
    return recs


@pytest.mark.slow
def test_sigkill_midreplay_zero_failed_requests(
    corpus, fit_single, tmp_path
):
    """SIGKILL one of two serving hosts mid-replay with no retry budget:
    every request is still answered (zero failed), the lost host's rows
    degrade to the pinned-zero FE-only tier through the survivor, and
    every answer WITHOUT a shard-loss fallback is bitwise-identical to
    the single-process serve of the same artifact."""
    model_dir = os.path.join(str(fit_single[0]), "models", "best")

    ref_out = tmp_path / "ref"
    r = subprocess.run(
        _serve_argv(corpus, model_dir, ref_out),
        env=_subprocess_env(
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
            PHOTON_SERVING_ENTITY_SHARD="1",
        ),
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert r.returncode == 0, r.stderr[-4000:]
    ref = _read_scores(ref_out)
    assert len(ref) == sum(FILE_SIZES)

    mh_out = tmp_path / "mh"
    sup = subprocess.Popen(
        _serve_argv(corpus, model_dir, mh_out) + ["--multihost", "2"],
        env=_subprocess_env(PHOTON_HOST_LOSS_RETRIES="0"),
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )
    pid_file = os.path.join(
        str(mh_out), "hosts", "attempt0-host1", "pid"
    )
    deadline = time.time() + 300
    try:
        while time.time() < deadline and not os.path.exists(pid_file):
            assert sup.poll() is None, (
                f"serve supervisor exited early rc={sup.returncode}:\n"
                f"{sup.communicate()[1][-4000:]}\n{_worker_errs(mh_out)}"
            )
            time.sleep(0.02)
        os.kill(int(open(pid_file).read()), signal.SIGKILL)
        so, se = sup.communicate(timeout=600)
    finally:
        if sup.poll() is None:
            sup.kill()
    assert sup.returncode == 0, f"{se[-4000:]}\n{_worker_errs(mh_out)}"

    with open(os.path.join(str(mh_out), "serving-summary.json")) as f:
        summary = json.load(f)
    mh = summary["multihost"]
    assert summary["failed_requests"] == 0, summary
    assert mh["host_losses"] == 1 and mh["survivor_hosts"] == 1, mh
    assert mh["fe_only_answers"] > 0, mh
    with open(os.path.join(str(mh_out), "journal.jsonl")) as f:
        events = [json.loads(ln) for ln in f if ln.strip()]
    losses = [e for e in events if e.get("type") == "host_loss"]
    assert len(losses) == 1 and losses[0]["source"] == "serve-supervisor"

    got = _read_scores(mh_out)
    assert set(got) == set(ref)
    differing = [u for u in ref if ref[u] != got[u]]
    # Only degraded answers may move, and they must actually be counted.
    assert len(differing) <= mh["fe_only_answers"], (
        len(differing), mh["fe_only_answers"],
    )
