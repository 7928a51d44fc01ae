"""Driver `refit_mesh`: `refit` on rows that lie on several chips.

The window, the end-to-end metrics, the outputs and the release are `refit`'s.
Set-up differs in one thing: a part of the problem holds one array a chip
(`generators/criteo_shape_mesh.PerChip`), each chip's rows become a
`GameDataset` on that chip, and the program's own entry
(`parallel/mesh.sample_sharded_dataset`) makes the sample-sharded data set of
them over a 1-D mesh of the cell's chips. The estimator is built, prepared and
fitted as `refit` does it: the program reads the sharding off the data.

After the warm fit, set-up prints the fit's dispatch decisions
(`run_profile()["dispatch"]`) on standard error.

A cell on more than one chip (worked example: `lr-criteo-full.fit`; this
stands here because `benchmarks/README.md` is a `benchmark` PR's to edit).
`chips: 4` in the workload file and in `BENCHMARK.json`, for state that fits
no single chip; `attach` refuses a machine with fewer. The generator
(`criteo_shape_mesh`) draws each chip's rows on that chip and returns per-chip
parts, never a whole array: every array of a part is a `PerChip` (one array a
chip, of one length; `weights` marks the pad rows that even out the last part;
`run.py`'s `first_half` cuts every chip's rows alike). The configuration's
`requires` names the program's entry, so a program without it is refused
before a row is made. The reference (`glm_sparse_lbfgs_mesh`) works on each
chip's part where it lies and adds the chips' partial values and gradients on
the first. `memory_peak_bytes` is the fullest chip's, `busy_s` and every
operation's time a mean over the chips (`trace_reduce.reduce`); list
`fit_mfu_mesh` (the whole fit's share of the mesh's peak) in place of
`fit_mfu_counted`, which divides all the rows' work by one chip's peak, and
`collective_share_pct` / `collectives_per_eval` (`layers/collectives.py` names
the operations). Rehearse with
`XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu python3
benchmarks/run.py --workload lr-criteo-full.fit --seed 7 --seconds 2 --trace 1
--rows 40001`.
"""

import dataclasses
import json
import sys
import time

import jax

from .refit import State, _estimator, _fit, end_to_end, outputs, release, window  # noqa: F401


def _dataset(part, mesh):
    from photon_ml_tpu.data.containers import SparseFeatures
    from photon_ml_tpu.data.game_dataset import GameDataset
    from photon_ml_tpu.parallel.mesh import sample_sharded_dataset

    chips = []
    for c in range(mesh.devices.size):
        shards = {
            name: SparseFeatures(feats["indices"].parts[c], feats["values"].parts[c], feats["dim"])
            for name, feats in part["shards"].items()
        }
        weights = part["weights"].parts[c]
        chip = GameDataset.build(shards, part["labels"].parts[c], weights=weights)
        # The generator made the parts of one length and says which rows are pads.
        chips.append(dataclasses.replace(chip, pad_rows=int(weights.shape[0] - weights.sum())))
    return sample_sharded_dataset(chips, mesh)


def setup(config, workload, problem):
    from photon_ml_tpu.parallel.mesh import make_mesh
    from photon_ml_tpu.utils.observability import CoordinateUpdateEvent, EventEmitter

    state = State()
    state.rows = problem["rows"]
    state.kinds = {c["id"]: c["kind"] for c in config["coordinates"]}
    emitter = EventEmitter()

    def on_update(event):
        state.events.append((state.fit_index, event.coordinate, event.seconds))
        # A mark on the profiler's clock where this update ended.
        with jax.profiler.TraceAnnotation(f"update_end:{event.coordinate}"):
            pass

    emitter.register(on_update, CoordinateUpdateEvent)
    mesh = make_mesh(jax.devices()[: len(problem["train"]["labels"].parts)])
    state.train = _dataset(problem["train"], mesh)
    state.validation = _dataset(problem["validation"], mesh)
    state.estimator, state.opt_configs = _estimator(config, state.rows, emitter)
    t0 = time.perf_counter()
    state.estimator.prepare(state.train)
    state.prepare_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    _fit(state)
    state.warm_fit_s = time.perf_counter() - t0
    state.warm_fit_timing = dict(state.estimator.fit_timing)
    print(f"dispatch {json.dumps(state.estimator.run_profile()['dispatch'])}", file=sys.stderr)
    return state
