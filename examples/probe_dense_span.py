"""On the chip: what one ELL plane's margins cost as a dense span and as a gather.

The table behind `data/containers.DENSE_SPAN_LIMIT` (PERF.md section 3). It
times `SparseFeatures.matvec` itself, annotated and not, on shards of four
planes of one span class each: `lr-criteo`'s shape (8,000,000 rows into
1,000,000 features) and a chip's part of `lr-criteo-full` (11,460,155 rows,
no multiple of 128). The limit is the largest class whose dense-span product
takes under half the gather's time at both.

    python examples/probe_dense_span.py [rows ...]

Prints one JSON line a (rows, class, ids) and writes them all to
`chiprun_out/probe_dense_span.json`. Run it where the default device is the
chip; on the CPU the times say nothing of it.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.containers import SparseFeatures, _with_spans

DIM = 1_000_000
PLANES = 4
CLASSES = (128, 256, 512, 1024, 2048, 4096)


def shard(key, rows, span, lows, power):
    """(rows, PLANES) ids, plane k's inside [lows[k], lows[k] + span): a hot
    head (log-uniform ranks, as a field's popular values are) or uniform."""
    u = jax.random.uniform(key, (rows, PLANES))
    rank = jnp.floor(span**u).astype(jnp.int32) - 1 if power else jnp.floor(span * u).astype(jnp.int32)
    indices = lows[None, :] + jnp.clip(rank, 0, span - 1)
    return SparseFeatures(indices.astype(jnp.int32), jnp.full((rows, PLANES), 39**-0.5, jnp.float32), DIM)


def ms_a_plane(features, w, calls=3):
    margins = jax.jit(lambda f, x: f.matvec(x))
    out = jax.block_until_ready(margins(features, w))
    seconds = []
    for _ in range(calls):
        start = time.perf_counter()
        jax.block_until_ready(margins(features, w))
        seconds.append(time.perf_counter() - start)
    return min(seconds) / PLANES * 1e3, out


def main():
    row_counts = [int(a) for a in sys.argv[1:]] or [8_000_000, 11_460_155]
    key = jax.random.PRNGKey(7)
    w = jax.random.normal(key, (DIM,), jnp.float32)
    lines = []
    for rows in row_counts:
        for span_class in CLASSES:
            span = span_class - 5  # a field a little narrower than its class
            lows = jnp.asarray([1000, 250_000, 600_000, DIM - span], jnp.int32)  # the last ends at dim - 1
            for power in (True, False):
                make = jax.jit(shard, static_argnums=(1, 2, 4))
                plain = make(jax.random.fold_in(key, span_class + power), rows, span, lows, power)
                # Past the limit `annotate_spans` would leave the planes wide:
                # the probe says what they would cost as dense spans.
                annotated = _with_spans(plain, lows, (span_class,) * PLANES)
                gather_ms, gathered = ms_a_plane(plain, w)
                span_ms, spanned = ms_a_plane(annotated, w)
                lines.append({
                    "device": jax.devices()[0].device_kind, "rows": rows, "class": span_class,
                    "ids": "power" if power else "uniform", "gather_ms": gather_ms, "dense_span_ms": span_ms,
                    "bit_equal": bool(jnp.array_equal(gathered, spanned)),
                })
                print(json.dumps(lines[-1]), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probe_dense_span.json", "w") as out:
        json.dump(lines, out, indent=1)


if __name__ == "__main__":
    main()
