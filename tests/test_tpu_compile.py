"""The main path's kernels, compiled at real widths by the chip's own compiler.

Interpret mode accepts programs that Mosaic refuses (the grouped sparse
rmatvec passed every parity test for sixteen PRs and had never compiled
for a TPU), so these cases hand the kernels to the TPU compiler that is
installed here, for a v5e that is described and not attached. Nothing
runs: a pass says the chip's compiler takes the program at this shape
and that the kernel is in it (`tpu_custom_call`), not that it is right
or fast.

All in ONE file, the topology described inside a module-scoped fixture
and never at import, in a `skipif` or in `parametrize` arguments: only
one process may hold the TPU library, and under xdist every worker
imports every test file. The persistent compilation cache is off around
the compiles (an entry written for a described device cannot be read
back without the chip).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data import bucketed
from photon_ml_tpu.ops import pallas_glm, pallas_sparse
from photon_ml_tpu.ops.losses import LOGISTIC
from photon_ml_tpu.types import TaskType

# README headline shape (dense fixed effect; row tile 1,024), `lr-epsilon`'s
# (benchmarks/configs/lr-epsilon.json; row tile 512, d no multiple of 128)
# and the e2e MovieLens shape (sparse fixed effect; chip_smoke.py).
DENSE_N, DENSE_D = 1_048_576, 512
EPSILON_N, EPSILON_D, EPSILON_ITERATIONS = 400_000, 2_000, 5
SPARSE_N, SPARSE_D, SPARSE_NNZ = 262_144, 200, 8
# chip_smoke's served model at 2M rows: d = 200 + intercept, rows//145
# users, rows//740 movies (+ the pinned zero row each).
SERVE_D, SERVE_USERS, SERVE_MOVIES, SERVE_BATCH = 201, 13_794, 2_703, 64


@pytest.fixture(scope="module")
def topology():
    """The described v5e:2x2, the persistent compilation cache off for as
    long as the module's tests run."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topology):
    """A SingleDeviceSharding on one described v5e chip."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topology.devices[0])


@pytest.fixture(scope="module")
def four_chips(topology):
    """A 1-D mesh over the four described chips."""
    from jax.sharding import Mesh

    return Mesh(np.asarray(topology.devices), ("data",))


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )


def _vec(sharding, n, dtype=jnp.float32):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)


def _scalar(sharding):
    return jax.ShapeDtypeStruct((), jnp.float32, sharding=sharding)


def _compiled_text(lowered) -> str:
    return lowered.compile().as_text()


# ------------------------------------------------------------------- dense


@pytest.mark.parametrize("x_dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("kernel", ["value_gradient", "hessian_vector"])
@pytest.mark.parametrize(
    "rows,dim,column_major",
    [(DENSE_N, DENSE_D, False), (EPSILON_N, EPSILON_D, False), (EPSILON_N, EPSILON_D, True)],
    ids=["readme", "epsilon", "epsilon-as-it-lies"],
)
def test_dense_kernels_compile_for_v5e(one_chip, rows, dim, column_major, kernel, x_dtype):
    """Both reads of X: (tile, d) blocks of a row-major matrix and, for one
    that lies column-major as `lr-epsilon`'s does, (d, tile) blocks of X^T."""
    X = jax.ShapeDtypeStruct((rows, dim), x_dtype, sharding=one_chip)
    w, n, s = _vec(one_chip, dim), _vec(one_chip, rows), _scalar(one_chip)
    if kernel == "value_gradient":
        lowered = pallas_glm.value_gradient_sums.lower(
            LOGISTIC, w, s, X, n, n, n, column_major=column_major
        )
    else:
        lowered = pallas_glm.hessian_vector_sums.lower(
            LOGISTIC, w, s, w, s, X, n, n, n, column_major=column_major
        )
    assert "tpu_custom_call" in _compiled_text(lowered)


# ------------------------------------------------------------------ sparse


@functools.lru_cache(maxsize=None)
def _movielens_pack(layout: str) -> bucketed.BucketedSparseFeatures:
    """A real host pack at the MovieLens shape. Its mean (tile, bucket)
    segment is exactly MAX_SP entries, so level 1 fills and the variance
    tail spills to a grouped level 2 and the COO list: the "spill" case
    keeps that; the two level-1-only cases drop the spill and force the
    level-1 layout, keeping the real segment widths."""
    rng = np.random.default_rng(0)
    rows = np.repeat(np.arange(SPARSE_N, dtype=np.int64), SPARSE_NNZ)
    cols = rng.integers(0, SPARSE_D, size=SPARSE_N * SPARSE_NNZ)
    vals = rng.standard_normal(SPARSE_N * SPARSE_NNZ).astype(np.float32)
    bf = bucketed.pack_bucketed(
        rows, cols, vals, SPARSE_N, SPARSE_D, host_only=True,
        row_aligned={"rowalign": True, "grouped": False, "spill": None}[layout],
    )
    assert bf.level2 is not None and not bf.level2.row_aligned
    assert bf.overflow_vals.shape[0] > 0
    if layout == "spill":
        return bf
    none = np.zeros((0,), np.int32)
    return dataclasses.replace(
        bf, level2=None, overflow_rows=none, overflow_cols=none,
        overflow_vals=np.zeros((0,), np.float32),
    )


@pytest.mark.parametrize("layout", ["rowalign", "grouped", "spill"])
@pytest.mark.parametrize("kernel", ["matvec", "rmatvec", "fused"])
def test_sparse_kernels_compile_for_v5e(one_chip, kernel, layout):
    pack = _movielens_pack(layout)
    if layout != "spill":  # the spill case keeps the planner's own layout
        assert pack.level1.row_aligned == (layout == "rowalign")
    bf = _on(one_chip, pack)
    if kernel == "matvec":
        lowered = pallas_sparse.matvec.lower(bf, _vec(one_chip, SPARSE_D))
    elif kernel == "rmatvec":
        lowered = pallas_sparse.rmatvec.lower(bf, _vec(one_chip, SPARSE_N))
    else:
        assert pallas_sparse.fused_feasible(pack)
        n = _vec(one_chip, SPARSE_N)
        lowered = pallas_sparse.fused_value_gradient_sums.lower(
            LOGISTIC, _vec(one_chip, SPARSE_D), _scalar(one_chip), bf, n, n, n
        )
    assert "tpu_custom_call" in _compiled_text(lowered)


# ------------------------------------------------------ long, narrow planes

# A raw ELL plane at the MovieLens shape is (N, 9). XLA's TPU compiler takes
# minutes over a reshape that flattens such an array, or a gather indexed by
# it (157-181 s each at N = 200,000; 271 s for _project_entries at 2M on the
# v5e), and a second or two over the same work through the (9, N) transpose.
# These programs go through the transpose (the fixed effect's margins a plane
# of it at a time); the bound is loose enough for a loaded host and far below
# what the direct form costs.
NARROW_N, NARROW_K = 200_000, 9
NARROW_COMPILE_LIMIT_S = 60.0


@pytest.mark.parametrize("program", ["project_entries", "fe_margins_ell", "re_margins_ell"])
def test_narrow_planes_compile_in_seconds_for_v5e(one_chip, program):
    from photon_ml_tpu.data import device_assemble
    from photon_ml_tpu.data.containers import SparseFeatures
    from photon_ml_tpu.transformers.game_transformer import _fe_margins, _re_margins

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n, k, dim, entities = NARROW_N, NARROW_K, SERVE_D, NARROW_N // 145
    idx, val = spec((n, k), jnp.int32), spec((n, k), jnp.float32)
    ent = spec((n,), jnp.int32)
    if program == "project_entries":
        lowered = device_assemble._project_entries.lower(
            spec((276_000,), jnp.int32), spec((entities + 2,), jnp.int32),
            idx, val, ent, dimw=dim + 1,
        )
    elif program == "fe_margins_ell":
        lowered = _fe_margins.lower(
            SparseFeatures(idx, val, dim), spec((dim,), jnp.float32), None
        )
    else:
        lowered = _re_margins.lower(
            SparseFeatures(idx, val, 208), ent,
            spec((entities + 1, 208), jnp.float32), None,
        )
    t0 = time.perf_counter()
    lowered.compile()
    assert time.perf_counter() - t0 < NARROW_COMPILE_LIMIT_S


# ------------------------------------------- the ELL objective, plane by plane

# `lr-criteo.fit`'s solve (benchmarks/configs/lr-criteo.json): the ELL
# objective written a plane at a time (`SparseFeatures.matvec` / `rmatvec`)
# under L-BFGS's loop. Whether the mechanism engages is decided at compile
# time and the program's text says which: every gather takes the coefficient
# vector from memory space 1 (VMEM; `S(1)` in the layout), and nothing in the
# program makes an array of rows x nnz elements (the one-gather form made a
# dozen, 1.25 GB each, and gathered from HBM).
CRITEO_ROWS, CRITEO_NNZ, CRITEO_DIM, CRITEO_ITERATIONS = 8_000_000, 39, 1_000_000, 3
_HLO_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]*)\](\{[^}]*\})? ([\w\-]+)\(([^)]*)\)(.*)$"
)


_Instruction = collections.namedtuple(
    "_Instruction", "elements minor layout opcode operands op_name computation calls"
)
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) \(.*\) -> .* \{$")


def _array_instructions(text):
    """name -> _Instruction of every instruction of a compiled program's
    text whose result is one array (`operands` are names; `minor` is the
    size of the dimension the layout puts on the lanes; `computation` is the
    computation that holds it and `calls` the one a fusion runs)."""
    found = {}
    computation = ""
    for line in text.splitlines():
        c = _HLO_COMPUTATION.match(line)
        if c:
            computation = c.group(1)
        m = _HLO_INSTRUCTION.match(line)
        if m:
            name, dims, layout, opcode, operands, rest = m.groups()
            op_name = re.search(r'op_name="([^"]*)"', rest)
            dims = [int(d) for d in dims.split(",") if d]
            # The minor dimension's size: the first index of `{1,0:T(8,128)}`.
            minor = re.match(r"\{(\d+)", layout or "")
            found[name] = _Instruction(
                math.prod(dims), dims[int(minor.group(1))] if minor and dims else 1,
                layout or "", opcode,
                [o.strip() for o in operands.split(",")], op_name.group(1) if op_name else "",
                computation, (re.search(r"calls=(%[\w.\-]+)", rest) or [None, ""])[1],
            )
    return found


def _buffers(instructions):
    """The instructions that are arrays in memory: those outside the fusions'
    own computations, where an instruction is a value in flight."""
    fused = {i.calls for i in instructions.values() if i.opcode == "fusion"}
    return {name: i for name, i in instructions.items() if i.computation not in fused}


def _criteo_span_classes():
    """The classes `annotate_spans` reads on a shard of `lr-criteo`'s fields."""
    import json
    import os

    from photon_ml_tpu.data.containers import span_class

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs", "lr-criteo.json")) as f:
        sizes = json.load(f)["generator"]["field_sizes"]
    assert len(sizes) == CRITEO_NNZ and sum(sizes) == CRITEO_DIM
    return tuple(span_class(0, size - 1) for size in sizes)


def _assert_the_dense_span_loops(instructions, rows, classes, evaluations):
    """What the program of an annotated shard must hold: for every class one
    rolled loop an evaluation of the margins, whose body multiplies a plane by
    compare, select and reduce inside one fusion (an array of class x rows
    elements is a value in flight, never a buffer) and holds no gather."""
    buffers = _buffers(instructions)
    narrow = sorted({c for c in classes if c})
    in_flight = collections.defaultdict(set)  # computation -> the classes of its class x rows values
    for i in instructions.values():
        for c in narrow:
            if i.elements == c * rows:
                in_flight[i.computation].add(c)
    assert set().union(*in_flight.values()) == set(narrow), sorted(in_flight.values())
    assert not [n for n, i in buffers.items() if i.elements >= min(narrow) * rows]
    dense_span = [i for i in buffers.values() if i.opcode == "fusion" and i.calls in in_flight]
    # A rolled loop holds one fusion a class; unrolled, a class of 21 planes
    # would hold 21.
    per_class = collections.Counter(c for i in dense_span for c in in_flight[i.calls])
    assert all(per_class[c] == evaluations for c in narrow), per_class
    bodies = {i.computation for i in dense_span}
    strays = [
        (n, i.op_name) for n, i in buffers.items()
        if i.computation in bodies and i.op_name.endswith("/gather")
    ]
    assert not strays, strays[:5]


@pytest.mark.parametrize("planes", ["gathered", "dense_span"])
def test_the_plane_loop_gathers_from_vmem_at_the_criteo_shape(one_chip, planes):
    """`planes` is what the shard shows: `gathered`, no narrow plane (the
    program of PR 32); `dense_span`, `lr-criteo`'s own fields, 27 of whose 39
    planes' margins are dense spans and 12 gathered; all 39 are scatter-added."""
    from photon_ml_tpu.data.containers import LabeledData, SparseFeatures, _with_spans
    from photon_ml_tpu.ops import objective
    from photon_ml_tpu.optimize.lbfgs import minimize_lbfgs

    classes = _criteo_span_classes() if planes == "dense_span" else ()
    table_pad = max(classes) if classes else 1

    def solve(indices, values, span_lo, labels, offsets, weights, w0):
        feats = _with_spans(SparseFeatures(indices, values, CRITEO_DIM), span_lo, classes)
        data = LabeledData(feats, labels, offsets, weights)
        return minimize_lbfgs(
            lambda w: objective.value_and_gradient(LOGISTIC, w, data, None, 1.0, use_pallas=False),
            w0, max_iterations=CRITEO_ITERATIONS, tolerance=1e-9,
        ).coefficients

    def plane(dtype):
        return jax.ShapeDtypeStruct((CRITEO_ROWS, CRITEO_NNZ), dtype, sharding=one_chip)

    rows = _vec(one_chip, CRITEO_ROWS)
    instructions = _array_instructions(_compiled_text(jax.jit(solve).lower(
        plane(jnp.int32), plane(jnp.float32), _vec(one_chip, CRITEO_NNZ, jnp.int32) if classes else None,
        rows, rows, rows, _vec(one_chip, CRITEO_DIM)
    )))
    # Parameters, bitcasts of them and loop-carried tuple elements hold the
    # stored planes; anything else of that size is a temporary.
    # Without a narrow plane every instruction is scanned, the fusions' own
    # too, as before there was an annotation; a dense span's class x rows
    # values, in flight inside its fusion, are more than rows x nnz.
    scanned = _buffers(instructions) if classes else instructions
    temporaries = [
        (name, i.opcode) for name, i in scanned.items()
        if i.elements >= CRITEO_ROWS * CRITEO_NNZ
        and i.opcode not in ("parameter", "bitcast", "get-tuple-element")
    ]
    assert not temporaries, temporaries[:5]
    if classes:
        assert sum(1 for c in classes if c) == 27 and sorted(set(classes)) == [0, 128, 256, 512, 1024, 2048]
        _assert_the_dense_span_loops(instructions, CRITEO_ROWS, classes, evaluations=2)
        # The planes' scatter-add still sums into an accumulator in VMEM.
        accumulators = [
            i for i in instructions.values()
            if i.opcode == "fusion" and i.elements == CRITEO_DIM and i.op_name.endswith("/scatter-add")
        ]
        assert len(accumulators) == 2 and all("S(1)" in i.layout for i in accumulators), accumulators
    # Every plane's gather, in the first evaluation (one loop deep: the
    # plane loop) and in the line search (under L-BFGS's loops), reads its
    # table, the plane loop's own copy of the coefficients, from VMEM.
    tables = {
        i.op_name.count("while/body"): instructions[i.operands[0]]
        for i in instructions.values()
        if i.opcode == "fusion" and i.elements == CRITEO_ROWS and i.op_name.endswith("/gather")
    }
    assert min(tables) == 1 and max(tables) >= 3, sorted(tables)
    for depth, table in tables.items():
        assert table.elements == CRITEO_DIM + table_pad and "S(1)" in table.layout, (depth, table)


# `lr-criteo-full.fit`'s programs: the same solve over four chips, 11,460,155
# rows a chip, through `ShardedDispatch`, and the scoring program of the
# training rows. Every chip runs the one-chip plane loops on its own rows.
FULL_ROWS_A_CHIP, CHIPS = 11_460_155, 4
_COLLECTIVE = re.compile(r" = (.+?) (all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)(?:-start)?\(")


@pytest.mark.parametrize("planes", ["gathered", "dense_span"])
@pytest.mark.parametrize("program", ["solve", "score"])
def test_the_sharded_plane_loop_at_the_whole_criteo_shape(four_chips, program, planes):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from photon_ml_tpu.data.containers import LabeledData, SparseFeatures, _with_spans
    from photon_ml_tpu.ops import objective
    from photon_ml_tpu.optimize.lbfgs import minimize_lbfgs
    from photon_ml_tpu.transformers.game_transformer import _fe_margins

    n = FULL_ROWS_A_CHIP * CHIPS
    planes, rows, whole = (NamedSharding(four_chips, spec) for spec in (P("data", None), P("data"), P()))
    indices = jax.ShapeDtypeStruct((n, CRITEO_NNZ), jnp.int32, sharding=planes)
    values = jax.ShapeDtypeStruct((n, CRITEO_NNZ), jnp.float32, sharding=planes)
    dispatch = pallas_glm.ShardedDispatch(four_chips, "data")
    # As `annotate_spans` hands them on: the classes static, the least ids
    # replicated beside the sharded planes.
    classes = _criteo_span_classes() if planes == "dense_span" else ()
    span_lo = _vec(whole, CRITEO_NNZ, jnp.int32) if classes else None
    table_pad = max(classes) if classes else 1

    def solve(indices, values, span_lo, labels, offsets, weights, w0):
        feats = _with_spans(SparseFeatures(indices, values, CRITEO_DIM), span_lo, classes)
        data = LabeledData(feats, labels, offsets, weights)
        return minimize_lbfgs(
            lambda w: objective.value_and_gradient(LOGISTIC, w, data, None, 1.0, use_pallas=dispatch),
            w0, max_iterations=CRITEO_ITERATIONS, tolerance=1e-9,
        ).coefficients

    if program == "solve":
        text = _compiled_text(jax.jit(solve).lower(
            indices, values, span_lo, _vec(rows, n), _vec(rows, n), _vec(rows, n), _vec(whole, CRITEO_DIM)
        ))
    else:
        text = _compiled_text(_fe_margins.lower(
            _with_spans(SparseFeatures(indices, values, CRITEO_DIM), span_lo, classes),
            _vec(whole, CRITEO_DIM), None,
        ))
    instructions = _array_instructions(text)
    # No array of a chip's rows x 39 elements is made, on any chip.
    scanned = _buffers(instructions) if classes else instructions
    temporaries = [
        (name, i.opcode) for name, i in scanned.items()
        if i.elements >= FULL_ROWS_A_CHIP * CRITEO_NNZ
        and i.opcode not in ("parameter", "bitcast", "get-tuple-element")
    ]
    assert not temporaries, temporaries[:5]
    if classes:  # every chip runs the one-chip dense-span loops on its own rows
        _assert_the_dense_span_loops(instructions, FULL_ROWS_A_CHIP, classes, evaluations=2 if program == "solve" else 1)
    # Every plane gather is of one chip's rows and reads its table from VMEM.
    tables = {
        i.op_name.count("while/body"): instructions[i.operands[0]]
        for i in instructions.values()
        if i.opcode == "fusion" and i.elements == FULL_ROWS_A_CHIP and i.op_name.endswith("/gather")
    }
    assert tables and min(tables) == 1, sorted(tables)
    for depth, table in tables.items():
        assert table.elements == CRITEO_DIM + table_pad and "S(1)" in table.layout, (depth, table)
    collectives = [(m.group(2), m.group(1)) for m in map(_COLLECTIVE.search, text.splitlines()) if m]
    if program == "solve":
        # One reduction an evaluation (the first, and the line search's), of
        # the value and the gradient together; nothing else crosses chips.
        assert max(tables) >= 3, sorted(tables)
        assert [kind for kind, _ in collectives] == ["all-reduce"] * 2, collectives
        assert all(f"f32[{CRITEO_DIM}]" in shapes for _, shapes in collectives), collectives
    else:
        assert not collectives, collectives


# ------------------------------------- the dense objective's per-row operands

# `lr-epsilon.fit`'s solve: the dense value+gradient kernel under L-BFGS's
# loops. On the chip an array's minor dimension is padded to 128 lanes, so an
# `f32[400000,1]` column is 204.8 MB for 1.6 MB of numbers: the kernel took
# labels, offsets and weights as such columns until PR 35, each made by a
# `reshape` that was a 205 MB copy at every evaluation (18 a fit), and read
# the padding beside X. Now they go in as `f32[1,400000]` rows.


def _default_layout(one_chip, shape, dtype):
    """How the chip's compiler lays a program's parameter of this shape and
    dtype when nobody says: `(0, 1)` row-major, `(1, 0)` column-major."""
    compiled = jax.jit(lambda x: x[0, 0]).lower(
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    ).compile()
    return tuple(compiled.input_formats[0][0].layout.major_to_minor)


def _whole_matrix_relayouts(instructions, shape):
    return [
        (name, i.opcode, i.layout) for name, i in instructions.items()
        if i.opcode in ("copy", "transpose") and i.elements >= math.prod(shape)
    ]


def test_the_dense_solve_holds_no_padded_column_at_the_epsilon_shape(one_chip):
    from photon_ml_tpu.data.containers import LabeledData
    from photon_ml_tpu.ops import objective
    from photon_ml_tpu.optimize.lbfgs import minimize_lbfgs

    # X as the coordinate hands it over since PR 37: lying as the compiler
    # lays the shape by default, column-major here, and said to lie so.
    shape = (EPSILON_N, EPSILON_D)
    column_major = _default_layout(one_chip, shape, jnp.bfloat16) == (1, 0)
    assert column_major

    def solve(features, labels, offsets, weights, w0):
        data = LabeledData(features, labels, offsets, weights, column_major=column_major)
        return minimize_lbfgs(
            lambda w: objective.value_and_gradient(LOGISTIC, w, data, None, 1.0, use_pallas=True),
            w0, max_iterations=EPSILON_ITERATIONS, tolerance=1e-7,
        ).coefficients

    rows = _vec(one_chip, EPSILON_N)
    instructions = _array_instructions(_compiled_text(jax.jit(solve).lower(
        jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip),
        rows, rows, rows, _vec(one_chip, EPSILON_D),
    )))
    # The kernel is there, before the loops and in the line search.
    calls = {
        i.op_name.count("while/body") for i in instructions.values()
        if i.opcode == "get-tuple-element" and i.op_name.endswith("value_gradient_sums/pallas_call")
    }
    assert calls == {0, 2}, sorted(calls)
    # The program takes X as it lies, column-major, and no instruction
    # relays it: the kernels read (d, tile) blocks of X^T, a bitcast. Read
    # as a row-major matrix the same text began with
    # `copy(bf16[400000,2000]{0,1:...} %features)`, 1.6 GB read and 1.6 GB
    # written in every execution (5.1 ms of a 42.5 ms fit).
    (x,) = [
        i for i in instructions.values()
        if i.opcode == "parameter" and i.elements == EPSILON_N * EPSILON_D
    ]
    assert x.layout.startswith("{0,1:"), x.layout
    assert not _whole_matrix_relayouts(instructions, shape)
    # No array of the rows' size puts fewer than 128 numbers on the lanes,
    # in the loops or before them.
    padded = [
        (name, i.opcode, i.layout) for name, i in instructions.items()
        if i.elements >= EPSILON_N and i.minor < 128
    ]
    assert not padded, padded[:5]
    # What is left of the reshapes: XLA keeps one for each row operand (a
    # vector's `T(1024)` tiles pad 400,000 elements to 400,384, a row's
    # `T(1,128)` tiles do not, so the two are not one buffer), before the
    # loops and in them, and it moves the 1.6 MB of numbers, not 205 MB.
    reshapes = [i for i in instructions.values() if i.opcode == "reshape" and i.elements >= EPSILON_N]
    assert len(reshapes) <= 6 and all(i.elements == i.minor == EPSILON_N for i in reshapes), reshapes


def test_the_tron_solve_runs_both_kernels_as_x_lies_at_the_epsilon_shape(one_chip):
    """`lr-epsilon-tron.fit`'s solve (ISSUE 40): the Hessian-vector kernel in
    the CG loop of the trust-region loop, the value+gradient kernel before the
    loops and once a trial step, both reading the column-major bfloat16 matrix
    as (d, tile) blocks of X^T: the chip's compiler takes the program, and
    nothing in it relays the matrix."""
    from photon_ml_tpu.data.containers import LabeledData
    from photon_ml_tpu.ops import objective
    from photon_ml_tpu.optimize.tron import minimize_tron

    shape = (EPSILON_N, EPSILON_D)

    def solve(features, labels, offsets, weights, w0):
        data = LabeledData(features, labels, offsets, weights, column_major=True)
        return minimize_tron(
            lambda w: objective.value_and_gradient(LOGISTIC, w, data, None, 1.0, use_pallas=True),
            lambda w, v: objective.hessian_vector(LOGISTIC, w, v, data, None, 1.0, use_pallas=True),
            w0, max_iterations=15, tolerance=1e-5,
        ).coefficients

    rows = _vec(one_chip, EPSILON_N)
    instructions = _array_instructions(_compiled_text(jax.jit(solve).lower(
        jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip),
        rows, rows, rows, _vec(one_chip, EPSILON_D),
    )))

    def depths(kernel):
        return {
            i.op_name.count("while/body") for i in instructions.values()
            if i.opcode == "get-tuple-element" and i.op_name.endswith(f"{kernel}/pallas_call")
        }

    assert depths("value_gradient_sums") == {0, 1}
    assert depths("hessian_vector_sums") == {2}
    (x,) = [
        i for i in instructions.values()
        if i.opcode == "parameter" and i.elements == EPSILON_N * EPSILON_D
    ]
    assert x.layout.startswith("{0,1:"), x.layout
    assert not _whole_matrix_relayouts(instructions, shape)


# The observation `column_major` rests on (PR 37): the compiler's DEFAULT
# layout for a 2-D array follows its shape. Where the feature width is no
# multiple of 128 and the row count is, column-major pads nothing and
# row-major pads d up to the next 128, and the compiler takes column-major;
# Mosaic constrains a kernel's operand to row-major. So the kernels read a
# matrix as it lies, (d, tile) blocks of X^T where it lies column-major, and
# nothing relays it; told the wrong way, XLA relays the whole matrix before
# the call. A compiler that changes its default fails here, not in a
# benchmark (the program reads the layout from the array, not from a rule).
LAID_SHAPES = [(EPSILON_N, EPSILON_D), (100_000, EPSILON_D), (DENSE_N, DENSE_D), (EPSILON_N, 2_048)]


@pytest.mark.parametrize("x_dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", LAID_SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_a_kernel_that_reads_x_as_it_lies_relays_nothing(one_chip, shape, x_dtype):
    n, d = shape
    column_major = _default_layout(one_chip, shape, x_dtype) == (1, 0)
    assert column_major == (d % 128 != 0)
    X = jax.ShapeDtypeStruct(shape, x_dtype, sharding=one_chip)
    w, rows, s = _vec(one_chip, d), _vec(one_chip, n), _scalar(one_chip)

    def relayouts(told):
        text = _compiled_text(pallas_glm.value_gradient_sums.lower(
            LOGISTIC, w, s, X, rows, rows, rows, column_major=told
        ))
        assert "tpu_custom_call" in text
        return _whole_matrix_relayouts(_array_instructions(text), shape)

    assert not relayouts(column_major)
    assert relayouts(not column_major)


# ----------------------------------------------------------------- serving


def test_serving_bucket_program_compiles_for_v5e(one_chip):
    """One bucket program of the engine serving chip_smoke's model: fixed
    effect + per-user + per-movie rows, request scratch donated as the
    engine donates it on an accelerator. No kernel is expected in it: the
    program is a gather and per-row reduces."""
    from photon_ml_tpu.serving.engine import _score_program

    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    rows = jax.ShapeDtypeStruct((SERVE_BATCH,), jnp.int32, sharding=one_chip)
    program = jax.jit(
        _score_program,
        static_argnames=("kinds", "shards", "meshes", "task"),
        donate_argnums=(0, 1, 2, 3),
    )
    compiled = program.lower(
        f32(SERVE_BATCH),
        {"g": f32(SERVE_BATCH, SERVE_D)},
        (None, rows, rows),
        (None, None, None),
        (f32(SERVE_D), f32(SERVE_USERS, SERVE_D), f32(SERVE_MOVIES, SERVE_D)),
        (None, None, None),
        kinds=("fe", "re", "re"),
        shards=("g", "g", "g"),
        meshes=(None, None, None),
        task=TaskType.LOGISTIC_REGRESSION,
    ).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 4 * SERVE_USERS * SERVE_D
    assert "tpu_custom_call" not in compiled.as_text()
