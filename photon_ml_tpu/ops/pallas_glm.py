"""Pallas TPU kernels for the GLM hot loop: fused value+gradient and
fused Hessian-vector product.

Why these exist: the single hottest op in the framework is the fixed-effect
objective evaluation — the TPU-native descendant of the reference's
ValueAndGradientAggregator hot loop (photon-lib
function/glm/ValueAndGradientAggregator.scala:137-161, reduced via
RDD.treeAggregate at :248-252). Expressed as plain XLA
(`ops.objective.value_and_gradient`), that op streams the design matrix X
from HBM **twice** per evaluation — once for the forward matvec `z = X @ w`
and once for the gradient `g = X^T u` — because XLA will not fuse two
matmuls that share an operand into one pass. At 1M x 512 f32 that is ~4 GB
of HBM traffic per L-BFGS iteration where ~2 GB suffices; the op is
bandwidth-bound, so halving traffic ~doubles throughput.

The kernels here stream each row-tile of X from HBM into VMEM **once** and
run both MXU contractions on it while it is resident:

    per row-tile T:
        z_T   = w . X_T^T              (MXU, [k, D] . [TILE_N, D]^T -> [k, TILE_N])
        u_T   = weight_T * l'(z_T, y_T)   (VPU, [1, TILE_N])
        val  += sum(weight_T * l(z_T, y_T))
        g    += u_T . X_T              (MXU, [k, TILE_N] . [TILE_N, D] -> [k, D])

The Hessian-vector kernel additionally packs [w ; v] into a single
[2, D] left-hand side so the two forward matvecs TRON needs (margins and
`q = X @ v`) cost one MXU pass:

    zq_T  = [w ; v] . X_T^T            (MXU, [2, D] . [TILE_N, D]^T)
    r_T   = weight_T * l''(z_T, y_T) * q_T
    hv   += r_T . X_T

**Layout: every per-row operand is lane-dense — rows on the lane axis.**
Labels, offsets and weights go in as (1, N) rows blocked (1, TILE_N); the
margins, the loss and `u` are (1, TILE_N) in the kernel; the coefficients
and the gradient travel as (k, D) rows. No (N, 1) or (D, 1) column is made
anywhere: the chip lays a 2-D array in (8, 128) tiles and pads its minor
dimension to 128 lanes, so an f32[400000, 1] column is 204.8 MB for 1.6 MB
of numbers. Until PR 35 the kernels took columns: each `reshape(n, 1)` was
a 205 MB copy made again at every evaluation (three an evaluation, 18 a fit
of `lr-epsilon.fit`, 5.7 ms of 61.7), the kernel read 614 MB of padding
beside the 1,600 MB of X, every per-row quantity in it was a (520, 1) array
of 65 vregs with 8 live numbers each, and the gradient contracted X on its
row axis (a transposed-left matmul of the whole tile). With rows the
margins are the `q . k^T` form and the gradient a plain matmul with X as it
lies: X is never transposed. From an f32[N] vector a (1, N) row is the same
numbers in the same order (XLA still writes a `reshape`: a vector's T(1024)
tiles pad N up, a row's T(1,128) tiles do not; 1.6 MB, microseconds).

Both kernels return *raw sums* (including `sum(u)` / `sum(r)`), so the
normalization-as-coefficient-algebra trick (ops/normalization.py, mirroring
ValueAndGradientAggregator.scala:36-80) stays entirely outside the kernel:
callers pass the already-effective coefficient vector and fold shift/factor
corrections into the returned sums. Grid steps on TPU execute sequentially
per core, so accumulating into an output block whose index_map is constant
is the standard safe reduction pattern.

Dispatch policy (`should_use`): the kernels engage only for problems where
the fusion pays — dense f32/bf16 X, N >= _MIN_ROWS, D >= _MIN_COLS, and a
row tile that fits the VMEM budget. The vmapped random-effect entity solves
(small N, small D per entity) and the sparse path fall through to XLA
automatically; no flags thread through the optimizer stack. On non-TPU
backends the kernels run only in interpret mode (tests); the XLA path is
used otherwise.

Precision/roofline history (v5e, 1M x 512 f32): at HIGHEST (6 bf16 MXU
  passes per matmul) the kernels were MXU-bound, not HBM-bound — the
  width-1/2 thin operand pads to an MXU tile and HIGHEST multiplies the
  passes, so bf16 X (half the HBM bytes) measured the SAME wall per pass
  (r03: 179-217 GB/s effective). DEFAULT was faster but its bf16-rounded
  gradients cost ~1.5x more line-search evaluations. The current default
  'hilo' (see the PHOTON_PALLAS_PRECISION block below) computes each
  matmul in TWO bf16 passes over a hi/lo split of X with the thin
  operand's hi/lo halves stacked along its free (sublane) dimension — all
  four cross products, 3x less MXU work than HIGHEST, at ~2e-5 agreement
  with a float64 host reference (f32 accumulation is the shared accuracy
  floor).

bf16-STORED X (r05, `prefers_bf16_storage`): the training design matrix is
  additionally stored bf16 by the fixed-effect coordinate when the kernels
  engage — half the HBM bytes per pass AND a single MXU pass per
  contraction (_x_parts: the lo half of X is zero by construction, so
  only the thin operand is hi/lo split). Quantization is data-level (~2^-8, once);
  the optimizer solves that problem exactly, so fn_evals stay at f32
  behavior (measured 27 -> 31 at 1M x 512, wall 0.124 -> 0.104 s/solve,
  469 -> 641 GB/s f32-normalized effective, coef diff 4e-4 relative).

How X lies, and how the kernels read it (PR 37, `column_major`): as it lies.
  The TPU compiler's DEFAULT layout for a 2-D array follows its shape: it
  lays [1048576, 512] or [400000, 2048] row-major and [400000, 2000]
  COLUMN-major (d is no multiple of 128 and n is, so column-major pads
  nothing where row-major pads 2,000 lanes to 2,048). Mosaic constrains a
  kernel's operand to row-major, so a kernel handed the (n, d) matrix of
  `lr-epsilon` made XLA relay all of it first: a `copy` of 1.6 GB read and
  1.6 GB written in every execution of `train_fn`, 5.1 ms of a 42.5 ms fit.
  A column-major (n, d) matrix IS a row-major (d, n) one, byte for byte, so
  for such a matrix the kernels take X^T (a bitcast) and read (d, tile)
  blocks of it: the margins become the plain matmul `w . X^T_tile` and the
  gradient the `q . k^T` form `u . (X^T_tile)^T` — the two contractions of
  the row-major kernel with their X axes exchanged, the same tile, the same
  order of summation, the same bits (2.25 ms a call against 2.27, v5e).
  Which it is comes from the array itself, read once where it is concrete
  (`lies_row_major`, at the coordinate's construction) and carried with
  the data (`LabeledData.column_major`); it is never guessed from a shape,
  and a wrong flag costs a relayout, never a wrong number. The other cure,
  storing X row-major by an explicit `Format`, does not survive JAX's
  persistent compilation cache at jax 0.9.0: an executable read back from
  the cache reports default layouts for its results whatever it was
  compiled for, so the array that the storing program returns in a second
  process claims to be column-major, holds row-major bytes, and the next
  program refuses it (PERF.md section 6, PR 37).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM
_SMEM = pltpu.SMEM

from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.utils.knobs import get_knob

Array = jax.Array

# Row-tile height: the rows of X a grid step holds in VMEM, and the lane-axis
# block of every per-row operand (labels, offsets, weights travel as (1, n)
# rows, blocked (1, tile)), so it is a multiple of 128 lanes. 1024 rows x 512
# features x 4 B = 2 MB per X tile; with double buffering and the (k, d)
# coefficient rows this stays well inside the ~16 MB/core VMEM envelope.
#
# Env overrides are validated leniently: a bad value falls back to the
# default with a warning instead of making the whole package unimportable
# for code paths that never touch the kernels.
def _env_tile() -> int:
    raw = str(get_knob("PHOTON_PALLAS_TILE"))
    try:
        tile = int(raw)
        if tile < 128 or tile % 128 != 0:
            raise ValueError
        if tile > 1024:
            # A 2048-row tile at d=512 in hilo mode is exactly the 8 MB
            # VMEM budget — a working set this module's own notes measure
            # as collapsing to ~13 GB/s. The budget check alone does not
            # exclude it, so cap the override at the measured-good 1024.
            import logging

            logging.getLogger(__name__).warning(
                "PHOTON_PALLAS_TILE=%d exceeds the measured-good maximum "
                "1024 (larger tiles thrash VMEM); capping at 1024",
                tile,
            )
            return 1024
        return tile
    except ValueError:
        import logging

        logging.getLogger(__name__).warning(
            "PHOTON_PALLAS_TILE=%r: must be a positive multiple of 128 (the "
            "row tile is the lane-axis block of the per-row operands); "
            "using the default 1024",
            raw,
        )
        return 1024


_TILE_N = _env_tile()
# VMEM budget for one X tile's WORKING SET (bytes): the f32 tile plus, in
# hilo mode, its bf16 hi/lo copies (another 4 bytes/elem). Wider problems
# shrink the row tile (amortizing grid overhead less) down to _TILE_MIN;
# wider still falls back to XLA rather than blocking the feature dimension
# (a D-blocked variant would need a second pass for margins; XLA is already
# fine for very wide problems). Tile 1024 measured 281 GB/s vs 179 at 512
# on v5e (grid-step overhead amortization), with slightly FEWER line-search
# evals; 2048 blows VMEM and collapses to ~13 GB/s.
_TILE_BYTES_LIMIT = 8 * 1024 * 1024
_TILE_MIN = 256
_MIN_ROWS = max(2048, 2 * _TILE_N)
_MIN_COLS = 128

_DISABLE_ENV = "PHOTON_DISABLE_PALLAS"

# MXU precision for the kernels' thin matmuls. The default 'hilo' runs TWO
# bf16 passes over a hi/lo split of X with the thin operand's hi/lo halves
# stacked along its free (sublane) dimension — the MXU pads that dimension to
# a tile anyway, so the extra rows are free and all four cross products
# land in 2 passes instead of HIGHEST's 6 (the r03 kernels were MXU-bound
# at HIGHEST precisely because of those passes; see the module docstring's
# roofline note). Accuracy: each operand is represented hi+lo to ~2^-16
# relative, so results match a float64 host reference to ~2e-5 — the same
# level HIGHEST achieved (f32 accumulation is the shared floor). This is
# the same decomposition pallas_sparse._onehot_contract uses.
# PHOTON_PALLAS_PRECISION=highest|high|default selects a classic MXU
# precision instead.
_PRECISION_NAMES = {
    "highest": jax.lax.Precision.HIGHEST,
    "high": jax.lax.Precision.HIGH,
    "default": jax.lax.Precision.DEFAULT,
    "hilo": None,  # handled by _rows_dot, not lax precision
}
_prec_name = str(get_knob("PHOTON_PALLAS_PRECISION"))
_PREC_MODE = _prec_name
_PRECISION = _PRECISION_NAMES[_prec_name]

# Kill switch. Initialized from PHOTON_DISABLE_PALLAS at import; flip at
# runtime with `set_enabled`. NOTE: `should_use` runs at *trace* time, so a
# change only affects jit programs traced afterwards — already-compiled
# coordinates keep their baked-in path. Set the env var before building
# coordinates (or call set_enabled first) to be sure.
_ENABLED = not get_knob(_DISABLE_ENV)

# Test hook: when True, `should_use` accepts non-TPU backends and the
# objective-layer dispatch passes interpret=True, so CPU CI exercises the
# real kernel bodies (the conftest mesh stands in for multi-chip the same
# way). Never set in production paths.
FORCE_INTERPRET = False


def set_enabled(on: bool) -> None:
    """Enable/disable the fused kernels for jit programs traced after this
    call (existing compiled programs are unaffected — see module note)."""
    global _ENABLED
    _ENABLED = bool(on)


def is_enabled() -> bool:
    return _ENABLED


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


_HEALTHY = False


def kernels_healthy() -> bool:
    """One-time compiled smoke test of both kernels against the XLA path:
    returns True, or raises.

    The kernels are exercised in interpreter mode by CI; only the chip's
    own compiler can refuse them, and only the chip can miscompute them.
    Probing a tiny problem once per process (numerics, not just absence of
    exceptions) turns either into an error at dispatch-decision time that
    carries the compiler's message — never a quiet switch to the XLA
    objective, which would be a different program under the same name.
    PHOTON_DISABLE_PALLAS=1 is the one explicit way to run the XLA path.
    """
    global _HEALTHY
    if _HEALTHY:
        return True
    import numpy as np

    from photon_ml_tpu.ops.losses import LOGISTIC

    rng = np.random.default_rng(0)
    n, d = 2 * _TILE_N, 128
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    y = jnp.asarray((rng.uniform(size=n) > 0.5).astype(np.float32))
    off = jnp.zeros((n,))
    wt = jnp.ones((n,))
    w = jnp.asarray((rng.normal(size=d) * 0.1).astype(np.float32))
    zero = jnp.zeros(())

    try:
        val, g, _ = value_gradient_sums(
            LOGISTIC, w, zero, X, y, off, wt, interpret=FORCE_INTERPRET
        )
        hv, _ = hessian_vector_sums(
            LOGISTIC, w, zero, w, zero, X, y, off, wt, interpret=FORCE_INTERPRET
        )
        # dispatch admits bf16 X too; probe that lowering path as well (a
        # bf16-specific Mosaic failure must not bypass the gate).
        val_bf, g_bf, _ = value_gradient_sums(
            LOGISTIC, w, zero, X.astype(jnp.bfloat16), y, off, wt,
            interpret=FORCE_INTERPRET,
        )
        # and the read of a matrix that lies column-major, (d, tile) blocks
        # of X^T (here the probe's X is relaid for it; the sums are the same).
        val_cm, g_cm, _ = value_gradient_sums(
            LOGISTIC, w, zero, X, y, off, wt, interpret=FORCE_INTERPRET,
            column_major=True,
        )
        # and the Hessian-vector kernel in the form a TRON solve on such a
        # matrix calls it: bf16-stored and column-major at once (d = 2,000).
        hv_bf_cm, _ = hessian_vector_sums(
            LOGISTIC, w, zero, w, zero, X.astype(jnp.bfloat16), y, off, wt,
            interpret=FORCE_INTERPRET, column_major=True,
        )
        jax.block_until_ready((val, g, hv, val_bf, g_bf, val_cm, g_cm, hv_bf_cm))
    except Exception as exc:  # compile or runtime failure
        raise RuntimeError(
            f"pallas_glm kernels do not compile or run on the "
            f"{jax.default_backend()} backend ({type(exc).__name__}: {exc}); "
            f"set {_DISABLE_ENV}=1 to run the XLA objective instead"
        ) from exc
    z = X @ w
    u = wt * LOGISTIC.d1(z, y)
    val_ref = jnp.sum(wt * LOGISTIC.loss(z, y))
    g_ref = u @ X
    hv_ref = (wt * LOGISTIC.d2(z, y) * (X @ w)) @ X
    # The XLA reference path itself runs bf16 MXU passes on TPU
    # (default matmul precision) while the kernels run at HIGHEST, so
    # the two legitimately differ at bf16 rounding level (~0.4%).
    # The probe discriminates broken kernels (garbage/layout bugs are
    # orders of magnitude off), not rounding regimes. Bars pinned in
    # contracts.PALLAS_GATE_TOLERANCES (ISSUE 20 tolerance-pin).
    from photon_ml_tpu.utils.contracts import PALLAS_GATE_TOLERANCES

    g_scale = jnp.max(jnp.abs(g_ref))
    hv_scale = jnp.max(jnp.abs(hv_ref))
    checks = {
        "value": bool(jnp.allclose(val, val_ref, **PALLAS_GATE_TOLERANCES["f32"])),
        "gradient": bool(jnp.max(jnp.abs(g - g_ref)) < 2e-2 * g_scale + 1e-3),
        "hessian_vector": bool(
            jnp.max(jnp.abs(hv - hv_ref)) < 2e-2 * hv_scale + 1e-3
        ),
        # bf16 inputs round at ~0.4%; same broken-vs-rounding bar.
        "value_bf16": bool(
            jnp.allclose(val_bf, val_ref, **PALLAS_GATE_TOLERANCES["bf16"])
        ),
        "gradient_bf16": bool(
            jnp.max(jnp.abs(g_bf - g_ref)) < 5e-2 * g_scale + 1e-2
        ),
        "hessian_vector_bf16_column_major": bool(
            jnp.max(jnp.abs(hv_bf_cm - hv_ref)) < 5e-2 * hv_scale + 1e-2
        ),
        "value_column_major": bool(
            jnp.allclose(val_cm, val_ref, **PALLAS_GATE_TOLERANCES["f32"])
        ),
        "gradient_column_major": bool(
            jnp.max(jnp.abs(g_cm - g_ref)) < 2e-2 * g_scale + 1e-3
        ),
    }
    wrong = sorted(k for k, ok in checks.items() if not ok)
    if wrong:
        raise RuntimeError(
            f"pallas_glm kernels compiled on the {jax.default_backend()} "
            f"backend but disagree with the XLA objective on {wrong}; "
            f"set {_DISABLE_ENV}=1 to run the XLA objective instead"
        )
    _HEALTHY = True
    return True


@dataclasses.dataclass(frozen=True)
class ShardedDispatch:
    """Fused-kernel dispatch decision for batch-sharded data: run the
    single-device kernel per shard under shard_map and psum the raw sums
    over `axis` — the fused equivalent of the reference's treeAggregate
    combiner tree (ValueAndGradientAggregator.scala:248-252), with the
    per-partition hot loop on the MXU and the combine on ICI."""

    mesh: Mesh
    axis: str


DispatchMode = Union[bool, ShardedDispatch]


def _static_checks(features, w, n_rows: int) -> bool:
    """Shape/dtype/VMEM gating shared by all dispatch modes. `n_rows` is the
    PER-DEVICE row count the kernel will actually see."""
    if not isinstance(features, jax.Array) and not hasattr(features, "shape"):
        return False
    if getattr(features, "ndim", 0) != 2 or w.ndim != 1:
        return False
    d = features.shape[1]
    if n_rows < _MIN_ROWS or d < _MIN_COLS:
        return False
    if features.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    # Budget at the WORKING size (f32 upcast + hilo's bf16 hi/lo copies);
    # a too-wide problem shrinks the row tile until grid overhead would
    # dominate, then falls back to XLA.
    if _tile_for(d) < _TILE_MIN:
        return False
    return True


def dispatch(features, w: Array) -> DispatchMode:
    """Decide how (whether) the fused kernels replace the XLA objective path.

    Returns False (XLA), True (single-device fused kernel) or a
    `ShardedDispatch` (per-shard fused kernel + psum under shard_map).

    A pallas_call is an opaque custom call to GSPMD: invoked directly on a
    sharded X it would all-gather the batch onto every device — the opposite
    of the intended win. So multi-device engagement requires a *concrete*
    array whose committed sharding this function can read: a NamedSharding
    over a 1-D mesh, batch axis sharded, feature axis replicated. Inside a
    jit trace (tracers carry no committed sharding) only a single visible
    device engages the kernel; multi-chip callers decide at coordinate
    construction time on the concrete array (FixedEffectCoordinate).
    """
    if not _ENABLED:
        return False
    if _interpret_default() and not FORCE_INTERPRET:
        # Interpret mode is for tests; never auto-engage it in production
        # CPU runs (it is slower than XLA).
        return False
    if getattr(features, "ndim", 0) != 2:
        return False
    n = features.shape[0]

    sharding = getattr(features, "sharding", None)
    n_devices: Optional[int] = None
    if isinstance(features, jax.Array):
        try:
            n_devices = len(sharding.device_set)
        except Exception:
            n_devices = None  # tracer or abstract sharding: unknown placement

    if n_devices is not None and n_devices > 1:
        # Multi-device: engage only for the canonical batch-sharded layout.
        if not isinstance(sharding, NamedSharding):
            return False
        mesh, spec = sharding.mesh, sharding.spec
        if len(mesh.axis_names) != 1:
            return False
        axis = mesh.axis_names[0]
        if not spec or spec[0] != axis:
            return False
        if len(spec) > 1 and spec[1] is not None:
            return False
        if n % mesh.devices.size != 0:
            # shard_map requires even shards; fall back rather than pass the
            # gate and crash at call time (shard_game_dataset pads, but a
            # caller-built array might not).
            return False
        per_device_rows = n // mesh.devices.size
        if not _static_checks(features, w, per_device_rows):
            return False
        if not kernels_healthy():
            return False
        return ShardedDispatch(mesh, axis)

    if n_devices is None and jax.device_count() > 1:
        # Sharding unknown inside a trace; be conservative on multi-device
        # hosts — the XLA path is the one GSPMD partitions correctly.
        return False
    if not _static_checks(features, w, n):
        return False
    # Last (it compiles a probe once per process): the kernels must actually
    # work on this backend.
    return kernels_healthy()


def should_use(features, w: Array) -> bool:
    """Boolean view of `dispatch` for callers that cannot carry a mesh
    (trace-time auto dispatch in ops/objective.py)."""
    return dispatch(features, w) is True


def prefers_bf16_storage(features, w: Array) -> bool:
    """Should this dense f32 design matrix be STORED bf16 for training?

    True when the fused kernels engage in hilo mode: bf16 storage halves
    the HBM bytes streamed per objective evaluation AND halves the MXU
    passes (_x_parts), while every multiply stays exact for the stored
    data (the thin operand is hi/lo split, never quantized). The quantization is
    data-level (~2^-8 relative on X entries, once) — the optimizer then
    solves that problem EXACTLY, so line searches and fn_evals behave as
    at f32, unlike bf16-rounded arithmetic on f32 data (which the r03
    DEFAULT-precision experiment measured at ~1.5x fn_evals). Opt out with
    PHOTON_DENSE_BF16X=0. Callers convert once at coordinate construction
    (game/coordinate.py) and train AND score on the converted array so
    coordinate-descent residuals stay consistent. The converted array lies
    as the device lays its shape by default (a bare `astype`) and is never
    relaid: the coordinate reads how it lies (`lies_row_major`) and the
    kernels read it so (module docstring, "How X lies")."""
    if not get_knob("PHOTON_DENSE_BF16X"):
        return False
    if _PREC_MODE != "hilo":
        return False
    if getattr(features, "dtype", None) != jnp.float32:
        return False
    mode = dispatch(features, w)
    return mode is True or isinstance(mode, ShardedDispatch)


def lies_row_major(features: Array) -> bool:
    """Does this concrete 2-D array lie row-major on its device(s)? False
    says: hand the kernels `column_major=True`. Read from the array, never
    inferred from its shape: the default for a shape is the compiler's to
    choose (column-major on a v5e where d is no multiple of 128 and n is, at
    jax 0.9.0; tests/test_tpu_compile.py records it)."""
    return tuple(features.format.layout.major_to_minor) == (0, 1)


def _tile_for(d: int) -> int:
    """Row-tile height for feature width d: the largest multiple of 128 not
    above _TILE_N whose VMEM working set (f32 tile + hilo's bf16 hi/lo
    copies) fits the budget — 512 at d = 2,000, 1,024 at d = 512. A multiple
    of 128 because the tile is also the lane-axis block of the (1, n) row
    operands, and because the gradient contracts over it in MXU chunks of
    128 (520 rows cost five chunks where 512 cost four). Below _TILE_MIN the
    grid overhead dominates — callers fall back to XLA (_static_checks)."""
    per_row = d * (8 if _PREC_MODE == "hilo" else 4)
    tile = min(_TILE_N, _TILE_BYTES_LIMIT // max(per_row, 1))
    return max(128, tile - tile % 128)


def _row_mask(n: int, tile: int, axis: int) -> Array:
    """Validity mask of the current grid step's rows, laid along `axis` of a
    2-D array: (1, tile) for the lane-dense per-row operands (axis 1),
    (tile, 1) for the X tile whose rows lie on sublanes (axis 0).

    Array sizes need not divide the block shape: Pallas pads boundary-block
    reads with undefined values, so every input is masked to exact zeros
    before use (a zero row contributes exactly zero to each accumulated sum —
    and masking x/y/offset as well as weight keeps NaN/Inf garbage from the
    padded rows out of 0*NaN traps in the losses and on the MXU).
    """
    shape = (tile, 1) if axis == 0 else (1, tile)
    rows = pl.program_id(0) * tile + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    return rows < n


def _hilo_split(a: Array) -> Tuple[Array, Array]:
    """Represent f32 `a` as bf16 hi + bf16 lo (exact to ~2^-16 relative)."""
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _x_parts(x: Array):
    """The X tile as the MXU operands of one contraction under the
    configured precision mode, computed once per tile and shared by both
    contractions: bf16-stored X as it lies (its lo half is zero by
    construction, so hilo runs ONE pass — half the HBM bytes and half the
    passes); f32 X as its bf16 hi/lo pair (two passes against HIGHEST's
    six); f32 X itself under a classic lax precision."""
    if _PREC_MODE != "hilo":
        return (x.astype(jnp.float32),)
    if x.dtype == jnp.bfloat16:
        return (x,)
    return _hilo_split(x.astype(jnp.float32))


def _rows_dot(rows: Array, x_parts, x_axis: int) -> Array:
    """`rows` (k, m) f32 contracted on its lane axis with axis `x_axis` of
    the X tile -> (k, ·) f32: the margins `w_rows · X^T` (the tile's
    feature axis) and the gradient `u_rows · X` (its row axis). On a
    (tile, d) tile of a row-major X those are the q·k^T form (x_axis 1)
    and a plain matmul (x_axis 0); on a (d, tile) tile of X^T, what a
    column-major X is, the other way round. X is the MXU's stationary
    operand both times and is never transposed.

    In hilo mode the f32 rows are hi/lo split and stacked along their free
    SUBLANE axis (the MXU pads k to a sublane tile anyway, so the extra rows
    are free): each pass over an X part computes both cross products, and
    the product is exact for bf16 data up to f32 accumulation — the rows are
    never quantized."""
    dims = (((1,), (x_axis,)), ((), ()))
    if _PREC_MODE != "hilo":
        return jax.lax.dot_general(
            rows, x_parts[0], dimension_numbers=dims,
            preferred_element_type=jnp.float32, precision=_PRECISION,
        )
    k = rows.shape[0]
    rows2 = jnp.concatenate(_hilo_split(rows), axis=0)
    out = sum(
        jax.lax.dot_general(
            rows2, part, dimension_numbers=dims,
            preferred_element_type=jnp.float32,
        )
        for part in x_parts
    )
    return out[:k] + out[k:]


def _masked_tile(n: int, tile: int, column_major: bool, x_ref, y_ref, off_ref, wt_ref):
    """This grid step's operands with the rows beyond n zeroed: the X tile
    as MXU operands (`_x_parts`) — (tile, d) of a row-major X, (d, tile) of
    the X^T that a column-major X is —, and labels, offsets and weights as
    (1, tile) rows — rows on the lane axis, four vregs at tile 512 where a
    (tile, 1) column took sixty-five. A zero in `u` does not silence a NaN
    in a padded row of X on the MXU, so X is masked too; the whole-tile
    select hides under the tile's DMA (2.18 ms a call with it on every
    step, 2.17 with none, at 400,000 x 2,000 bf16 on a v5e)."""
    rows = _row_mask(n, tile, 1)
    return (
        _x_parts(jnp.where(rows if column_major else _row_mask(n, tile, 0), x_ref[:], 0)),
        jnp.where(rows, y_ref[:], 0.0),
        jnp.where(rows, off_ref[:], 0.0),
        jnp.where(rows, wt_ref[:], 0.0),
    )


def _value_grad_kernel(loss: PointwiseLoss, n: int, tile: int, column_major: bool,
                       x_ref, y_ref, off_ref, wt_ref, w_ref, stats_ref, grad_ref):
    @pl.when(pl.program_id(0) == 0)
    def _():
        stats_ref[0, 0] = 0.0
        stats_ref[0, 1] = 0.0
        grad_ref[:] = jnp.zeros_like(grad_ref)

    # The X tile's feature axis and its row axis: (tile, d) or (d, tile).
    feature_axis, row_axis = (0, 1) if column_major else (1, 0)
    x_parts, y, off, wt = _masked_tile(n, tile, column_major, x_ref, y_ref, off_ref, wt_ref)
    z = _rows_dot(w_ref[:], x_parts, feature_axis) + off
    u = wt * loss.d1(z, y)
    stats_ref[0, 0] += jnp.sum(wt * loss.loss(z, y))
    stats_ref[0, 1] += jnp.sum(u)
    grad_ref[:] += _rows_dot(u, x_parts, row_axis)


def _hvp_kernel(loss: PointwiseLoss, n: int, tile: int, column_major: bool,
                x_ref, y_ref, off_ref, wt_ref, wv_ref, vshift_ref, stats_ref, hv_ref):
    @pl.when(pl.program_id(0) == 0)
    def _():
        stats_ref[0, 0] = 0.0
        hv_ref[:] = jnp.zeros_like(hv_ref)

    feature_axis, row_axis = (0, 1) if column_major else (1, 0)
    x_parts, y, off, wt = _masked_tile(n, tile, column_major, x_ref, y_ref, off_ref, wt_ref)
    zq = _rows_dot(wv_ref[:], x_parts, feature_axis)  # rows: the margins, X @ v
    r = wt * loss.d2(zq[0:1] + off, y) * (zq[1:2] + vshift_ref[0, 0])
    stats_ref[0, 0] += jnp.sum(r)
    hv_ref[:] += _rows_dot(r, x_parts, row_axis)


def _dense_call(kernel, name, features, row_operands, coef_rows, scalars,
                n_stats, flops_per_entry, interpret, column_major):
    """One pass of `kernel` over the row tiles of X, read as it lies: a
    (tile, d) block of a row-major X, or (`column_major`) a (d, tile) block
    of X^T, which for a matrix that lies column-major is a bitcast and no
    relayout (module docstring, "How X lies"). Every per-row operand
    goes in as a (1, n) row blocked (1, tile) — from an (n,) vector that is
    a bitcast — and the coefficients as (k, d) rows; the results are the
    (1, n_stats) scalar sums in SMEM and one (1, d) row. No (n, 1) or
    (d, 1) array is made: on the chip an f32 column pads its minor
    dimension of 1 to 128 lanes, 128 times its size, and the kernel would
    read the padding beside X."""
    n, d = features.shape
    tile = _tile_for(d)
    row = lambda a: a.reshape(1, n).astype(jnp.float32)
    row_spec = pl.BlockSpec((1, tile), lambda i: (0, i), memory_space=_VMEM)
    whole = lambda shape, space: pl.BlockSpec(shape, lambda i: (0, 0), memory_space=space)
    if column_major:
        x, x_spec = features.T, pl.BlockSpec((d, tile), lambda i: (0, i), memory_space=_VMEM)
    else:
        x, x_spec = features, pl.BlockSpec((tile, d), lambda i: (i, 0), memory_space=_VMEM)
    return pl.pallas_call(
        functools.partial(kernel, n, tile, column_major),
        # What a device trace calls this operation, said here and not left
        # to what JAX infers from the enclosing function: the benchmark's
        # readers match it (benchmarks/layers/kernels.py), and count an
        # evaluation by the (1, 2) pair that the first result is.
        name=name,
        grid=(pl.cdiv(n, tile),),
        in_specs=[
            x_spec,
            *[row_spec] * len(row_operands),
            whole(coef_rows.shape, _VMEM),
            *[whole((1, 1), _SMEM)] * len(scalars),
        ],
        out_specs=[whole((1, n_stats), _SMEM), whole((1, d), _VMEM)],
        out_shape=[
            jax.ShapeDtypeStruct((1, n_stats), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=flops_per_entry * n * d,
            bytes_accessed=n * d * features.dtype.itemsize
            + 4 * n * len(row_operands) + 4 * d * (coef_rows.shape[0] + 1),
            transcendentals=2 * n,
        ),
        interpret=interpret,
    )(
        x,
        *[row(a) for a in row_operands],
        coef_rows.astype(jnp.float32),
        *[jnp.asarray(s, jnp.float32).reshape(1, 1) for s in scalars],
    )


@functools.partial(jax.jit, static_argnames=("loss", "interpret", "column_major"))
def value_gradient_sums(
    loss: PointwiseLoss,
    w_eff: Array,
    shift: Array,
    features: Array,
    labels: Array,
    offsets: Array,
    weights: Array,
    *,
    interpret: bool = False,
    column_major: bool = False,
) -> Tuple[Array, Array, Array]:
    """Raw fused sums for the weighted GLM objective.

    Returns (value, grad_raw, sum_u) with
        value    = sum_i weight_i * l(z_i, y_i),   z = X @ w_eff + shift + offset
        grad_raw = X^T u,   u = weight * l'(z, y)
        sum_u    = sum_i u_i
    Normalization corrections (g = factor * (grad_raw - sum_u * shifts)) and
    L2 terms are the caller's job (ops/objective.py), exactly as the raw
    aggregator sums are post-processed in the reference. `column_major`
    says how `features` lies (`lies_row_major`): the same sums, bit for bit,
    read without a relayout from a matrix that lies so.
    """
    # Fold the scalar margin shift into offsets so the kernel sees one vector.
    stats, grad = _dense_call(
        functools.partial(_value_grad_kernel, loss), "value_gradient_sums",
        features, (labels, offsets + shift, weights), w_eff[None, :], (),
        n_stats=2, flops_per_entry=4, interpret=interpret,
        column_major=column_major,
    )
    return stats[0, 0], grad[0], stats[0, 1]


@functools.partial(jax.jit, static_argnames=("loss", "interpret", "column_major"))
def hessian_vector_sums(
    loss: PointwiseLoss,
    w_eff: Array,
    shift: Array,
    v_eff: Array,
    v_shift: Array,
    features: Array,
    labels: Array,
    offsets: Array,
    weights: Array,
    *,
    interpret: bool = False,
    column_major: bool = False,
) -> Tuple[Array, Array]:
    """Raw fused sums for the Gauss-Newton Hessian-vector product.

    Returns (hv_raw, sum_r) with
        hv_raw = X^T r,   r = weight * l''(z, y) * (X @ v_eff + v_shift)
        sum_r  = sum_i r_i
    """
    stats, hv = _dense_call(
        functools.partial(_hvp_kernel, loss), "hessian_vector_sums",
        features, (labels, offsets + shift, weights),
        jnp.stack([w_eff, v_eff]), (v_shift,),
        n_stats=1, flops_per_entry=6, interpret=interpret,
        column_major=column_major,
    )
    return hv[0], stats[0, 0]


# ---------------------------------------------------------------- distributed


def sharded_value_gradient_sums(
    loss: PointwiseLoss,
    w_eff: Array,
    shift: Array,
    features: Array,
    labels: Array,
    offsets: Array,
    weights: Array,
    *,
    mesh: Mesh,
    axis: str,
    interpret: bool = False,
    column_major: bool = False,
) -> Tuple[Array, Array, Array]:
    """Distributed fused objective: per-device fused kernel + psum of the
    raw sums (value, grad_raw, sum_u) over `axis`.

    This is the TPU shape of ValueAndGradientAggregator's treeAggregate
    (:248-252): seqOp = the Pallas row-tile loop on each device's shard,
    combOp = one ICI all-reduce. Raw-sum semantics are identical to the
    single-device kernel, so normalization/L2 post-processing in
    ops/objective.py is unchanged.
    """

    def per_device(w, s, X, y, off, wt):
        val, g, sum_u = value_gradient_sums(
            loss, w, s, X, y, off, wt, interpret=interpret,
            column_major=column_major,
        )
        stats = jax.lax.psum(jnp.stack([val, sum_u]), axis)
        return stats[0], jax.lax.psum(g, axis), stats[1]

    from photon_ml_tpu.parallel.mesh import shard_map_compat

    fn = shard_map_compat(
        per_device,
        mesh=mesh,
        in_specs=(P(), P(), P(axis, None), P(axis), P(axis), P(axis)),
        out_specs=(P(), P(), P()),
    )
    return fn(w_eff, shift, features, labels, offsets, weights)


def sharded_hessian_vector_sums(
    loss: PointwiseLoss,
    w_eff: Array,
    shift: Array,
    v_eff: Array,
    v_shift: Array,
    features: Array,
    labels: Array,
    offsets: Array,
    weights: Array,
    *,
    mesh: Mesh,
    axis: str,
    interpret: bool = False,
    column_major: bool = False,
) -> Tuple[Array, Array]:
    """Distributed fused Hessian-vector product: per-device fused kernel +
    psum of (hv_raw, sum_r) — HessianVectorAggregator.scala:136-142's
    treeAggregate as one ICI all-reduce."""

    def per_device(w, s, v, vs, X, y, off, wt):
        hv, sum_r = hessian_vector_sums(
            loss, w, s, v, vs, X, y, off, wt, interpret=interpret,
            column_major=column_major,
        )
        return jax.lax.psum(hv, axis), jax.lax.psum(sum_r, axis)

    from photon_ml_tpu.parallel.mesh import shard_map_compat

    fn = shard_map_compat(
        per_device,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(axis, None), P(axis), P(axis), P(axis)),
        out_specs=(P(), P()),
    )
    return fn(w_eff, shift, v_eff, v_shift, features, labels, offsets, weights)
