"""Native LibSVM parser vs the pure-Python tokenizer.

Mirrors the index-store strategy: the Python implementation is the semantic
reference; the C++ engine must produce bit-identical CSR output on the same
input. Tests skip when no compiler is available (the framework falls back
to Python automatically).
"""

from __future__ import annotations

import numpy as np
import pytest

from photon_ml_tpu.data import libsvm
from photon_ml_tpu.native import libsvm_parser

TRICKY = (
    "+1 1:0.5 3:2.0\n"
    "\n"
    "-1 2:1e-3 7:-4.25   # trailing comment 9:9\n"
    "   # comment-only line\n"
    "3.5 1:+2.5 10:1E2\n"
    "-1 5:0.125"  # no trailing newline
)


@pytest.fixture
def tricky_file(tmp_path):
    p = tmp_path / "t.libsvm"
    p.write_text(TRICKY)
    return str(p)


def _python_parse(path, **kw):
    """Force the pure-Python tokenizer regardless of native availability."""
    import unittest.mock as mock

    with mock.patch.object(libsvm_parser, "parse_file", lambda *a, **k: None):
        return libsvm.read_libsvm(path, **kw)


def test_native_available_or_skipped():
    if not libsvm_parser.available():
        pytest.skip("no native toolchain in this environment")


def test_native_matches_python(tricky_file):
    if not libsvm_parser.available():
        pytest.skip("no native toolchain")
    for kw in (
        dict(),
        dict(add_intercept=False),
        dict(zero_based=True),
        dict(num_features=64),
        dict(binary_labels_to_01=False),
    ):
        native = libsvm.read_libsvm(tricky_file, **kw)
        ref = _python_parse(tricky_file, **kw)
        np.testing.assert_array_equal(native.indptr, ref.indptr)
        np.testing.assert_array_equal(native.indices, ref.indices)
        np.testing.assert_allclose(native.values, ref.values, rtol=1e-6)
        np.testing.assert_allclose(native.labels, ref.labels)
        assert native.dim == ref.dim


def test_native_raw_output(tricky_file):
    if not libsvm_parser.available():
        pytest.skip("no native toolchain")
    out = libsvm_parser.parse_file(tricky_file)
    assert out is not None
    labels, indptr, indices, values, max_idx = out
    np.testing.assert_allclose(labels, [1.0, -1.0, 3.5, -1.0])
    np.testing.assert_array_equal(indptr, [0, 2, 4, 6, 7])
    np.testing.assert_array_equal(indices, [0, 2, 1, 6, 0, 9, 4])
    np.testing.assert_allclose(
        values, [0.5, 2.0, 1e-3, -4.25, 2.5, 100.0, 0.125], rtol=1e-6
    )
    assert max_idx == 9


def test_empty_file(tmp_path):
    p = tmp_path / "e.libsvm"
    p.write_text("\n# only comments\n")
    ds = libsvm.read_libsvm(str(p), add_intercept=False, num_features=3)
    assert ds.num_rows == 0 and ds.dim == 3


def test_malformed_falls_back_to_python_error(tmp_path):
    p = tmp_path / "bad.libsvm"
    p.write_text("notanumber 1:2\n")
    with pytest.raises(ValueError):
        libsvm.read_libsvm(str(p))


def test_float64_precision_preserved(tmp_path):
    """dtype=float64 must not round-trip values through float32 natively."""
    if not libsvm_parser.available():
        pytest.skip("no native toolchain")
    p = tmp_path / "p.libsvm"
    p.write_text("1 1:0.1\n")
    ds = libsvm.read_libsvm(str(p), add_intercept=False, dtype=np.float64)
    assert ds.values[0] == 0.1  # exact f64 repr of the parsed literal


def test_hex_floats_rejected_consistently(tmp_path):
    """strtod accepts 0x10; Python float() does not. Native must decline so
    both engines agree on what a valid file is."""
    p = tmp_path / "h.libsvm"
    p.write_text("1 1:0x10\n")
    assert libsvm_parser.parse_file(str(p)) is None or not libsvm_parser.available()
    with pytest.raises(ValueError):
        libsvm.read_libsvm(str(p))


def test_huge_index_falls_back_loudly(tmp_path):
    p = tmp_path / "big.libsvm"
    p.write_text("1 3000000000:1.0\n")
    if libsvm_parser.available():
        assert libsvm_parser.parse_file(str(p)) is None
    with pytest.raises((ValueError, OverflowError)):
        libsvm.read_libsvm(str(p))


def test_no_trailing_newline_tail_token(tmp_path):
    """File ending mid-token without a newline must parse the final value
    exactly (guards the buffer-termination path)."""
    if not libsvm_parser.available():
        pytest.skip("no native toolchain")
    p = tmp_path / "t.libsvm"
    p.write_bytes(b"1 1:2.5 2:3")
    out = libsvm_parser.parse_file(str(p))
    assert out is not None
    _, _, indices, values, _ = out
    np.testing.assert_array_equal(indices, [0, 1])
    np.testing.assert_allclose(values, [2.5, 3.0])


def test_kill_switch_is_global(tmp_path, monkeypatch):
    """PHOTON_DISABLE_NATIVE must gate every native component through the one
    shared loader in native/build.py."""
    from photon_ml_tpu.native import build

    monkeypatch.setenv("PHOTON_DISABLE_NATIVE", "1")
    assert build.native_library_path() is None


def test_failed_build_keeps_and_logs_the_compiler_error_once(monkeypatch, caplog):
    """When g++ fails, the bindings fall back to the Python codecs — a
    different program — so the compiler's error is kept and logged, once."""
    import logging
    import subprocess

    from photon_ml_tpu.native import build

    def failing_gxx(cmd, **kwargs):
        raise subprocess.CalledProcessError(
            1, cmd, stderr=b"avro_reader.cc:7: fatal error: vector: No such file"
        )

    monkeypatch.delenv("PHOTON_DISABLE_NATIVE", raising=False)
    monkeypatch.setattr(build, "_ATTEMPTED", False)
    monkeypatch.setattr(build, "_CACHED", None)
    monkeypatch.setattr(build, "_BUILD_ERROR", None)
    monkeypatch.setattr(build, "_source_hash", lambda: "0" * 16)  # nothing cached
    monkeypatch.setattr(build.subprocess, "run", failing_gxx)
    with caplog.at_level(logging.WARNING, logger=build.__name__):
        assert build.native_library_path() is None
        assert build.native_library_path() is None  # no second attempt
    assert "fatal error: vector" in build.build_error()
    logged = [r for r in caplog.records if "native library build failed" in r.message]
    assert len(logged) == 1 and "fatal error: vector" in logged[0].getMessage()


def test_missing_value_after_colon_rejected(tmp_path):
    """'idx:' with no attached value must fail in both engines — the native
    parser must not consume the next line's label as the value."""
    for text in ("1 1:\n0 2:3\n", "1 1: 2\n"):
        p = tmp_path / "mv.libsvm"
        p.write_text(text)
        if libsvm_parser.available():
            assert libsvm_parser.parse_file(str(p)) is None
        with pytest.raises(ValueError):
            libsvm.read_libsvm(str(p))
