"""Data-file parsing: CSV / TSV / LibSVM with format auto-detection.

Behavioral port of the reference parser stack (`src/io/parser.cpp:1-258`,
`parser.hpp`): the format is detected from the first lines (tab/comma
separated vs `idx:value` pairs), the label is column 0 by default, and
LibSVM sparse rows are densified (the TPU dataset is dense-binned anyway).
A fast native path (C++, `native/parser.cpp`) is used when the compiled
extension is available; this numpy fallback is always correct.
"""
from __future__ import annotations

import os
from typing import List, Optional, Tuple

import numpy as np

from .. import log


def detect_format(path: str, has_header: bool = False) -> str:
    """Reference: Parser::CreateParser autodetect (parser.cpp:200-258)."""
    with open(path) as fh:
        lines = []
        for _ in range(32):
            line = fh.readline()
            if not line:
                break
            if line.strip():
                lines.append(line.strip())
    if has_header and lines:
        lines = lines[1:]
    if not lines:
        log.fatal("Data file %s is empty" % path)
    sample = lines[0]
    tokens = sample.replace("\t", " ").replace(",", " ").split()
    colon = sum(1 for t in tokens if ":" in t)
    if colon >= max(1, len(tokens) - 1):
        return "libsvm"
    if "\t" in sample:
        return "tsv"
    if "," in sample:
        return "csv"
    return "tsv"  # whitespace separated


def load_data_file(path: str, has_header: bool = False,
                   label_column: int = 0
                   ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Load a data file into (features, label). Mirrors
    DatasetLoader::LoadFromFile's parsing stage (dataset_loader.cpp:159-217)
    without the distributed partitioning (see parallel/loader.py for that).
    """
    fmt = detect_format(path, has_header)
    if fmt == "libsvm":
        return _load_libsvm(path)
    delim = "," if fmt == "csv" else None
    # native fast path for single-character delimiters (tab/comma); the
    # whitespace-split variant stays in Python
    native_delim = None
    if fmt == "csv":
        native_delim = ","
    elif fmt == "tsv":
        with open(path) as fh:
            first = fh.readline()
        if "\t" in first:
            native_delim = "\t"
    if native_delim is not None:
        mat = _native_parse(path, native_delim, has_header)
        if mat is not None:
            log.info("Parsed %s with the native parser "
                     "(native/parser_native.so)", path)
            labels = mat[:, label_column]
            data = np.delete(mat, label_column, axis=1)
            return np.ascontiguousarray(data), labels.copy()
    # which parser ran is never silent: the .so is a build product
    # (native/build.py), absent from a fresh checkout
    log.info("Parsing %s with the Python parser", path)
    rows: List[List[float]] = []
    labels: List[float] = []
    with open(path) as fh:
        if has_header:
            fh.readline()
        for line in fh:
            line = line.strip()
            if not line:
                continue
            parts = line.split(delim) if delim else line.split()
            vals = [_parse_float(p) for p in parts]
            labels.append(vals[label_column])
            rows.append(vals[:label_column] + vals[label_column + 1:])
    data = np.asarray(rows, np.float64)
    return data, np.asarray(labels, np.float64)


_native_lib = None
_native_tried = False


def native_parser_loaded() -> bool:
    """True once a parse call has loaded native/parser_native.so."""
    return _native_lib is not None


def _native_parse(path: str, delim: str, has_header: bool):
    """Parse via native/parser_native.so (native/parser.cpp) when built;
    returns None to fall back to the Python path."""
    global _native_lib, _native_tried
    if not _native_tried:
        import ctypes
        so = os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))),
            "native", "parser_native.so")
        # the .so is a build product: latch only once it exists, so a
        # process that builds it (native/build.py) after its first parse
        # still picks it up
        if os.path.exists(so):
            _native_tried = True
            try:
                lib = ctypes.CDLL(so)
                lib.lgbm_tpu_parse_dense.restype = ctypes.c_int
                lib.lgbm_tpu_free.argtypes = [ctypes.POINTER(ctypes.c_double)]
                _native_lib = lib
            except OSError as e:
                log.warning("native parser unavailable: %s", e)
    if _native_lib is None:
        return None
    import ctypes
    rows = ctypes.c_int64(0)
    cols = ctypes.c_int64(0)
    data = ctypes.POINTER(ctypes.c_double)()
    rc = _native_lib.lgbm_tpu_parse_dense(
        path.encode(), ctypes.c_char(delim.encode()),
        1 if has_header else 0, ctypes.byref(rows), ctypes.byref(cols),
        ctypes.byref(data))
    if rc != 0:
        return None
    try:
        mat = np.ctypeslib.as_array(
            data, shape=(rows.value, cols.value)).copy()
    finally:
        _native_lib.lgbm_tpu_free(data)
    return mat


def _parse_float(tok: str) -> float:
    tok = tok.strip()
    if not tok or tok.lower() in ("na", "nan", "null", "none", "?"):
        return float("nan")
    try:
        return float(tok)
    except ValueError:
        return float("nan")


def _load_libsvm(path: str) -> Tuple[np.ndarray, np.ndarray]:
    labels: List[float] = []
    rows: List[List[Tuple[int, float]]] = []
    max_idx = -1
    with open(path) as fh:
        for line in fh:
            line = line.split("#", 1)[0].strip()
            if not line:
                continue
            parts = line.split()
            labels.append(_parse_float(parts[0]))
            row = []
            for tok in parts[1:]:
                if ":" not in tok:
                    continue
                idx_s, val_s = tok.split(":", 1)
                # qid tokens are query markers, not features
                if idx_s == "qid":
                    continue
                idx = int(idx_s)
                row.append((idx, _parse_float(val_s)))
                max_idx = max(max_idx, idx)
            rows.append(row)
    n = len(rows)
    data = np.zeros((n, max_idx + 1), np.float64)
    for i, row in enumerate(rows):
        for idx, val in row:
            data[i, idx] = val
    return data, np.asarray(labels, np.float64)


def load_query_file(path: str) -> Optional[np.ndarray]:
    """Reference: Metadata query file `<data>.query` (metadata.cpp)."""
    qfile = path + ".query"
    if not os.path.exists(qfile):
        return None
    with open(qfile) as fh:
        return np.asarray([int(x) for x in fh.read().split()], np.int64)


def load_weight_file(path: str) -> Optional[np.ndarray]:
    wfile = path + ".weight"
    if not os.path.exists(wfile):
        return None
    with open(wfile) as fh:
        return np.asarray([float(x) for x in fh.read().split()], np.float64)
