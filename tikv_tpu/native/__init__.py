"""Native (C++) runtime components, compiled on first import.

The reference's hot loops live in C++/Rust (RocksDB iterators, the row
codec, tidb_query's decode paths); here three of them are one CPython
extension (fastbuild.cpp): the data-loader — the MVCC→columnar builder
feeding both the host pipeline and the TPU device feed — and, on the
serving path, an aggregation's host finalize, which turns the fetched
Pallas accumulator (a GROUP BY's grid, or the one slot of an
aggregation without) into result planes in one call that holds the GIL
throughout (``hash_finalize_packed``), and a fast-path reply's encode,
which turns result planes into the msgpack bytes of the rows without a
Python value a cell (``encode_rows_msgpack``).

The build is hermetic and optional: g++ compiles the module into
``_build/`` keyed by source hash (one compile per source change, ~2s);
any failure leaves every export below ``None`` and callers use their
interpreted or numpy fallback, so the framework never hard-requires a
compiler.  No fallback is silent: the cold build labels itself
``cold_build=native|interpreted``, the finalize counts itself on
``/health`` ``device_mesh.finalize`` and the encode on ``/health``
``fastpath.encode``.
"""

from __future__ import annotations

import hashlib
import importlib.util
import os
import subprocess
import sysconfig

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "fastbuild.cpp")


def _load():
    try:
        with open(_SRC, "rb") as f:
            src = f.read()
    except OSError:
        return None
    digest = hashlib.sha256(src).hexdigest()[:16]
    cache = os.path.join(_DIR, "_build")
    so = os.path.join(cache, f"_fastbuild_{digest}.so")
    if not os.path.exists(so):
        os.makedirs(cache, exist_ok=True)
        tmp = f"{so}.{os.getpid()}.tmp"
        cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
               f"-I{sysconfig.get_paths()['include']}", _SRC, "-o", tmp]
        try:
            r = subprocess.run(cmd, capture_output=True, timeout=120)
        except (OSError, subprocess.TimeoutExpired):
            return None
        if r.returncode != 0:
            import logging
            logging.getLogger(__name__).warning(
                "native fastbuild compile failed:\n%s",
                r.stderr.decode(errors="replace"))
            return None
        os.replace(tmp, so)
    spec = importlib.util.spec_from_file_location("_fastbuild", so)
    if spec is None or spec.loader is None:
        return None
    mod = importlib.util.module_from_spec(spec)
    try:
        spec.loader.exec_module(mod)
    except Exception:
        return None
    return mod


_mod = _load()
mvcc_build_columnar = getattr(_mod, "mvcc_build_columnar", None)
build_mvcc_sst = getattr(_mod, "build_mvcc_sst", None)
# flat-plane CF_WRITE parse (device-side MVCC resolution feed; the core
# loop optionally releases the GIL — always on the streaming worker, so
# its parse overlaps SST ingest and the loader's encode; only
# with a spare core on the build path, where yielding on a single-CPU
# box just hands the core to background tick threads)
mvcc_parse_planes = getattr(_mod, "mvcc_parse_planes", None)
# an aggregation's host finalize: fetched (2, HI, W) int32 accumulator
# parts → key / value / validity planes in caller-made buffers (no key
# planes: the one row of an aggregation without GROUP BY), GIL held
# from entry to return (device/aggregate.py finalize_packed, which
# keeps the numpy chain as the fallback and the tests' oracle)
hash_finalize_packed = getattr(_mod, "hash_finalize_packed", None)
# a fast-path reply's rows: [(values, validity), ...] planes (int64 /
# uint64 / float64 beside a bool validity) → the msgpack array of rows,
# or None where it does not take a plane (server/fastpath.py
# encode_response, which keeps the Python chain as the fallback and the
# tests' oracle); GIL held for a small reply, released for a large one
encode_rows_msgpack = getattr(_mod, "encode_rows_msgpack", None)
# the GIL probe's one sample: sleep with the GIL released, stamp the
# wake-up, retake the GIL, stamp again (utils/trace.py watch_gil, which
# falls back to time.sleep's lateness and says so: mode=overshoot)
gil_probe = getattr(_mod, "gil_probe", None)
