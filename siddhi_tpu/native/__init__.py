"""Native (C++) runtime components, loaded via ctypes.

The reference runs its ingress hot path on the JVM (Disruptor ring +
per-event ``StreamEvent`` allocation, ``stream/StreamJunction.java:254-316``).
Here the equivalent is ``ingress.cpp``: a C++ data-loader that parses raw
transport bytes (CSV lines), dictionary-encodes strings, routes rows to
partition lanes (crc32 — bit-identical to ``tpu/partition.py::_hash_key``)
and packs fixed-capacity SoA column buffers that ``emit_lane`` copies into
numpy arrays ready for ``jax.device_put``.

Built on first use with ``g++ -O3`` into ``_build/``, under a file name that
carries a hash of ``ingress.cpp``: a binary is loaded only if it was built
from exactly the source beside it (mtimes say nothing after a copy or a
checkout). If it cannot be built, ``native_available()`` is False,
``native_unavailable_reason()`` holds the compiler's complaint, and callers
that can fall back to the pure Python packers (``tpu/batch.py``).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "ingress.cpp")
_BUILD_DIR = os.path.join(_HERE, "_build")

_lib = None
_lib_lock = threading.Lock()
_unavailable_reason = None
NATIVE_AVAILABLE = False


def so_path() -> str:
    """The shared object for the ``ingress.cpp`` on disk now."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, f"libsiddhi_ingress-{digest}.so")


def _build(so: str) -> bool:
    """Compile ``ingress.cpp`` to ``so`` (atomically: a killed build must not
    leave a truncated file under the name a later run trusts)."""
    global _unavailable_reason
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-o", tmp, _SRC],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
        return True
    except subprocess.CalledProcessError as e:
        _unavailable_reason = (
            f"g++ exited {e.returncode}: "
            f"{e.stderr.decode(errors='replace').strip()[-2000:]}")
    except (OSError, subprocess.TimeoutExpired) as e:
        _unavailable_reason = f"{type(e).__name__}: {e}"
    if os.path.exists(tmp):
        os.remove(tmp)
    return False


def _load():
    global _lib, NATIVE_AVAILABLE, _unavailable_reason
    with _lib_lock:
        if _lib is not None:
            return _lib
        so = so_path()
        if not os.path.exists(so) and not _build(so):
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            # the name matches the source, the contents do not load (another
            # architecture's build dir was copied in): rebuild once
            if not _build(so):
                return None
            try:
                lib = ctypes.CDLL(so)
            except OSError as e2:
                _unavailable_reason = f"dlopen failed: {e}; after rebuild: {e2}"
                return None
        lib.sp_create.restype = ctypes.c_void_p
        lib.sp_create.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
                                  ctypes.c_int, ctypes.c_int64]
        lib.sp_destroy.argtypes = [ctypes.c_void_p]
        lib.sp_encode.restype = ctypes.c_int32
        lib.sp_encode.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
        lib.sp_dict_size.restype = ctypes.c_int64
        lib.sp_dict_size.argtypes = [ctypes.c_void_p]
        lib.sp_dict_get.restype = ctypes.c_int64
        lib.sp_dict_get.argtypes = [ctypes.c_void_p, ctypes.c_int32,
                                    ctypes.c_char_p, ctypes.c_int64]
        lib.sp_lane_of.restype = ctypes.c_int32
        lib.sp_lane_of.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
        lib.sp_lane_len.restype = ctypes.c_int64
        lib.sp_lane_len.argtypes = [ctypes.c_void_p, ctypes.c_int32]
        lib.sp_parse_errors.restype = ctypes.c_int64
        lib.sp_parse_errors.argtypes = [ctypes.c_void_p]
        lib.sp_ingest_csv.restype = ctypes.c_int64
        lib.sp_ingest_csv.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int, ctypes.c_int32, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64)]
        lib.sp_emit_lane.restype = ctypes.c_int64
        lib.sp_emit_lane.argtypes = [
            ctypes.c_void_p, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p]
        # wide emit: 'd' columns stay float64 (the host tier's f64 policy)
        lib.sp_emit_lane_wide.restype = ctypes.c_int64
        lib.sp_emit_lane_wide.argtypes = lib.sp_emit_lane.argtypes
        _lib = lib
        NATIVE_AVAILABLE = True
        return lib


# 'd' emits as float32: parse keeps full double precision in the staging
# cells, but emit narrows to the device policy float (tpu/dtypes.py).
# The WIDE emit (emit_lane(wide=True)) keeps 'd' as float64 for the
# host/columnar edge, where the policy is interpreter-exact f64.
_TYPE_NP = {
    "f": np.float32, "d": np.float32, "i": np.int32, "l": np.int64,
    "b": np.uint8, "s": np.int32,
}
_TYPE_NP_WIDE = dict(_TYPE_NP, d=np.float64)


class NativeIngress:
    """Lane-routed CSV ingress backed by the C++ library.

    ``types`` is one char per payload column ('f','d','i','l','b','s');
    ``key_col`` is the payload column index used for crc32 lane routing
    (-1 routes everything to lane 0).
    """

    def __init__(self, types: str, key_col: int = -1, n_lanes: int = 1,
                 capacity: int = 1024):
        lib = _load()
        if lib is None:
            raise RuntimeError(
                f"native ingress unavailable: {native_unavailable_reason()}")
        self._lib = lib
        self.types = types
        self.n_lanes = n_lanes
        self.capacity = capacity
        self._h = lib.sp_create(types.encode(), len(types), key_col, n_lanes,
                                capacity)
        if not self._h:
            raise ValueError("sp_create failed (bad schema)")
        self._row_seq = ctypes.c_int64(0)
        self._decode_cache: list = [None]

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.sp_destroy(h)
            self._h = None

    # -- ingest ------------------------------------------------------------
    def ingest_csv(self, data: bytes, base_ts: int = 0, ts_last: bool = False,
                   tag: int = 0, final: bool = True, offset: int = 0) -> int:
        """Feeds raw CSV bytes starting at ``offset`` (no copy); returns bytes
        consumed (stops short when a lane filled up — drain with emit_lane and
        call again with offset advanced past the consumed prefix)."""
        addr = ctypes.cast(ctypes.c_char_p(data), ctypes.c_void_p).value
        return self._lib.sp_ingest_csv(
            self._h, addr + offset, len(data) - offset, base_ts,
            1 if ts_last else 0, tag, 1 if final else 0,
            ctypes.byref(self._row_seq))

    # -- dictionary --------------------------------------------------------
    def encode(self, s: str) -> int:
        b = s.encode()
        return self._lib.sp_encode(self._h, b, len(b))

    def decode(self, code: int):
        if code == 0:
            return None
        cache = self._decode_cache
        if 0 < code < len(cache) and cache[code] is not None:
            return cache[code]
        if code < 0 or code >= self._lib.sp_dict_size(self._h):
            return None
        cap = 4096
        while True:
            buf = ctypes.create_string_buffer(cap)
            n = self._lib.sp_dict_get(self._h, code, buf, cap)
            if n >= 0:
                break
            cap *= 2  # valid code, so -1 means the buffer was too small
        s = buf.raw[:n].decode()
        while len(cache) <= code:
            cache.append(None)
        cache[code] = s
        return s

    def dict_size(self) -> int:
        return self._lib.sp_dict_size(self._h)

    def lane_of(self, key: str) -> int:
        b = key.encode()
        return self._lib.sp_lane_of(self._h, b, len(b))

    def lane_len(self, lane: int) -> int:
        return self._lib.sp_lane_len(self._h, lane)

    @property
    def parse_errors(self) -> int:
        return self._lib.sp_parse_errors(self._h)

    # -- emit --------------------------------------------------------------
    def emit_lane(self, lane: int, wide: bool = False) -> dict:
        """Drains one lane into fresh numpy arrays padded to capacity.

        Returns {'cols': [np array per payload column], 'ts', 'tag', 'valid',
        'count'} — same contract as tpu/batch.py builders. ``wide=True``
        keeps 'd' columns as float64 (host/columnar edge policy) via
        ``sp_emit_lane_wide``."""
        cap = self.capacity
        fn = self._lib.sp_emit_lane_wide if wide else self._lib.sp_emit_lane
        dts = _TYPE_NP_WIDE if wide else _TYPE_NP
        cols = [np.zeros(cap, dtype=dts[t]) for t in self.types]
        ts = np.zeros(cap, dtype=np.int64)
        tag = np.zeros(cap, dtype=np.int32)
        valid = np.zeros(cap, dtype=np.uint8)
        ptrs = (ctypes.c_void_p * len(cols))(
            *[c.ctypes.data_as(ctypes.c_void_p).value for c in cols])
        n = fn(
            self._h, lane, ptrs,
            ts.ctypes.data_as(ctypes.c_void_p),
            tag.ctypes.data_as(ctypes.c_void_p),
            valid.ctypes.data_as(ctypes.c_void_p))
        return {"cols": cols, "ts": ts, "tag": tag,
                "valid": valid.astype(bool), "count": int(n)}


def native_available() -> bool:
    return _load() is not None


def native_unavailable_reason():
    """Why the library could not be built or loaded (the compiler's stderr,
    or the OS error); None while it is available or was never tried."""
    return _unavailable_reason
