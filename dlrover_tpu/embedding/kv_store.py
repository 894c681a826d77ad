"""ctypes bindings for the C++ KvEmbedding store (built on demand).

Reference parity: the Python surface of TFPlus KvVariable
(tfplus/kv_variable/python/ops/kv_variable_ops.py — gather/
gather_or_insert/gather_or_zeros, scatter ops, import/export V1-V3,
eviction, frequency tracking) re-exposed over a dependency-free C ABI
(pybind11 is not in this image; SURVEY.md §2.6).

The .so is compiled from dlrover_tpu/native/kv_embedding.cc with g++ the
first time it's needed and cached next to the source.
"""

import ctypes
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

from dlrover_tpu.common.log import default_logger as logger

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native"
)
_SRC = os.path.join(_NATIVE_DIR, "kv_embedding.cc")
_SO = os.path.join(_NATIVE_DIR, "libkv_embedding.so")
_BUILD_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None


_CXX_FLAGS = ["-O3", "-shared", "-fPIC", "-std=c++17", "-pthread"]


def _so_fresh(so: str) -> bool:
    """Fresh = newer than the source AND built with the CURRENT flags
    (the `.flags` sidecar): an mtime-only check kept serving cached
    .so files built with since-removed ISA flags, so a flag fix never
    reached deployed caches."""
    if not os.path.exists(so) or (
        os.path.getmtime(so) < os.path.getmtime(_SRC)
    ):
        return False
    try:
        with open(so + ".flags") as f:
            return f.read() == " ".join(_CXX_FLAGS)
    except OSError:
        return False


def _so_path() -> str:
    """Prefer a fresh prebuilt .so next to the source (no toolchain
    needed at runtime); else build there if writable, falling back to a
    per-user cache dir (installed read-only site-packages)."""
    if _so_fresh(_SO):
        return _SO
    if os.access(_NATIVE_DIR, os.W_OK):
        return _SO
    cache = os.path.join(
        os.path.expanduser("~"), ".cache", "dlrover_tpu"
    )
    os.makedirs(cache, exist_ok=True)
    return os.path.join(cache, "libkv_embedding.so")


def _build_so() -> str:
    import fcntl

    so = _so_path()
    # fresh prebuilt .so: no lock file, no toolchain — works on
    # read-only installs
    if _so_fresh(so):
        return so
    with _BUILD_LOCK:
        # cross-process exclusion: g++ writes the output in place, so
        # concurrently launched workers must not compile over a .so a
        # third process is dlopen-ing — build to a temp name under an
        # flock, then rename atomically.
        lock_path = so + ".lock"
        with open(lock_path, "w") as lockf:
            fcntl.flock(lockf, fcntl.LOCK_EX)
            try:
                if _so_fresh(so):
                    return so
                tmp = f"{so}.{os.getpid()}.tmp"
                # baseline ISA only (no -march): the .so may be
                # prebuilt into an image or land in a shared ~/.cache
                # crossing heterogeneous hosts, where newer ISA
                # extensions SIGILL with no diagnostic. The ALU-bound
                # hot kernels still get AVX2/FMA: the .cc dispatches
                # per-host at load time (target_clones + a
                # __builtin_cpu_supports-guarded NR adam kernel), so
                # no -march is needed HERE.
                cmd = ["g++"] + _CXX_FLAGS + ["-o", tmp, _SRC]
                logger.info(
                    "building kv_embedding native lib: %s", " ".join(cmd)
                )
                try:
                    subprocess.run(
                        cmd, check=True, capture_output=True, text=True
                    )
                except subprocess.CalledProcessError as e:
                    logger.error(
                        "kv_embedding build failed:\n%s", e.stderr
                    )
                    raise
                os.replace(tmp, so)
                with open(so + ".flags", "w") as f:
                    f.write(" ".join(_CXX_FLAGS))
                return so
            finally:
                fcntl.flock(lockf, fcntl.LOCK_UN)


def _lib() -> ctypes.CDLL:
    global _LIB
    if _LIB is not None:
        return _LIB
    lib = ctypes.CDLL(_build_so())
    i64 = ctypes.c_int64
    u64 = ctypes.c_uint64
    u32 = ctypes.c_uint32
    f32 = ctypes.c_float
    p = ctypes.c_void_p
    i64p = ctypes.POINTER(ctypes.c_int64)
    f32p = ctypes.POINTER(ctypes.c_float)

    lib.kv_create.restype = p
    lib.kv_create.argtypes = [i64, ctypes.c_int, u64, f32]
    lib.kv_free.argtypes = [p]
    lib.kv_size.restype = i64
    lib.kv_size.argtypes = [p]
    lib.kv_dim.restype = i64
    lib.kv_dim.argtypes = [p]
    lib.kv_version.restype = u64
    lib.kv_version.argtypes = [p]
    lib.kv_lookup.argtypes = [p, i64p, i64, f32p, ctypes.c_int]
    lib.kv_scatter_add.argtypes = [p, i64p, i64, f32p, f32]
    lib.kv_apply_sgd.argtypes = [p, i64p, i64, f32p, f32]
    lib.kv_apply_adagrad.argtypes = [p, i64p, i64, f32p, f32, f32]
    lib.kv_apply_adam.argtypes = [
        p, i64p, i64, f32p, f32, f32, f32, f32, i64, f32, f32,
    ]
    lib.kv_evict.restype = i64
    lib.kv_evict.argtypes = [p, u32, ctypes.c_double]
    lib.kv_delete_keys.restype = i64
    lib.kv_delete_keys.argtypes = [p, i64p, i64]
    lib.kv_export_count.restype = i64
    lib.kv_export_count.argtypes = [p, u64]
    lib.kv_export_rows.restype = i64
    lib.kv_export_rows.argtypes = [p, u64, i64p, f32p, i64]
    lib.kv_import_rows.argtypes = [p, i64p, f32p, i64]
    u32p = ctypes.POINTER(ctypes.c_uint32)
    lib.kv_max_state_mult.restype = ctypes.c_int
    lib.kv_max_state_mult.argtypes = [p]
    lib.kv_export_full.restype = i64
    lib.kv_export_full.argtypes = [
        p, u64, i64p, f32p, u32p, i64, ctypes.c_int,
    ]
    lib.kv_import_full.argtypes = [
        p, i64p, f32p, u32p, i64, ctypes.c_int,
    ]
    lib.kv_set_spill_path.restype = ctypes.c_int
    lib.kv_set_spill_path.argtypes = [p, ctypes.c_char_p]
    lib.kv_spill.restype = i64
    lib.kv_spill.argtypes = [p, u32, ctypes.c_double]
    lib.kv_disk_size.restype = i64
    lib.kv_disk_size.argtypes = [p]
    lib.kv_compact.restype = i64
    lib.kv_compact.argtypes = [p]
    _LIB = lib
    return lib


def _i64p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _f32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


class KvEmbeddingTable:
    """Dynamic hashtable embedding table (host DRAM, C++ core)."""

    def __init__(
        self,
        dim: int,
        initializer: str = "zeros",   # zeros | normal
        init_scale: float = 0.01,
        seed: int = 0,
    ):
        self._lib = _lib()
        self.dim = int(dim)
        mode = 1 if initializer == "normal" else 0
        self._h = self._lib.kv_create(
            self.dim, mode, seed, ctypes.c_float(init_scale)
        )

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.kv_free(h)
            self._h = None

    def __len__(self) -> int:
        return int(self._lib.kv_size(self._h))

    @property
    def version(self) -> int:
        return int(self._lib.kv_version(self._h))

    def _keys(self, keys) -> np.ndarray:
        return np.ascontiguousarray(np.asarray(keys), dtype=np.int64).ravel()

    def lookup(self, keys, insert_missing: bool = True) -> np.ndarray:
        """Gather rows [n, dim]; missing keys insert (GatherOrInsert) or
        read as zeros (GatherOrZeros)."""
        k = self._keys(keys)
        out = np.empty((k.size, self.dim), np.float32)
        self._lib.kv_lookup(
            self._h, _i64p(k), k.size, _f32p(out),
            1 if insert_missing else 0,
        )
        return out.reshape(*np.shape(keys), self.dim)

    def scatter_add(self, keys, values, alpha: float = 1.0):
        k = self._keys(keys)
        v = np.ascontiguousarray(values, np.float32).reshape(
            k.size, self.dim
        )
        self._lib.kv_scatter_add(
            self._h, _i64p(k), k.size, _f32p(v), ctypes.c_float(alpha)
        )

    def apply_sgd(self, keys, grads, lr: float):
        k = self._keys(keys)
        g = np.ascontiguousarray(grads, np.float32).reshape(
            k.size, self.dim
        )
        self._lib.kv_apply_sgd(
            self._h, _i64p(k), k.size, _f32p(g), ctypes.c_float(lr)
        )

    def apply_adagrad(self, keys, grads, lr: float, eps: float = 1e-10):
        k = self._keys(keys)
        g = np.ascontiguousarray(grads, np.float32).reshape(
            k.size, self.dim
        )
        self._lib.kv_apply_adagrad(
            self._h, _i64p(k), k.size, _f32p(g),
            ctypes.c_float(lr), ctypes.c_float(eps),
        )

    def apply_adam(
        self, keys, grads, lr: float, step: int,
        b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
        l1: float = 0.0, l2: float = 0.0,
    ):
        """Sparse Adam; l1/l2 > 0 gives the reference's Group Adam
        (sparse group lasso on embedding rows)."""
        k = self._keys(keys)
        g = np.ascontiguousarray(grads, np.float32).reshape(
            k.size, self.dim
        )
        self._lib.kv_apply_adam(
            self._h, _i64p(k), k.size, _f32p(g),
            ctypes.c_float(lr), ctypes.c_float(b1), ctypes.c_float(b2),
            ctypes.c_float(eps), step, ctypes.c_float(l1),
            ctypes.c_float(l2),
        )

    # ---- hybrid DRAM/disk tier (reference tfplus hybrid_embedding) ----

    def set_spill_path(self, path: str) -> bool:
        """Enable the disk tier; cold rows move there via spill() and
        promote back transparently on access."""
        return bool(
            self._lib.kv_set_spill_path(self._h, path.encode())
        )

    def spill(
        self, min_freq: int = 0, max_idle_sec: float = 0.0
    ) -> int:
        """Demote cold rows (freq < min_freq OR idle > max_idle_sec)
        to the disk tier. Returns rows moved."""
        return int(
            self._lib.kv_spill(
                self._h,
                ctypes.c_uint32(min_freq),
                ctypes.c_double(max_idle_sec),
            )
        )

    def disk_size(self) -> int:
        return int(self._lib.kv_disk_size(self._h))

    def compact(self) -> int:
        """Rewrite the spill file dropping dead (promoted/evicted)
        records; returns live disk rows."""
        return int(self._lib.kv_compact(self._h))

    def delete(self, keys) -> int:
        """Targeted row removal (DRAM + disk tier). The shard-move
        handoff: rows re-owned by another host are deleted here so
        stale copies never re-enter delta exports. Returns rows
        removed."""
        k = self._keys(keys)
        return int(
            self._lib.kv_delete_keys(self._h, _i64p(k), k.size)
        )

    def evict(self, min_freq: int = 0, max_idle_sec: float = 0.0) -> int:
        """Drop cold (freq < min_freq) or idle rows; returns count."""
        return int(
            self._lib.kv_evict(
                self._h, min_freq, ctypes.c_double(max_idle_sec)
            )
        )

    def export(
        self, since_version: int = 0
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Full (since_version=0) or delta export → (keys, values).
        Delta export backs incremental model delivery (reference
        ImportV3/ExportV3)."""

        def _fill(keys, cap, since):
            vals = np.empty((cap, self.dim), np.float32)
            got = int(
                self._lib.kv_export_rows(
                    self._h, since, _i64p(keys), _f32p(vals), cap
                )
            )
            return got, (vals,)

        got, keys, (vals,) = self._export_with_retry(
            since_version, _fill
        )
        return keys[:got], vals[:got]

    def _export_with_retry(self, since_version: int, fill):
        """count-then-fill isn't atomic vs concurrent inserts: allocate
        headroom and retry while the buffer fills to the brim (a full
        buffer can't be distinguished from a truncated one)."""
        headroom = 1024
        while True:
            n = int(self._lib.kv_export_count(self._h, since_version))
            cap = n + headroom
            keys = np.empty(cap, np.int64)
            got, extra = fill(keys, cap, since_version)
            if got < cap:
                return got, keys, extra
            headroom *= 4

    def import_(self, keys, values):
        k = self._keys(keys)
        v = np.ascontiguousarray(values, np.float32).reshape(
            k.size, self.dim
        )
        self._lib.kv_import_rows(self._h, _i64p(k), _f32p(v), k.size)

    @property
    def state_mult(self) -> int:
        """Widest per-row state (1=values, 2=+adagrad, 3=+adam m,v)."""
        return int(self._lib.kv_max_state_mult(self._h))

    def export_full(
        self, since_version: int = 0, state_mult: Optional[int] = None
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
        """Export (keys, state[n, mult*dim], freq, mult): row values AND
        optimizer moments AND eviction stats (reference ExportV2). The
        width adapts to the optimizer actually in use — an SGD table
        exports dim floats per row, not 3*dim of zeros."""
        while True:
            mult = state_mult or self.state_mult

            def _fill(keys, cap, since):
                state = np.empty((cap, mult * self.dim), np.float32)
                freq = np.empty(cap, np.uint32)
                got = int(
                    self._lib.kv_export_full(
                        self._h, since, _i64p(keys), _f32p(state),
                        freq.ctypes.data_as(
                            ctypes.POINTER(ctypes.c_uint32)
                        ),
                        cap, mult,
                    )
                )
                return got, (state, freq)

            got, keys, (state, freq) = self._export_with_retry(
                since_version, _fill
            )
            # a concurrent optimizer step may have widened rows after
            # we sampled mult — their moments would be silently clipped;
            # re-export at the wider width instead
            if state_mult is None and self.state_mult > mult:
                continue
            return keys[:got], state[:got], freq[:got], mult

    def import_full(self, keys, state, freq, state_mult: int):
        k = self._keys(keys)
        s = np.ascontiguousarray(state, np.float32).reshape(
            k.size, state_mult * self.dim
        )
        f = np.ascontiguousarray(freq, np.uint32).ravel()
        self._lib.kv_import_full(
            self._h, _i64p(k), _f32p(s),
            f.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            k.size, state_mult,
        )

    # ---- checkpoint integration ----
    def state_dict(self) -> dict:
        keys, state, freq, mult = self.export_full(0)
        return {
            "keys": keys,
            "state": state,
            "freq": freq,
            "dim": self.dim,
            "state_mult": mult,
        }

    def load_state_dict(self, state: dict):
        assert int(state["dim"]) == self.dim
        if "state" in state:
            self.import_full(
                state["keys"], state["state"], state["freq"],
                int(state.get("state_mult", 3)),
            )
        else:  # legacy values-only checkpoint
            self.import_(state["keys"], state["values"])
