"""JIT build system for native host ops.

Rebuild of op_builder/builder.py (``OpBuilder`` :119, ``jit_load`` :405):
compiles csrc/*.cpp into shared libraries with g++ on first use and loads
them via ctypes (the reference uses torch cpp_extension + pybind11; this
build is torch-free so the ABI is plain C). Libraries are built under the
checkout (``.ds_build/``, git-ignored) and named by the hash of their
source, so a ``.so`` built from another checkout's source is never loaded
against this one — mtimes say nothing in a freshly unpacked tree.
SIMD width is whatever -march=native provides (reference simd_width
detection, builder.py:318); ops degrade to scalar loops when AVX2 is
absent.
"""

import ctypes
import hashlib
import os
import subprocess
from pathlib import Path

from deepspeed_tpu.utils.chip import CHECKOUT
from deepspeed_tpu.utils.logging import logger

CSRC = CHECKOUT / "csrc"
BUILD_DIR = Path(os.environ.get("DS_BUILD_DIR", CHECKOUT / ".ds_build"))


class OpBuilderError(RuntimeError):
    pass


class CPUOpBuilder:
    """One native op = one .cpp file compiled to one .so."""

    NAME = None
    SOURCE = None            # filename under csrc/
    EXTRA_FLAGS = []

    def source_path(self) -> Path:
        return CSRC / self.SOURCE

    def lib_path(self) -> Path:
        digest = hashlib.sha256(self.source_path().read_bytes()).hexdigest()
        return BUILD_DIR / f"{self.NAME}-{digest[:16]}.so"

    def is_compatible(self) -> bool:
        return self.source_path().exists() and _has_compiler()

    def needs_build(self) -> bool:
        return not self.lib_path().exists()

    def build(self) -> Path:
        BUILD_DIR.mkdir(parents=True, exist_ok=True)
        src, lib = self.source_path(), self.lib_path()
        # build beside the target and rename: another process (the
        # multi-process tests build concurrently) never dlopens a
        # half-written library
        tmp = lib.with_name(f"{lib.name}.{os.getpid()}.tmp")
        cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC",
               "-march=native", "-fopenmp", "-pthread",
               str(src), "-o", str(tmp)] + list(self.EXTRA_FLAGS)
        try:
            subprocess.run(cmd, check=True, capture_output=True, text=True)
        except subprocess.CalledProcessError as e:  # fall back: no -march
            cmd = [c for c in cmd if c != "-march=native"]
            try:
                subprocess.run(cmd, check=True, capture_output=True,
                               text=True)
            except subprocess.CalledProcessError as e2:
                raise OpBuilderError(
                    f"building {self.NAME} failed:\n{e2.stderr}") from e2
            logger.warning(f"{self.NAME}: built without -march=native "
                           f"({e.stderr.splitlines()[-1] if e.stderr else ''})")
        os.replace(tmp, lib)
        return lib

    def load(self) -> ctypes.CDLL:
        """jit_load (builder.py:405): build if absent, dlopen, memoise."""
        if self.NAME in _LOADED:
            return _LOADED[self.NAME]
        if not self.is_compatible():
            raise OpBuilderError(
                f"op {self.NAME} unavailable (missing source or compiler)")
        if self.needs_build():
            logger.info(f"JIT-building native op {self.NAME}...")
            self.build()
        lib = ctypes.CDLL(str(self.lib_path()))
        self._declare(lib)
        _LOADED[self.NAME] = lib
        return lib

    def _declare(self, lib):
        """Subclasses set argtypes/restype for type safety."""


_LOADED = {}


def _has_compiler() -> bool:
    try:
        subprocess.run(["g++", "--version"], capture_output=True, check=True)
        return True
    except Exception:  # pragma: no cover
        return False


c_float_p = ctypes.POINTER(ctypes.c_float)
c_char_p = ctypes.c_char_p
i64 = ctypes.c_int64


class CPUAdamBuilder(CPUOpBuilder):
    NAME = "deepspeed_cpu_adam"
    SOURCE = "cpu_adam.cpp"

    def _declare(self, lib):
        lib.ds_adam_create.argtypes = [ctypes.c_int, ctypes.c_float,
                                       ctypes.c_float, ctypes.c_float,
                                       ctypes.c_float, ctypes.c_int]
        lib.ds_adam_create.restype = ctypes.c_int
        lib.ds_adam_step.argtypes = [ctypes.c_int, i64, ctypes.c_float,
                                     c_float_p, c_float_p, c_float_p,
                                     c_float_p, i64]
        lib.ds_adam_step.restype = ctypes.c_int
        lib.ds_adam_destroy.argtypes = [ctypes.c_int]
        lib.ds_adagrad_step.argtypes = [ctypes.c_float, ctypes.c_float,
                                        ctypes.c_float, c_float_p, c_float_p,
                                        c_float_p, i64]
        lib.ds_adagrad_step.restype = ctypes.c_int
        lib.ds_has_avx2.restype = ctypes.c_int


class AsyncIOBuilder(CPUOpBuilder):
    NAME = "deepspeed_aio"
    SOURCE = "aio.cpp"

    def _declare(self, lib):
        lib.aio_handle_create.argtypes = [ctypes.c_int] * 5
        lib.aio_handle_create.restype = i64
        lib.aio_handle_destroy.argtypes = [i64]
        for fn in (lib.aio_async_pread, lib.aio_async_pwrite,
                   lib.aio_sync_pread, lib.aio_sync_pwrite):
            fn.argtypes = [i64, ctypes.c_char_p, ctypes.c_char_p, i64, i64]
            fn.restype = i64
        lib.aio_wait.argtypes = [i64, i64]
        lib.aio_wait.restype = i64
        lib.aio_pending.argtypes = [i64]
        lib.aio_pending.restype = i64
        lib.aio_kernel_available.argtypes = [ctypes.c_char_p]
        lib.aio_kernel_available.restype = ctypes.c_int
        lib.aio_max_inflight.argtypes = []
        lib.aio_max_inflight.restype = i64
        lib.aio_reset_max_inflight.argtypes = []
        lib.aio_reset_max_inflight.restype = None


ALL_OPS = {
    "cpu_adam": CPUAdamBuilder,
    "async_io": AsyncIOBuilder,
}
