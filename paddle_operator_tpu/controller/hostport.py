"""Host-port block allocator.

Successor of both the reference's in-controller HostPortMap
(main.go:86-108 + controllers/paddlejob_controller.go:320-374) and the
standalone ``third_party/hostport-allocator`` (informer-based port manager
for the legacy TrainingJob CRD).

The allocator hands out *blocks* of contiguous ports (the reference gives
every Host-network job a block of 20 ports starting at a cursor that wraps
within [35000, 65000)); released blocks are recycled.  Controller restarts
re-adopt blocks from job annotations (reference controller.go:324-331).

Two implementations, same interface:

- :class:`PyHostPortAllocator` — pure Python.
- :class:`NativeHostPortAllocator` — the C++ allocator in ``native/`` via
  ctypes (the reference's native component analogue), built on demand
  from ``native/*.cpp``; :func:`make_allocator` falls back to Python only
  where it can be neither found nor built.
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Optional, Set

from paddle_operator_tpu.api.types import HOST_PORT_RANGE, PORT_NUM


class PortExhausted(Exception):
    pass


class PyHostPortAllocator:
    """Block allocator over [start, end) with wrap-around cursor + free list."""

    def __init__(self, start: int = HOST_PORT_RANGE[0],
                 end: int = HOST_PORT_RANGE[1],
                 block: int = PORT_NUM) -> None:
        assert end - start >= block > 0
        self.start, self.end, self.block = start, end, block
        self._cur = start
        self._used: Set[int] = set()
        self._lock = threading.Lock()

    def allocate(self) -> int:
        """Return the base port of a fresh block."""
        with self._lock:
            n_blocks = (self.end - self.start) // self.block
            for _ in range(n_blocks):
                base = self._cur
                self._cur += self.block
                if self._cur + self.block > self.end:
                    self._cur = self.start
                if base not in self._used:
                    self._used.add(base)
                    return base
            raise PortExhausted(
                f"no free {self.block}-port block in [{self.start},{self.end})"
            )

    def release(self, base: int) -> None:
        with self._lock:
            self._used.discard(base)

    def adopt(self, base: int) -> bool:
        """Re-adopt a block found in a job annotation after controller
        restart (reference controller.go:324-331).  Returns False if the
        block is already owned."""
        with self._lock:
            if base in self._used:
                return False
            self._used.add(base)
            return True

    def in_use(self, base: int) -> bool:
        return base in self._used


_NATIVE_LIB_NAME = "libtpujob_native.so"


def _build_native_lib(src_dir: str, out: str) -> bool:
    """Build ``native/*.cpp`` with the repo's own Makefile into a private
    directory, then rename the library into place: a reader (or another
    builder — pytest-xdist workers all import at once) sees either no
    file or a whole one, never a partial write."""
    import shutil
    import subprocess
    import tempfile

    from paddle_operator_tpu.utils.observability import get_logger

    os.makedirs(os.path.dirname(out), exist_ok=True)
    tmp = tempfile.mkdtemp(prefix=".build-", dir=os.path.dirname(out))
    try:
        proc = subprocess.run(["make", "-C", src_dir, f"BUILD={tmp}"],
                              capture_output=True, text=True)
        if proc.returncode == 0:
            os.replace(os.path.join(tmp, _NATIVE_LIB_NAME), out)
            return True
        why = f"rc={proc.returncode}: {proc.stderr.strip()[-400:]}"
    except OSError as e:       # no make on this machine
        why = str(e)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    get_logger().warning(f"native library build failed ({why})")
    return False


def _find_native_lib() -> Optional[str]:
    """The native library's path: the image's prebuilt copy
    (``paddle_operator_tpu/_native``, see Dockerfile), else
    ``native/build`` — built on demand from ``native/*.cpp`` when absent
    or older than a source, since ``native/build`` is not in git."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    packaged = os.path.join(here, "_native", _NATIVE_LIB_NAME)
    if os.path.exists(packaged):
        return packaged
    src_dir = os.path.abspath(os.path.join(here, "..", "native"))
    out = os.path.join(src_dir, "build", _NATIVE_LIB_NAME)
    srcs = [os.path.join(src_dir, f) for f in ("hostport.cpp", "dataio.cpp",
                                               "Makefile")]
    if not all(os.path.exists(f) for f in srcs):
        return out if os.path.exists(out) else None
    stale = (not os.path.exists(out)
             or os.path.getmtime(out) < max(map(os.path.getmtime, srcs)))
    if stale and not _build_native_lib(src_dir, out):
        return None
    return out


class NativeHostPortAllocator:
    """ctypes binding to the C++ allocator (native/hostport.cpp)."""

    def __init__(self, start: int = HOST_PORT_RANGE[0],
                 end: int = HOST_PORT_RANGE[1],
                 block: int = PORT_NUM,
                 lib_path: Optional[str] = None) -> None:
        path = lib_path or _find_native_lib()
        if path is None:
            raise FileNotFoundError(
                "native allocator library not found and could not be built")
        lib = ctypes.CDLL(path)
        lib.hp_new.restype = ctypes.c_void_p
        lib.hp_new.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_int]
        lib.hp_free.argtypes = [ctypes.c_void_p]
        lib.hp_allocate.restype = ctypes.c_int
        lib.hp_allocate.argtypes = [ctypes.c_void_p]
        lib.hp_release.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.hp_adopt.restype = ctypes.c_int
        lib.hp_adopt.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.hp_in_use.restype = ctypes.c_int
        lib.hp_in_use.argtypes = [ctypes.c_void_p, ctypes.c_int]
        self._lib = lib
        self._h = lib.hp_new(start, end, block)
        if not self._h:
            raise ValueError(
                f"invalid allocator params: start={start} end={end} block={block}"
            )

    def __del__(self) -> None:
        if getattr(self, "_h", None):
            self._lib.hp_free(self._h)
            self._h = None

    def allocate(self) -> int:
        p = self._lib.hp_allocate(self._h)
        if p < 0:
            raise PortExhausted("native allocator: no free block")
        return p

    def release(self, base: int) -> None:
        self._lib.hp_release(self._h, base)

    def adopt(self, base: int) -> bool:
        return bool(self._lib.hp_adopt(self._h, base))

    def in_use(self, base: int) -> bool:
        return bool(self._lib.hp_in_use(self._h, base))


def make_allocator(start: int = HOST_PORT_RANGE[0],
                   end: int = HOST_PORT_RANGE[1],
                   block: int = PORT_NUM):
    """Prefer the native allocator; fall back to Python where the library
    can be neither found nor built (the failed build is logged)."""
    try:
        return NativeHostPortAllocator(start, end, block)
    except (FileNotFoundError, OSError):
        return PyHostPortAllocator(start, end, block)
