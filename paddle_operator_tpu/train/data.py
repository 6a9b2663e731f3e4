"""Data input pipeline.

The reference leaves data entirely to user containers (PV/PVC mounts,
docs/user-guide.md:260-347).  Here the framework ships the TPU-shaped
loading pattern: each process reads only its own shard of the data
(per-process sharding by ``jax.process_index``), batches are assembled
host-side and placed onto the device mesh as **globally sharded arrays**
(``jax.make_array_from_process_local_data``), and a background prefetcher
keeps N batches in flight so the host never stalls the device step.

Sources: synthetic LM tokens (bench/tests), memory-mapped token files
(the standard pretraining format: one flat uint16/uint32 array), and any
python iterator.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Dict, Iterator, Optional

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding

from paddle_operator_tpu.parallel.sharding import batch_sharding
from paddle_operator_tpu.utils import tracing as TR


def synthetic_lm_batches(batch_size: int, seq_len: int, vocab: int,
                         seed: int = 0) -> Iterator[Dict[str, np.ndarray]]:
    """Deterministic infinite synthetic stream (per-process seed offset so
    dp shards differ)."""
    rng = np.random.default_rng(seed + 1315423911 * jax.process_index())
    while True:
        yield {"tokens": rng.integers(
            0, vocab, (batch_size, seq_len), dtype=np.int32)}


def deterministic_lm_batches(global_batch: int, seq_len: int, vocab: int,
                             *, seed: int = 0, start_step: int = 0
                             ) -> Iterator[Dict[str, np.ndarray]]:
    """Elastic-resume data source: the batch for global step *k* is a pure
    function of ``(seed, k)`` — independent of process count, mesh shape,
    and iteration history — so a gang resumed on a different dp size
    replays the exact same global batch sequence.  ``start_step`` is the
    fast-forward: resuming at step *s* means ``start_step=s`` and the
    stream continues with step *s*'s batch, no repeated or skipped data
    (ft/elastic.py computes the offset when the global batch changed).

    Contrast with :func:`synthetic_lm_batches`, whose per-process RNG
    stream makes replay impossible once the world reshapes."""
    step = start_step
    while True:
        rng = np.random.default_rng(np.random.SeedSequence([seed, step]))
        yield {"tokens": rng.integers(
            0, vocab, (global_batch, seq_len), dtype=np.int32)}
        step += 1


def process_slice(batch: Dict[str, np.ndarray],
                  process_index: Optional[int] = None,
                  process_count: Optional[int] = None
                  ) -> Dict[str, np.ndarray]:
    """This process's row block of a *global* batch (what
    ``make_array_from_process_local_data`` expects).  Deterministic
    sources yield global batches so every world shape sees the same data;
    each process then feeds only its contiguous shard."""
    pi = jax.process_index() if process_index is None else process_index
    pc = jax.process_count() if process_count is None else process_count
    if pc == 1:
        return batch
    out = {}
    for k, v in batch.items():
        if v.shape[0] % pc:
            raise ValueError(
                f"global batch {v.shape[0]} not divisible by "
                f"{pc} processes for key {k!r}")
        per = v.shape[0] // pc
        out[k] = v[pi * per:(pi + 1) * per]
    return out


class NativeTokenFile:
    """ctypes binding to the native mmap gather (native/dataio.cpp): one C
    call assembles a whole [B, win] int32 batch from a flat token file."""

    def __init__(self, path: str, dtype=np.uint16,
                 lib_path: Optional[str] = None) -> None:
        import ctypes

        from paddle_operator_tpu.controller.hostport import _find_native_lib

        width = np.dtype(dtype).itemsize
        if width not in (2, 4):
            raise ValueError(f"unsupported token dtype {dtype}")
        lib_file = lib_path or _find_native_lib()
        if lib_file is None:
            raise FileNotFoundError(
                "native library not found and could not be built")
        lib = ctypes.CDLL(lib_file)
        lib.dio_open.restype = ctypes.c_void_p
        lib.dio_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.dio_len.restype = ctypes.c_int64
        lib.dio_len.argtypes = [ctypes.c_void_p]
        lib.dio_gather.restype = ctypes.c_int
        lib.dio_gather.argtypes = [
            ctypes.c_void_p,
            np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
            ctypes.c_int64, ctypes.c_int64,
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")]
        lib.dio_close.argtypes = [ctypes.c_void_p]
        self._lib = lib
        self._h = lib.dio_open(path.encode(), width)
        if not self._h:
            raise FileNotFoundError(f"dio_open failed for {path}")

    def __len__(self) -> int:
        return int(self._lib.dio_len(self._h))

    def gather(self, starts: np.ndarray, win: int) -> np.ndarray:
        starts = np.ascontiguousarray(starts, np.int64)
        out = np.empty((len(starts), win), np.int32)
        rc = self._lib.dio_gather(self._h, starts, len(starts), win, out)
        if rc != 0:
            raise IndexError("window out of bounds")
        return out

    def close(self) -> None:
        if getattr(self, "_h", None):
            self._lib.dio_close(self._h)
            self._h = None

    def __del__(self) -> None:
        self.close()


def mmap_token_batches(path: str, batch_size: int, seq_len: int,
                       *, dtype=np.uint16, seed: int = 0,
                       loop: bool = True,
                       native: Optional[bool] = None
                       ) -> Iterator[Dict[str, np.ndarray]]:
    """Sample [batch, seq+1] windows from a flat token file (memory-mapped;
    zero-copy until batch assembly).  Each process samples independently —
    with per-process seeds the dp shards are disjoint in expectation.

    ``native``: use the C++ gather (native/dataio.cpp) — one call per
    batch instead of a per-row python slice loop.  Default: native (the
    library is built on demand, controller/hostport.py), python where it
    can be neither found nor built; pass True/False to force."""
    reader = None
    if native is not False:
        try:
            reader = NativeTokenFile(path, dtype)
        except (FileNotFoundError, ValueError):
            if native:
                raise
    if reader is not None:
        n = len(reader) - seq_len - 1
    else:
        data = np.memmap(path, dtype=dtype, mode="r")
        n = len(data) - seq_len - 1
    if n <= 0:
        raise ValueError(f"{path}: too short for seq_len={seq_len}")
    rng = np.random.default_rng(seed + 2654435761 * jax.process_index())
    while True:
        starts = rng.integers(0, n, batch_size)
        if reader is not None:
            batch = reader.gather(starts, seq_len + 1)
        else:
            batch = np.stack([np.asarray(data[s:s + seq_len + 1])
                              for s in starts]).astype(np.int32)
        yield {"tokens": batch}
        if not loop:
            break


class DevicePrefetcher:
    """Wrap a host-batch iterator: place batches onto the mesh with the
    standard (dp, fsdp) batch sharding, keeping `depth` batches in flight
    on a background thread."""

    def __init__(self, it: Iterator[Dict[str, np.ndarray]], mesh: Mesh,
                 *, depth: int = 2,
                 sharding: Optional[NamedSharding] = None) -> None:
        self.it = it
        self.mesh = mesh
        self.sharding = sharding or batch_sharding(mesh, extra_dims=1)
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._err: Optional[BaseException] = None
        self._t = threading.Thread(target=self._fill, daemon=True)
        self._t.start()

    def _place(self, batch: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
        out = {}
        with TR.phase("data.place"):    # on the prefetcher's thread
            for k, v in batch.items():
                if jax.process_count() > 1:
                    out[k] = jax.make_array_from_process_local_data(
                        self.sharding, v)
                else:
                    out[k] = jax.device_put(v, self.sharding)
        return out

    def _fill(self) -> None:
        try:
            for batch in self.it:
                self._q.put(self._place(batch))
        except BaseException as e:  # surfaced on next()
            self._err = e
        self._q.put(None)

    def __iter__(self):
        return self

    def __next__(self) -> Dict[str, jax.Array]:
        item = self._q.get()
        if item is None:
            if self._err is not None:
                raise self._err
            raise StopIteration
        return item
