"""Host-port allocator tests — both the Python and the native C++
implementation (reference analogue: third_party/hostport-allocator, which
ships zero tests — SURVEY.md §4)."""

import threading

import pytest

from paddle_operator_tpu.controller.hostport import (
    NativeHostPortAllocator,
    PortExhausted,
    PyHostPortAllocator,
    make_allocator,
)


def native_available():
    try:
        NativeHostPortAllocator(35000, 35080, 8)
        return True
    except (FileNotFoundError, OSError):
        return False


IMPLS = [PyHostPortAllocator]
if native_available():
    IMPLS.append(NativeHostPortAllocator)


@pytest.fixture(params=IMPLS, ids=lambda c: c.__name__)
def alloc_cls(request):
    return request.param


class TestAllocator:
    def test_allocate_unique_blocks(self, alloc_cls):
        a = alloc_cls(35000, 35080, 8)
        bases = [a.allocate() for _ in range(10)]
        assert len(set(bases)) == 10
        assert all(35000 <= b < 35080 and (b - 35000) % 8 == 0 for b in bases)

    def test_exhaustion(self, alloc_cls):
        a = alloc_cls(35000, 35016, 8)
        a.allocate()
        a.allocate()
        with pytest.raises(PortExhausted):
            a.allocate()

    def test_release_recycles(self, alloc_cls):
        a = alloc_cls(35000, 35016, 8)
        b1 = a.allocate()
        a.allocate()
        a.release(b1)
        assert a.allocate() == b1

    def test_adopt(self, alloc_cls):
        a = alloc_cls(35000, 35080, 8)
        assert a.adopt(35024)
        assert not a.adopt(35024)
        assert a.in_use(35024)
        # adopted blocks are skipped by allocate
        bases = [a.allocate() for _ in range(9)]
        assert 35024 not in bases

    def test_thread_safety(self, alloc_cls):
        a = alloc_cls(35000, 43000, 8)
        out, lock = [], threading.Lock()

        def work():
            mine = [a.allocate() for _ in range(50)]
            with lock:
                out.extend(mine)

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(set(out)) == 400


class TestNative:
    def test_native_lib_builds_and_loads(self):
        assert native_available(), (
            "native allocator neither found nor buildable on demand "
            "(controller/hostport.py _find_native_lib)"
        )

    def test_make_allocator_prefers_native(self):
        a = make_allocator(35000, 35080, 8)
        assert isinstance(a, NativeHostPortAllocator)

    def test_native_exhaustion_message(self):
        a = NativeHostPortAllocator(35000, 35008, 8)
        a.allocate()
        with pytest.raises(PortExhausted):
            a.allocate()
