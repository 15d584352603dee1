"""AsyncIOHandle error-path regressions: reads against missing/short
files must raise the typed :class:`AioError` (never hand back a partial
buffer silently), and ``__del__`` must surface — not mask — pending-op
leaks.  Companion to tests/test_offload_aio.py (happy paths) and the KV
tier, whose spill files lean on exactly these contracts."""

import gc
import warnings

import numpy as np
import pytest


def _aio_available():
    from deepspeed_tpu.ops.builder import AsyncIOBuilder
    return AsyncIOBuilder().is_compatible()


aio_required = pytest.mark.skipif(not _aio_available(),
                                  reason="no g++ toolchain")


@aio_required
class TestAioErrorPaths:
    def _handle(self):
        from deepspeed_tpu.ops.aio import AsyncIOHandle
        return AsyncIOHandle(thread_count=2, block_size=1 << 16)

    def test_sync_pread_missing_file_raises_typed(self, tmp_path):
        from deepspeed_tpu.ops.aio import AioError

        h = self._handle()
        buf = np.empty(64, np.float32)
        with pytest.raises(AioError) as ei:
            h.sync_pread(buf, str(tmp_path / "gone.bin"))
        assert ei.value.path == str(tmp_path / "gone.bin")
        assert ei.value.expected == buf.nbytes
        assert ei.value.actual is None          # missing, not short
        assert isinstance(ei.value, OSError)    # catchable as IOError too

    def test_async_pread_missing_file_raises_before_queueing(self, tmp_path):
        from deepspeed_tpu.ops.aio import AioError

        h = self._handle()
        buf = np.empty(64, np.float32)
        with pytest.raises(AioError):
            h.async_pread(buf, str(tmp_path / "gone.bin"))
        # nothing was queued — the failure must not surface later as an
        # anonymous failed-chunk count on an unrelated wait()
        assert h.pending() == 0
        assert h.wait() == 0

    def test_short_file_raises_not_partial_buffer(self, tmp_path):
        from deepspeed_tpu.ops.aio import AioError

        h = self._handle()
        x = np.arange(100, dtype=np.float32)
        p = str(tmp_path / "short.bin")
        assert h.sync_pwrite(x, p) == 0
        sentinel = np.full(200, -1.0, np.float32)
        with pytest.raises(AioError) as ei:
            h.sync_pread(sentinel, p)
        assert ei.value.expected == sentinel.nbytes
        assert ei.value.actual == x.nbytes
        # the buffer was never touched — no silent partial fill
        assert (sentinel == -1.0).all()

    def test_short_file_raises_with_offset(self, tmp_path):
        from deepspeed_tpu.ops.aio import AioError

        h = self._handle()
        x = np.arange(100, dtype=np.float32)
        p = str(tmp_path / "off.bin")
        assert h.sync_pwrite(x, p) == 0
        tail = np.empty(10, np.float32)
        # offset + nbytes lands past EOF by one element
        with pytest.raises(AioError):
            h.sync_pread(tail, p, offset=91 * 4)
        # exact-fit read at the boundary still works
        assert h.sync_pread(tail, p, offset=90 * 4) == 0
        np.testing.assert_array_equal(tail, x[90:])

    def test_file_shrunk_after_queue_raises_on_sync(self, tmp_path):
        """A file truncated between the size check and the read must
        surface through sync_pread's failed-chunk raise, not a silently
        stale buffer."""
        from deepspeed_tpu.ops.aio import AioError

        h = self._handle()
        x = np.arange(1000, dtype=np.float32)
        p = str(tmp_path / "shrink.bin")
        assert h.sync_pwrite(x, p) == 0
        with open(p, "r+b") as f:
            f.truncate(10)
        buf = np.empty_like(x)
        with pytest.raises(AioError):
            h.sync_pread(buf, p)

    def test_del_warns_on_pending_ops(self, tmp_path):
        h = self._handle()
        buf = np.random.randn(1 << 16).astype(np.float32)
        for i in range(8):
            h.async_pwrite(buf, str(tmp_path / f"leak{i}.bin"))
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            del h
            gc.collect()
        # ops may have drained before __del__ ran (threaded backend) —
        # but if any were pending, the leak must have been surfaced
        # (the collection can also reap another test's unclosed file in
        # this worker: that warning is not this handle's)
        leak_warns = [x for x in w if issubclass(x.category, ResourceWarning)
                      and "unclosed file" not in str(x.message)]
        for x in leak_warns:
            assert "pending" in str(x.message)
        # files landed either way: the drain inside __del__ (or the
        # workers) finished the writes instead of abandoning them
        for i in range(8):
            assert (tmp_path / f"leak{i}.bin").stat().st_size == buf.nbytes

    def test_del_quiet_after_wait(self, tmp_path):
        h = self._handle()
        buf = np.random.randn(1024).astype(np.float32)
        h.async_pwrite(buf, str(tmp_path / "ok.bin"))
        assert h.wait() == 0
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            del h
            gc.collect()
        assert not [x for x in w if issubclass(x.category, ResourceWarning)]
