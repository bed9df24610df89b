"""Multiprocess DataLoader (io/worker.py): parity with in-process loading,
shared-memory transport, persistent workers, error/crash propagation.
Reference: ``fluid/dataloader/dataloader_iter.py:342``
(_DataLoaderIterMultiProcess) + ``memory/allocation/mmap_allocator.cc``."""
import multiprocessing as mp
import os
import time

import numpy as np
import pytest

from paddle_tpu.io import DataLoader, Dataset, IterableDataset, get_worker_info
from paddle_tpu.io.worker import WorkerFailure


@pytest.fixture(autouse=True)
def _fast_fork(monkeypatch, request):
    """fork-start for speed (forkserver costs ~10s/pool on this box); the
    default forkserver path is exercised by test_forkserver_default_start."""
    if "forkserver" not in request.node.name:
        monkeypatch.setenv("PADDLE_TPU_WORKER_START", "fork")


def test_forkserver_default_start():
    ds = ArrayDataset()
    assert os.environ.get("PADDLE_TPU_WORKER_START") is None
    got = _collect(DataLoader(ds, batch_size=16, num_workers=2,
                              use_process=True))
    assert got == list(range(64))


class BackendProbeDataset(Dataset):
    """Each sample reports how many JAX backends its worker process has
    initialised so far — for every batch after a worker's first, that is
    after the worker collated a batch."""

    def __len__(self):
        return 12

    def __getitem__(self, i):
        import jax._src.xla_bridge as xb

        return np.float32(i), np.int64(len(xb._backends))


def test_forkserver_worker_never_touches_jax():
    # One process per chip: the parent holds the accelerator, so a worker
    # that initialised a backend would fight it for the TPU. The default
    # collate used to build Tensors (jax arrays) inside the worker.
    loader = DataLoader(BackendProbeDataset(), batch_size=4, num_workers=1,
                        use_process=True)
    backends = []
    for x, n in loader:
        assert type(x).__name__ == "Tensor"  # the parent wraps the leaves
        backends += np.asarray(n._value).tolist()
    assert backends == [0] * 12


def _worker_platform(_):
    import jax

    return np.asarray([jax.default_backend() == "cpu"])


def test_worker_that_does_touch_jax_gets_the_cpu(monkeypatch):
    # user code in a worker may still call jax; it must never get the chip
    monkeypatch.setenv("JAX_PLATFORMS", "")
    loader = DataLoader(ArrayDataset(n=8), batch_size=4, num_workers=1,
                        use_process=True, collate_fn=_worker_platform)
    assert all(bool(b[0]) for b in loader)


class ArrayDataset(Dataset):
    def __init__(self, n=64, dim=8):
        self.x = np.arange(n * dim, dtype=np.float32).reshape(n, dim)

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], np.int64(i)


class PyHeavyDataset(ArrayDataset):
    """Pure-Python per-sample transform: the GIL-bound case processes exist
    for. Every sample comes back beside the pid that made it, and the first
    sample of each of the first ``parties`` batches waits until ``parties``
    workers are inside the transform at once (a worker that waits can take
    no second batch, so each of them holds one)."""

    def __init__(self, n, batch_size, parties):
        super().__init__(n)
        self._batch_size, self._parties = batch_size, parties
        self._together = mp.get_context("fork").Barrier(parties)

    def __getitem__(self, i):
        batch, first = divmod(i, self._batch_size)
        if first == 0 and batch < self._parties:
            self._together.wait(timeout=60)
        acc = 0.0
        for j in range(20000):
            acc += (i * j) % 7
        x, y = super().__getitem__(i)
        return x + (acc % 3), y, np.int64(os.getpid())


class BoomDataset(ArrayDataset):
    def __getitem__(self, i):
        if i == 13:
            raise ValueError("boom at 13")
        return super().__getitem__(i)


class KillSelfDataset(ArrayDataset):
    def __getitem__(self, i):
        if i == 7:
            os._exit(42)  # simulates a segfaulted/killed worker
        return super().__getitem__(i)


class ShardedIterable(IterableDataset):
    def __init__(self, n=64):
        self.n = n

    def __iter__(self):
        info = get_worker_info()
        wid = info.id if info else 0
        nw = info.num_workers if info else 1
        for i in range(wid, self.n, nw):
            yield np.float32(i)


def _collect(loader):
    out = []
    for xb, yb in loader:
        out.extend(np.asarray(yb).tolist())
        assert np.asarray(xb).dtype == np.float32
    return out


def test_process_mode_parity_with_single_thread():
    ds = ArrayDataset()
    base = _collect(DataLoader(ds, batch_size=8, num_workers=0))
    got = _collect(DataLoader(ds, batch_size=8, num_workers=4,
                              use_process=True))
    assert got == base
    # batches themselves identical
    b0 = next(iter(DataLoader(ds, batch_size=8, num_workers=0)))
    b1 = next(iter(DataLoader(ds, batch_size=8, num_workers=4,
                              use_process=True)))
    np.testing.assert_array_equal(np.asarray(b0[0]), np.asarray(b1[0]))


def test_process_mode_without_shared_memory():
    ds = ArrayDataset()
    base = _collect(DataLoader(ds, batch_size=8, num_workers=0))
    got = _collect(DataLoader(ds, batch_size=8, num_workers=2,
                              use_process=True, use_shared_memory=False))
    assert got == base


def test_worker_exception_propagates():
    loader = DataLoader(BoomDataset(), batch_size=4, num_workers=2,
                        use_process=True)
    with pytest.raises(WorkerFailure, match="boom at 13"):
        list(loader)


def test_killed_worker_detected():
    loader = DataLoader(KillSelfDataset(), batch_size=4, num_workers=2,
                        use_process=True)
    with pytest.raises(WorkerFailure, match="exited unexpectedly"):
        list(loader)


def test_persistent_workers_reuse_pool_across_epochs():
    ds = ArrayDataset()
    loader = DataLoader(ds, batch_size=8, num_workers=2, use_process=True,
                        persistent_workers=True)
    e1 = _collect(loader)
    pool = loader._pool
    assert pool is not None
    pids = [p.pid for p in pool._procs]
    e2 = _collect(loader)
    assert e1 == e2
    assert loader._pool is pool
    assert [p.pid for p in pool._procs] == pids
    assert all(p.is_alive() for p in pool._procs)
    loader.__del__()
    assert all(not p.is_alive() for p in pool._procs)


def test_early_break_then_reiterate():
    ds = ArrayDataset()
    loader = DataLoader(ds, batch_size=8, num_workers=2, use_process=True,
                        persistent_workers=True)
    it = iter(loader)
    next(it), next(it)  # abandon mid-epoch
    del it
    assert _collect(loader) == list(range(64))  # stale epoch fully discarded


def _worker_2_starts_late(wid):
    if wid == 2:
        time.sleep(0.5)


@pytest.mark.parametrize("worker_init_fn", [None, _worker_2_starts_late])
def test_iterable_dataset_process_sharding(worker_init_fn):
    # every worker streams its own shard once, however late it starts: its
    # siblings, done with theirs, must not take the epoch's start meant for it
    loader = DataLoader(ShardedIterable(48), batch_size=4, num_workers=3,
                        use_process=True, worker_init_fn=worker_init_fn)
    got = []
    for batch in loader:
        got.extend(np.asarray(batch).astype(int).tolist())
    assert sorted(got) == list(range(48))


def _ok_init(wid):
    pass  # runs in the child


def _bad_init(wid):
    raise RuntimeError("init failed")


def test_worker_init_fn_runs_and_failure_propagates():
    ds = ArrayDataset()
    assert _collect(DataLoader(ds, batch_size=8, num_workers=2,
                               use_process=True, worker_init_fn=_ok_init)) \
        == list(range(64))

    loader = DataLoader(ds, batch_size=8, num_workers=2, use_process=True,
                        worker_init_fn=_bad_init)
    with pytest.raises(WorkerFailure, match="worker_init_fn"):
        list(loader)


def test_python_heavy_transform_runs_in_every_worker_process():
    """The reason process workers exist: a pure-Python transform is GIL-bound
    under threads but parallel under processes. Counted, not timed: the
    transform ran in ``num_workers`` processes at once, none of them this
    one, and the batches are the threaded loader's."""
    ds = PyHeavyDataset(n=32, batch_size=4, parties=4)

    def collect(loader):
        xs, ys, pids = zip(*((np.asarray(x), np.asarray(y), np.asarray(p))
                             for x, y, p in loader))
        return (np.concatenate(xs), np.concatenate(ys),
                set(np.concatenate(pids).tolist()))

    x_thr, y_thr, pids_thr = collect(
        DataLoader(ds, batch_size=4, num_workers=4))
    x_proc, y_proc, pids_proc = collect(
        DataLoader(ds, batch_size=4, num_workers=4, use_process=True))

    assert pids_thr == {os.getpid()}
    assert len(pids_proc) == 4 and os.getpid() not in pids_proc
    np.testing.assert_array_equal(x_proc, x_thr)
    np.testing.assert_array_equal(y_proc, y_thr)
    assert y_proc.tolist() == list(range(32))


def test_concurrent_iterators_on_persistent_loader():
    """Review regression: a second live iterator must not cross epoch tags
    with the persistent pool (it gets its own temporary pool)."""
    ds = ArrayDataset()
    loader = DataLoader(ds, batch_size=8, num_workers=2, use_process=True,
                        persistent_workers=True)
    it1, it2 = iter(loader), iter(loader)
    a1 = [np.asarray(next(it1)[1]).tolist() for _ in range(4)]
    a2 = [np.asarray(next(it2)[1]).tolist() for _ in range(4)]
    assert a1 == a2
    rest1 = [np.asarray(b[1]).tolist() for b in it1]
    rest2 = [np.asarray(b[1]).tolist() for b in it2]
    assert rest1 == rest2 and len(a1 + rest1) == 8


def test_timeout_raises_on_hung_worker():
    loader = DataLoader(HangDataset(), batch_size=4, num_workers=1,
                        use_process=True, timeout=3)
    with pytest.raises(WorkerFailure, match="timed out"):
        list(loader)


class HangDataset(ArrayDataset):
    def __getitem__(self, i):
        if i == 5:
            time.sleep(600)
        return super().__getitem__(i)
