"""paddle.io: Dataset / Sampler / DataLoader (reference:
python/paddle/io/ — unverified, SURVEY.md §0).

The reference's multiprocess workers + LoDTensorBlockingQueue become a
background prefetch thread feeding ``jax.device_put`` (double buffering —
host→HBM copy overlaps compute). A C++ prefetch core slots in behind the
same API (csrc/, loaded when built).
"""
from __future__ import annotations

import itertools
import os
import queue
import threading

import numpy as np

from ..core.tensor import Tensor
from ..core.random import default_generator

__all__ = [
    "Dataset", "IterableDataset", "TensorDataset", "ComposeDataset",
    "ChainDataset", "Subset", "random_split",
    "Sampler", "SequenceSampler", "RandomSampler", "WeightedRandomSampler",
    "BatchSampler", "DistributedBatchSampler", "DataLoader",
    "get_worker_info", "default_collate_fn", "pack_varlen",
]


def pack_varlen(rows, max_len, pad_id=0):
    """Pad/pack variable-length int sequences into a dense int32 batch +
    lengths (native multithreaded kernel when csrc/ is built)."""
    from . import _native

    out, lengths = _native.pack_varlen(rows, max_len, pad_id)
    return Tensor(out), Tensor(lengths)


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise RuntimeError("IterableDataset is not subscriptable")

    def __len__(self):
        raise RuntimeError("IterableDataset has no len()")


class TensorDataset(Dataset):
    def __init__(self, tensors):
        self.tensors = tensors

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return self.tensors[0].shape[0]


class ComposeDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __getitem__(self, idx):
        out = []
        for ds in self.datasets:
            item = ds[idx]
            out.extend(item if isinstance(item, (tuple, list)) else [item])
        return tuple(out)

    def __len__(self):
        return min(len(ds) for ds in self.datasets)


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __iter__(self):
        for ds in self.datasets:
            yield from ds


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset, self.indices = dataset, list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    total = len(dataset)
    if all(isinstance(l, float) for l in lengths):
        lengths = [int(total * f) for f in lengths]
        lengths[-1] = total - sum(lengths[:-1])
    if sum(lengths) != total:
        raise ValueError("sum of lengths must equal dataset size")
    perm = np.random.RandomState(
        default_generator.initial_seed() & 0x7FFFFFFF
    ).permutation(total)
    out, off = [], 0
    for n in lengths:
        out.append(Subset(dataset, perm[off : off + n].tolist()))
        off += n
    return out


class Sampler:
    def __init__(self, data_source=None):
        self.data_source = data_source

    def __iter__(self):
        raise NotImplementedError

    def __len__(self):
        return len(self.data_source)


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))


class RandomSampler(Sampler):
    def __init__(self, data_source, replacement=False, num_samples=None,
                 generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self._num_samples = num_samples

    @property
    def num_samples(self):
        return self._num_samples or len(self.data_source)

    def __iter__(self):
        n = len(self.data_source)
        if self.replacement:
            return iter(np.random.randint(0, n, self.num_samples).tolist())
        if n >= (1 << 16):
            # epoch shuffles of large datasets: native Fisher–Yates
            # (csrc/), seeded from the same global stream so runs stay
            # reproducible under paddle.seed
            from . import _native

            seed = int(np.random.randint(0, 2**31 - 1))
            return iter(
                _native.shuffle_indices(n, seed)[: self.num_samples].tolist()
            )
        return iter(np.random.permutation(n)[: self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(
            weights.numpy() if isinstance(weights, Tensor) else weights,
            np.float64,
        )
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        return iter(
            np.random.choice(
                len(self.weights), self.num_samples, self.replacement, p
            ).tolist()
        )

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    def __init__(self, dataset=None, sampler=None, shuffle=False,
                 batch_size=1, drop_last=False):
        self.batch_size = batch_size
        self.drop_last = drop_last
        if sampler is not None:
            self.sampler = sampler
        elif shuffle:
            self.sampler = RandomSampler(dataset)
        else:
            self.sampler = SequenceSampler(dataset)

    def __iter__(self):
        batch = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        n = len(self.sampler)
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size


class DistributedBatchSampler(BatchSampler):
    """Shards the dataset across data-parallel ranks (reference:
    python/paddle/io/dataloader/batch_sampler.py)."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        from ..distributed import get_world_size, get_rank

        self.dataset = dataset
        self.batch_size = batch_size
        self.nranks = num_replicas if num_replicas is not None else get_world_size()
        self.local_rank = rank if rank is not None else get_rank()
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.epoch = 0
        self.num_samples = -(-len(dataset) // self.nranks)
        self.total_size = self.num_samples * self.nranks

    def __iter__(self):
        n = len(self.dataset)
        if self.shuffle:
            rs = np.random.RandomState(self.epoch)
            indices = rs.permutation(n).tolist()
        else:
            indices = list(range(n))
        indices += indices[: (self.total_size - n)]
        indices = indices[self.local_rank : self.total_size : self.nranks]
        batch = []
        for idx in indices:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def set_epoch(self, epoch):
        self.epoch = epoch

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size


class _WorkerInfo:
    def __init__(self, id=0, num_workers=0, dataset=None):
        self.id, self.num_workers, self.dataset = id, num_workers, dataset


_worker_info = None


def get_worker_info():
    return _worker_info


def default_collate_fn(batch):
    """Stack samples into batched Tensors, matching paddle's collate."""
    sample = batch[0]
    if isinstance(sample, Tensor):
        return Tensor(np.stack([s.numpy() for s in batch]))
    if isinstance(sample, np.ndarray):
        return Tensor(np.stack(batch))
    if isinstance(sample, (int, np.integer)):
        return Tensor(np.asarray(batch, np.int64))
    if isinstance(sample, (float, np.floating)):
        return Tensor(np.asarray(batch, np.float32))
    if isinstance(sample, (list, tuple)):
        transposed = list(zip(*batch))
        return [default_collate_fn(list(group)) for group in transposed]
    if isinstance(sample, dict):
        return {k: default_collate_fn([d[k] for d in batch]) for k in sample}
    return batch


def _pid_alive(pid):
    try:
        os.kill(pid, 0)
        return True
    except (ProcessLookupError, ValueError):
        return False
    except PermissionError:
        return True


def _claim_worker_id(claim_dir):
    """Filesystem-based worker-id counter: O_EXCL slot files work across
    any spawn boundary (mp.Value's SemLock does not survive pickling to
    a spawned pool worker in sandboxed environments). Slots record the
    claimant's pid so a worker respawned after a pool-mate died can
    reclaim the dead slot (keeping ids < num_workers) instead of
    counting upward forever."""
    i = 0
    while True:
        slot = os.path.join(claim_dir, f"w{i}")
        try:
            return _try_claim_slot(slot, i)
        except FileNotFoundError:
            # claim_dir removed by close() while this worker was still
            # spawning (anywhere in the claim/reap sequence): the pool is
            # shutting down, nothing will consume our output — any id is
            # fine, exit the claim loop quietly
            return i
        except _SlotTaken:
            i += 1


class _SlotTaken(Exception):
    """Internal: this slot is live-owned, try the next one."""


def _try_claim_slot(slot, i):
    try:
        fd = os.open(slot, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
        os.write(fd, str(os.getpid()).encode())
        os.close(fd)
        return i
    except FileExistsError:
        # dead claimant? take over via an exclusive reap marker so
        # only one respawned worker recycles the slot
        try:
            with open(slot) as f:
                owner = int(f.read().strip() or -1)
        except FileNotFoundError:
            raise  # claim_dir gone: let the caller exit quietly
        except (OSError, ValueError):
            owner = -1
        if owner != -1 and not _pid_alive(owner):
            try:
                rfd = os.open(
                    slot + ".reap", os.O_CREAT | os.O_EXCL | os.O_WRONLY
                )
            except FileExistsError:
                raise _SlotTaken from None
            try:
                # re-check under the marker: another reaper may have
                # recycled this slot between our read and the win
                try:
                    with open(slot) as f:
                        owner = int(f.read().strip() or -1)
                except FileNotFoundError:
                    raise
                except (OSError, ValueError):
                    owner = -1
                if owner == -1 or _pid_alive(owner):
                    raise _SlotTaken from None
                with open(slot, "w") as f:
                    f.write(str(os.getpid()))
                return i
            finally:
                os.close(rfd)
                try:
                    os.unlink(slot + ".reap")
                except FileNotFoundError:
                    pass
        raise _SlotTaken from None


def _pool_init(dataset, collate_fn, worker_init_fn, claim_dir, num_workers):
    """Spawned-worker initializer: installs the dataset/collate globals
    once per worker (pickled once, not per batch) and runs the user's
    worker_init_fn with a stable worker id.

    Workers must stay off the accelerator — the parent owns the (single)
    TPU client — so the child is pinned to the CPU backend and collation
    stays in numpy; the parent tensorizes."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    global _WORKER_DATASET, _WORKER_COLLATE, _worker_info
    _WORKER_DATASET = dataset
    _WORKER_COLLATE = collate_fn
    wid = _claim_worker_id(claim_dir) if claim_dir else 0
    _worker_info = _WorkerInfo(
        id=wid, num_workers=num_workers, dataset=dataset
    )
    if worker_init_fn is not None:
        worker_init_fn(wid)


def _collate_numpy(batch):
    """default_collate_fn that stays in numpy (worker-process side)."""
    sample = batch[0]
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, np.integer)):
        return np.asarray(batch, np.int64)
    if isinstance(sample, (float, np.floating)):
        return np.asarray(batch, np.float32)
    if isinstance(sample, (list, tuple)):
        return [_collate_numpy(list(g)) for g in zip(*batch)]
    if isinstance(sample, dict):
        return {k: _collate_numpy([d[k] for d in batch]) for k in sample}
    return batch


def _tensorize(tree):
    if isinstance(tree, np.ndarray):
        return Tensor(tree)
    if isinstance(tree, list):
        return [_tensorize(t) for t in tree]
    if isinstance(tree, tuple):
        return tuple(_tensorize(t) for t in tree)
    if isinstance(tree, dict):
        return {k: _tensorize(v) for k, v in tree.items()}
    return tree


def _pool_fetch(indices):
    samples = [_WORKER_DATASET[i] for i in indices]
    if _WORKER_COLLATE is None:  # default collate, numpy side
        return _collate_numpy(samples)
    return _WORKER_COLLATE(samples)


def _pool_warmup():
    return os.getpid()


def _picklable(*objs):
    import pickle

    try:
        for o in objs:
            pickle.dumps(o)
        return True
    except Exception:
        return False


class DataLoader:
    """Iterates a Dataset with batching + background prefetch.

    ``num_workers>0`` fetches batches in spawned worker *processes*
    (reference: python/paddle/io/dataloader/worker.py — unverified): the
    dataset/collate_fn ship to each worker once, batch index lists are
    dispatched with a bounded in-flight window, and results are yielded
    strictly in order. Falls back to a daemon prefetch thread when the
    dataset/collate aren't picklable or the dataset is iterable —
    spawn (not fork) is mandatory here because a forked child of a
    process with a live TPU client hangs.
    """

    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=False, timeout=0, worker_init_fn=None,
                 persistent_workers=False):
        self.dataset = dataset
        self.return_list = return_list
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = max(2, prefetch_factor)
        self.worker_init_fn = worker_init_fn
        self.persistent_workers = persistent_workers
        self.timeout = timeout
        self._executor = None
        self._claim_dir = None
        self._picklable_ok = None  # decided once, on first iteration
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if batch_sampler is not None:
            self.batch_sampler = batch_sampler
            self.batch_size = getattr(batch_sampler, "batch_size", batch_size)
        elif self._iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        else:
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size,
                drop_last=drop_last,
            )
            self.batch_size = batch_size

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset DataLoader has no len()")
        return len(self.batch_sampler)

    def _native_batch_iter(self):
        """Native fast path: TensorDataset over numpy arrays + default
        collate → per-field multithreaded row gather in C++ (csrc/),
        yielding device-ready contiguous batches."""
        from . import _native

        fields = self.dataset.tensors
        for batch_idx in self.batch_sampler:
            idx = np.asarray(list(batch_idx), np.int64)
            yield [Tensor(_native.gather_rows(t, idx)) for t in fields]

    def _use_native_fast_path(self):
        from . import _native

        return (
            isinstance(self.dataset, TensorDataset)
            and self.collate_fn is default_collate_fn
            and bool(self.dataset.tensors)
            and all(isinstance(t, np.ndarray) for t in self.dataset.tensors)
            and _native.lib() is not None
        )

    def _fetch_iter(self):
        if not self._iterable_mode and self._use_native_fast_path():
            yield from self._native_batch_iter()
            return
        if self._iterable_mode:
            buf = []
            for item in self.dataset:
                buf.append(item)
                if len(buf) == self.batch_size:
                    yield self.collate_fn(buf)
                    buf = []
            if buf and not getattr(self, "drop_last", False):
                yield self.collate_fn(buf)
        else:
            for batch_idx in self.batch_sampler:
                yield self.collate_fn([self.dataset[i] for i in batch_idx])

    def _ensure_executor(self):
        if self._executor is None:
            import multiprocessing as mp
            from concurrent.futures import ProcessPoolExecutor

            import tempfile

            ctx = mp.get_context("spawn")
            claim_dir = self._claim_dir = tempfile.mkdtemp(prefix="pdtpu_dl_")
            collate = (None if self.collate_fn is default_collate_fn
                       else self.collate_fn)
            ex = ProcessPoolExecutor(
                max_workers=self.num_workers, mp_context=ctx,
                initializer=_pool_init,
                initargs=(self.dataset, collate, self.worker_init_fn,
                          claim_dir, self.num_workers),
            )
            # Spawn every worker NOW with the accelerator disabled in the
            # inherited env: children unpickle initargs during bootstrap
            # (before the initializer runs), and a Tensor-bearing
            # dataset must not touch the parent's chip from a worker:
            # a chip belongs to one process.
            prev = os.environ.get("JAX_PLATFORMS")
            os.environ["JAX_PLATFORMS"] = "cpu"
            try:
                ex.submit(_pool_warmup).result()
            finally:
                if prev is None:
                    os.environ.pop("JAX_PLATFORMS", None)
                else:
                    os.environ["JAX_PLATFORMS"] = prev
            self._executor = ex
        return self._executor

    def _process_iter(self):
        from collections import deque

        ex = self._ensure_executor()
        window = self.prefetch_factor * self.num_workers
        pending = deque()
        try:
            for batch_idx in self.batch_sampler:
                pending.append(ex.submit(_pool_fetch, list(batch_idx)))
                if len(pending) >= window:
                    yield _tensorize(pending.popleft().result(
                        timeout=self.timeout or None))
            while pending:
                yield _tensorize(pending.popleft().result(
                    timeout=self.timeout or None))
        finally:
            if not self.persistent_workers:
                self.close()

    def close(self):
        """Shut down pool workers (also for ``persistent_workers=True``)
        and remove the worker-id claim directory. Idempotent; called
        automatically at the end of each epoch for non-persistent pools
        and from ``__del__`` otherwise."""
        if self._executor is not None:
            self._executor.shutdown(wait=False, cancel_futures=True)
            self._executor = None
        if self._claim_dir is not None:
            import shutil

            shutil.rmtree(self._claim_dir, ignore_errors=True)
            self._claim_dir = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def __iter__(self):
        if self.num_workers == 0:
            yield from self._fetch_iter()
            return
        if self._picklable_ok is None:
            self._picklable_ok = (not self._iterable_mode) and _picklable(
                self.dataset, self.collate_fn, self.worker_init_fn
            )
        if self._picklable_ok:
            yield from self._process_iter()
            return
        # background-thread prefetch pipeline
        q: queue.Queue = queue.Queue(maxsize=self.prefetch_factor * self.num_workers)
        sentinel = object()
        error: list = []

        def producer():
            try:
                for item in self._fetch_iter():
                    q.put(item)
            except BaseException as e:  # propagate to the consumer, don't
                error.append(e)         # silently truncate the epoch
            finally:
                q.put(sentinel)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        while True:
            item = q.get()
            if item is sentinel:
                break
            yield item
        t.join()
        if error:
            raise error[0]
