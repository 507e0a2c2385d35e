"""DataLoader (reference: python/paddle/io/dataloader/dataloader_iter.py —
multiprocess worker pool + blocking queues; here a thread prefetch pipeline,
since batches are numpy and the consumer is an async TPU dispatch)."""

from __future__ import annotations

import itertools
import queue
import threading
from typing import Callable, Optional

import numpy as np
from ..enforce import InvalidTypeError

from .dataset import Dataset, IterableDataset
from .sampler import BatchSampler, DistributedBatchSampler

__all__ = ["DataLoader", "default_collate_fn", "get_worker_info",
           "prefetch_to_device"]

_worker_info = threading.local()


def get_worker_info():
    return getattr(_worker_info, "info", None)


class WorkerInfo:
    def __init__(self, id, num_workers, dataset):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset


def default_collate_fn(batch):
    """Stack a list of samples into batched numpy arrays (reference:
    python/paddle/io/dataloader/collate.py)."""
    sample = batch[0]
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, float, np.integer, np.floating)):
        return np.asarray(batch)
    if isinstance(sample, (str, bytes)):
        return list(batch)
    if isinstance(sample, dict):
        return {k: default_collate_fn([b[k] for b in batch]) for k in sample}
    if isinstance(sample, (tuple, list)):
        transposed = list(zip(*batch))
        return type(sample)(default_collate_fn(list(col)) for col in transposed)
    try:
        return np.stack([np.asarray(b) for b in batch])
    except Exception:
        return list(batch)


def _is_device_puttable(leaf):
    import jax
    return isinstance(leaf, (np.ndarray, np.generic, jax.Array))


def prefetch_to_device(iterator, size: int = 2, sharding=None):
    """Device double-buffering: keep `size` batches' host->device
    transfers in flight ahead of consumption.

    ``jax.device_put`` is asynchronous — it returns immediately with the
    DMA enqueued — so holding a small deque of already-put batches means
    the NEXT batch's transfer rides under the CURRENT step's compute
    instead of serializing before the dispatch (the input-pipeline
    equivalent of the comm_overlap gradient schedule). Array leaves
    (numpy / jax) are transferred, to `sharding` when given; non-array
    leaves (strings, python scalars) pass through untouched.

    Used by hapi.Model.fit; wrap any batch iterator:
        for batch in prefetch_to_device(loader, size=2): ...
    """
    import collections

    import jax

    from ..enforce import enforce_ge
    enforce_ge(size, 1, op="prefetch_to_device", name="size")

    def put(batch):
        return jax.tree.map(
            lambda leaf: (jax.device_put(leaf, sharding)
                          if _is_device_puttable(leaf) else leaf), batch)

    it = iter(iterator)
    buf = collections.deque()
    done = False
    while True:
        while not done and len(buf) < size:
            try:
                buf.append(put(next(it)))
            except StopIteration:
                done = True
        if not buf:
            return
        yield buf.popleft()


class DataLoader:
    def __init__(self, dataset: Dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=None, use_buffer_reader=True, prefetch_factor=None,
                 use_shared_memory=True, timeout=0, worker_init_fn=None):
        del feed_list, places, return_list, use_shared_memory, timeout
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        if num_workers is None:
            from ..flags import flag
            num_workers = int(flag("dataloader_num_workers"))
        self.num_workers = num_workers
        if prefetch_factor is None:
            from ..flags import flag
            prefetch_factor = int(flag("io_prefetch_factor"))
        self.prefetch_factor = max(prefetch_factor, 1)
        self.use_buffer_reader = use_buffer_reader
        self.worker_init_fn = worker_init_fn
        self._iterable = isinstance(dataset, IterableDataset)
        if self._iterable:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size,
                drop_last=drop_last)

    def __len__(self):
        if self._iterable:
            raise InvalidTypeError("IterableDataset has no len()",
                                   op="DataLoader.__len__")
        return len(self.batch_sampler)

    def _iter_batches(self):
        if self._iterable:
            it = iter(self.dataset)
            while True:
                batch = list(itertools.islice(it, self.batch_size))
                if not batch:
                    return
                if len(batch) < self.batch_size and self.drop_last:
                    return
                yield self.collate_fn(batch)
        else:
            for indices in self.batch_sampler:
                yield self.collate_fn([self.dataset[i] for i in indices])

    def __iter__(self):
        if self.num_workers <= 0 or self._iterable:
            yield from self._iter_batches()
            return
        # threaded pipeline: workers fetch+collate batches ahead of consumption
        out_q: "queue.Queue" = queue.Queue(maxsize=self.num_workers * self.prefetch_factor)
        idx_q: "queue.Queue" = queue.Queue()
        batches = list(self.batch_sampler)
        for i, b in enumerate(batches):
            idx_q.put((i, b))
        n_batches = len(batches)
        stop = threading.Event()

        def worker(wid):
            _worker_info.info = WorkerInfo(wid, self.num_workers, self.dataset)
            if self.worker_init_fn is not None:
                self.worker_init_fn(wid)
            while not stop.is_set():
                try:
                    i, indices = idx_q.get_nowait()
                except queue.Empty:
                    return
                try:
                    item = self.collate_fn([self.dataset[j] for j in indices])
                except Exception as e:  # surface worker errors to consumer
                    item = e
                # bounded put that observes stop (consumer may abandon early)
                while not stop.is_set():
                    try:
                        out_q.put((i, item), timeout=0.1)
                        break
                    except queue.Full:
                        continue

        threads = [threading.Thread(target=worker, args=(w,), daemon=True)
                   for w in range(self.num_workers)]
        for t in threads:
            t.start()
        try:
            # reorder to sampler order
            pending = {}
            next_idx = 0
            received = 0
            while received < n_batches:
                i, data = out_q.get()
                received += 1
                pending[i] = data
                while next_idx in pending:
                    item = pending.pop(next_idx)
                    next_idx += 1
                    if isinstance(item, Exception):
                        raise item
                    yield item
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=1.0)
