"""Data pipeline: DataFeeder, DataLoader, reader decorators.

Reference: python/paddle/fluid/data_feeder.py:212 DataFeeder,
fluid/reader.py:101 DataLoader.from_generator / :953 GeneratorLoader,
python/paddle/reader/decorator.py (shuffle/batch/buffered).  TPU-first:
instead of a C++ LoDTensorBlockingQueue feeding a create_py_reader_op in
the graph, the loader is a host-side prefetching iterator that yields feed
dicts; jax.device_put overlaps H2D with compute via async dispatch, and
the double-buffer decorator mirrors buffered_reader (reference:
operators/reader/buffered_reader.cc).
"""
from __future__ import annotations

import queue
import threading
from typing import Callable, Iterable, List, Optional, Sequence

import numpy as np

from .framework.core import Variable
from .framework.dtype import to_numpy_dtype
from .framework.scope import LoDTensor


class DataFeeder:
    """reference: data_feeder.py:212 — converts sample lists to feed dicts."""

    def __init__(self, feed_list: Sequence, place=None, program=None):
        self.feed_list = feed_list
        self.place = place

    def feed(self, iterable) -> dict:
        slots: List[List] = [[] for _ in self.feed_list]
        for sample in iterable:
            for i, val in enumerate(sample):
                slots[i].append(np.asarray(val))
        out = {}
        for var, vals in zip(self.feed_list, slots):
            name = var.name if isinstance(var, Variable) else str(var)
            arr = np.stack(vals) if vals and vals[0].shape else np.asarray(vals)
            if isinstance(var, Variable) and var.dtype is not None:
                want = to_numpy_dtype(var.dtype)
                # honor declared non-batch dims (e.g. label shape [-1, 1])
                want_rank = len(var.shape)
                while arr.ndim < want_rank:
                    arr = arr[..., None]
                arr = arr.astype(want)
            out[name] = arr
        return out


class DataLoader:
    """reference: fluid/reader.py:101.

    from_generator returns a loader whose set_sample_generator /
    set_sample_list_generator / set_batch_generator feed a background
    prefetch queue (the py_reader blocking-queue analog).

    ``use_multiprocess=True`` moves the whole reader pipeline (user
    generator + batching + ndarray conversion) into a forked worker
    process streaming batches over a bounded queue — the
    GeneratorLoader._start_process path (reference:
    fluid/reader.py _reader_process_loop + imperative/data_loader.cc's
    SIGCHLD handling); the parent polls worker liveness so a crashed
    worker raises instead of hanging the training loop.  When places are
    given, a second stage device_puts upcoming batches ahead of use (the
    buffered_reader.cc double-buffer-to-device analog).
    """

    def __init__(self, feed_list=None, capacity=64, iterable=True,
                 return_list=False, use_double_buffer=True,
                 use_multiprocess=False, drop_last=True):
        self.feed_list = feed_list or []
        self.capacity = capacity
        self.iterable = iterable
        self.return_list = return_list
        self.use_double_buffer = use_double_buffer
        self.use_multiprocess = use_multiprocess
        self.drop_last = drop_last
        self._batch_fn: Optional[Callable[[], Iterable]] = None
        self._places = None
        self._worker = None  # live worker process (for tests/debugging)

    @staticmethod
    def from_generator(feed_list=None, capacity=64, use_double_buffer=True,
                       iterable=True, return_list=False, use_multiprocess=False,
                       drop_last=True):
        return DataLoader(feed_list, capacity, iterable, return_list,
                          use_double_buffer, use_multiprocess, drop_last)

    @staticmethod
    def from_dataset(dataset, places=None, drop_last=True):
        loader = DataLoader(drop_last=drop_last)
        loader._batch_fn = lambda: iter(dataset)
        loader._places = places
        return loader

    # ------------------------------------------------------------------
    def set_sample_generator(self, reader, batch_size, drop_last=None,
                             places=None):
        from .reader_decorator import batch as batch_dec

        if drop_last is None:
            drop_last = self.drop_last
        return self.set_sample_list_generator(
            batch_dec(reader, batch_size, drop_last), places
        )

    def set_sample_list_generator(self, reader, places=None):
        feeder = DataFeeder(self.feed_list)

        def gen():
            for samples in reader():
                yield feeder.feed(samples)

        self._batch_fn = gen
        self._places = places
        return self

    def set_batch_generator(self, reader, places=None):
        def gen():
            for batch in reader():
                if isinstance(batch, dict):
                    yield batch
                else:
                    out = {}
                    for var, val in zip(self.feed_list, batch):
                        name = var.name if isinstance(var, Variable) else str(var)
                        out[name] = np.asarray(val)
                    yield out

        self._batch_fn = gen
        self._places = places
        return self

    # ------------------------------------------------------------------
    def _thread_iter(self):
        """In-process background prefetch (the r2 path)."""
        q: "queue.Queue" = queue.Queue(maxsize=max(2, self.capacity))
        sentinel = object()
        err: list = []

        def worker():
            try:
                for item in self._batch_fn():
                    q.put(item)
            except BaseException as e:  # propagate to consumer
                err.append(e)
            finally:
                q.put(sentinel)

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        while True:
            item = q.get()
            if item is sentinel:
                if err:
                    raise err[0]
                return
            yield item

    def _mp_iter(self):
        """Worker-process prefetch (reference:
        fluid/reader.py GeneratorLoader._start_process /
        _reader_process_loop): the reader runs in a forked child, batches
        stream over a bounded queue, and the parent detects a dead worker
        instead of blocking forever.  The child is a fork of a parent
        that holds the chip: the reader must stay on the host (numpy) —
        a child that touches JAX would fight the parent for the device."""
        import multiprocessing as mp

        ctx = mp.get_context("fork")
        q = ctx.Queue(maxsize=max(2, self.capacity))
        DONE, ERR = "__pt_reader_done__", "__pt_reader_err__"
        batch_fn = self._batch_fn

        def worker_loop():
            try:
                for item in batch_fn():
                    q.put(item)
                q.put((DONE,))
            except BaseException as e:
                import traceback

                q.put((ERR, repr(e), traceback.format_exc()))

        proc = ctx.Process(target=worker_loop, daemon=True)
        proc.start()
        self._worker = proc
        try:
            while True:
                try:
                    item = q.get(timeout=2.0)
                except queue.Empty:
                    if not proc.is_alive():
                        raise RuntimeError(
                            f"DataLoader worker process died unexpectedly "
                            f"(exitcode={proc.exitcode}) — e.g. killed by "
                            f"the OOM killer or a signal"
                        )
                    continue
                if isinstance(item, tuple) and item and item[0] == DONE:
                    return
                if isinstance(item, tuple) and item and item[0] == ERR:
                    raise RuntimeError(
                        f"DataLoader worker raised: {item[1]}\n{item[2]}")
                yield item
        finally:
            self._worker = None
            if proc.is_alive():
                proc.terminate()
            proc.join(timeout=5)
            q.close()

    def _device_prefetch(self, it, depth=2):
        """Stage upcoming batches on device ahead of use (reference:
        operators/reader/buffered_reader.cc — double buffer to the
        device): jax.device_put dispatches the H2D copy asynchronously,
        so the copy of batch k+1 overlaps compute of batch k."""
        import collections

        import jax

        device = None
        places = self._places
        if places:
            p = places[0] if isinstance(places, (list, tuple)) else places
            if hasattr(p, "jax_device"):
                device = p.jax_device()
        if device is None:
            yield from it
            return
        buf = collections.deque()
        for feed in it:
            if isinstance(feed, dict):
                feed = {k: jax.device_put(v, device)
                        if isinstance(v, np.ndarray) else v
                        for k, v in feed.items()}
            buf.append(feed)
            if len(buf) >= depth:
                yield buf.popleft()
        while buf:
            yield buf.popleft()

    def __iter__(self):
        if self._batch_fn is None:
            raise RuntimeError("DataLoader has no generator set")
        if self.use_multiprocess:
            it = self._mp_iter()
        elif self.use_double_buffer:
            it = self._thread_iter()
        else:
            it = self._batch_fn()
        if self.use_double_buffer:
            it = self._device_prefetch(it)
        yield from it

    # legacy py_reader-style start/reset are no-ops for iterable loaders
    def start(self):
        pass

    def reset(self):
        pass


def _with_sparse_prefetch(program, it):
    """One-batch look-ahead: while batch N runs, submit batch N+1's
    sparse ids to the SparsePrefetcher so the distributed_lookup_table
    pulls overlap the device step (SURVEY §7 hard part 5; reference:
    communicator.h:237 background threads).  Engaged only in
    stale-tolerant modes — prefetch.prefetch_enabled()."""
    if program is None:
        yield from it
        return
    lookups = []  # (table, ids var name) per slot
    try:
        for op_ in program.global_block().ops:
            if op_.type == "distributed_lookup_table":
                ids = op_.inputs.get("Ids", [])
                # r5 cross-table merge: one op carries per-slot
                # table_names; a slot submitted under the wrong table
                # would never be take()n and leak in the prefetcher
                tables = (op_.attrs.get("table_names")
                          or [op_.attrs.get("table_name")] * len(ids))
                lookups.extend(zip(tables, ids))
    except Exception:
        lookups = []
    if not lookups:
        yield from it
        return

    from .distributed_ps import prefetch as _prefetch
    from .distributed_ps import runtime as _ps_runtime

    def submit(feed):
        if not _prefetch.prefetch_enabled():
            return
        try:
            pre = _ps_runtime.prefetcher()
        except Exception:
            return
        for table, name in lookups:
            ids = feed.get(name)
            if ids is None:
                continue
            pre.submit(table, np.asarray(ids).astype(np.int64).ravel())

    prev = next(it, None)
    while prev is not None:
        nxt = next(it, None)
        if nxt is not None:
            submit(nxt)
        yield prev
        prev = nxt


_multitrainer_lock = __import__("threading").Lock()


def _train_from_dataset(executor, program, dataset, scope, fetch_list,
                        fetch_info, print_period, thread=0):
    """Dataset-driven training loop (reference: executor.py:1448
    train_from_dataset -> MultiTrainer + one HogwildWorker per thread,
    multi_trainer.cc:119 / hogwild_worker.cc:189).

    ``thread`` (or dataset.set_thread) > 1 runs N worker threads that
    round-robin the dataset's batch stream against the shared root
    scope: the whole-program jit keeps intermediates inside XLA, so the
    only scope traffic is the persistable state — concurrent, lock-free
    Hogwild updates, exactly the reference's semantics.  On the PS path
    this overlaps the per-batch pull/push RPC latency of one worker with
    the compute of the others, which is what actually feeds the chip on
    a host-loop-bound workload (measured r4: 1.39x at thread=4 on the
    host-bound CPU config)."""
    if dataset is None:
        raise ValueError("dataset is required")
    block = program.global_block() if program is not None else None

    def clean(feed):
        if block is None:
            return feed
        # datasets emit companion "<slot>.lens" entries; feed only what
        # the program declares (reference: DataFeed binds use_slots)
        return {k: v for k, v in feed.items() if block.has_var(k)}

    def report(step, out):
        if fetch_list and step % print_period == 0:
            infos = fetch_info or [getattr(f, "name", str(f))
                                   for f in fetch_list]
            msg = ", ".join(f"{i}={np.asarray(v).mean():.6f}"
                            for i, v in zip(infos, out))
            print(f"[train_from_dataset] step {step}: {msg}")

    nthreads = int(thread) or int(getattr(dataset, "thread_num", 1) or 1)
    it = dataset._iter_batches()
    it = _with_sparse_prefetch(program, it)
    if nthreads <= 1:
        step = 0
        for feed in it:
            out = executor.run(program, feed=clean(feed),
                               fetch_list=fetch_list, scope=scope)
            report(step, out)
            step += 1
        return None

    import threading

    from .framework.scope import global_scope
    from .utils import flags as _flags

    root = scope if scope is not None else global_scope()
    # One MultiTrainer at a time per process (reference: the trainer is a
    # process singleton, multi_trainer.cc) — also keeps the donation-flag
    # save/restore below from racing a second concurrent trainer.
    with _multitrainer_lock:
        # Hogwild workers share the parent scope's param buffers, so
        # buffer donation must be off (a buffer donated by worker A would
        # be a deleted buffer in worker B's captured arguments) — and so
        # must the executor step session: workers race on one
        # compiled.session, and a worker re-publishing its own
        # post-step state as "current" would silently discard the
        # updates another worker wrote to the scope in between
        old_donate = _flags._flags.get("FLAGS_tpu_donate_buffers")
        old_session = _flags._flags.get("FLAGS_tpu_step_session")
        _flags._flags["FLAGS_tpu_donate_buffers"] = False
        _flags._flags["FLAGS_tpu_step_session"] = False
        try:
            # first batch runs on the calling thread so the program
            # compiles once (workers then only hit the executor cache)
            first = next(it, None)
            if first is None:
                return None
            report(0, executor.run(program, feed=clean(first),
                                   fetch_list=fetch_list, scope=root))
            lock = threading.Lock()
            stop = threading.Event()
            counter = [1]
            errors = []

            def worker():
                while not stop.is_set():
                    try:
                        with lock:
                            feed = next(it, None)
                            if feed is None:
                                return
                            step = counter[0]
                            counter[0] += 1
                        out = executor.run(program, feed=clean(feed),
                                           fetch_list=fetch_list,
                                           scope=root)
                        report(step, out)
                    except Exception as exc:  # surface the first failure
                        errors.append(exc)
                        stop.set()  # abort the other workers promptly
                        return

            workers = [threading.Thread(target=worker, daemon=True)
                       for _ in range(nthreads)]
            for w in workers:
                w.start()
            for w in workers:
                w.join()
            if errors:
                raise errors[0]
        finally:
            _flags._flags["FLAGS_tpu_donate_buffers"] = old_donate
            _flags._flags["FLAGS_tpu_step_session"] = old_session
    return None


class PyReader(DataLoader):
    """reference: fluid/reader.py PyReader (layers/io.py py_reader shim).

    Either pass feed_list (create_py_reader_by_data) or shapes+dtypes
    (py_reader) — in the latter case data vars are created on the current
    main program and exposed via ``.data_vars`` / read_file().
    """

    def __init__(self, capacity=64, shapes=None, dtypes=None, feed_list=None,
                 use_double_buffer=True, iterable=True, return_list=False,
                 name=None):
        if feed_list is None and shapes is not None:
            from .layers import data as data_layer
            feed_list = [
                data_layer(f"{name or 'py_reader'}_slot_{i}", list(s)[1:],
                           dtype=dt, append_batch_size=True)
                for i, (s, dt) in enumerate(zip(shapes, dtypes))
            ]
        super().__init__(feed_list, capacity, iterable, return_list,
                         use_double_buffer)

    # py_reader API names
    def decorate_paddle_reader(self, reader, places=None):
        return self.set_sample_list_generator(reader, places)

    def decorate_sample_list_generator(self, reader, places=None):
        return self.set_sample_list_generator(reader, places)

    def decorate_batch_generator(self, reader, places=None):
        return self.set_batch_generator(reader, places)

    def decorate_tensor_provider(self, reader, places=None):
        return self.set_batch_generator(reader, places)

    def read_file(self):
        """The program-feed vars this reader fills (layers/io.py
        read_file analog under feed-based execution)."""
        return list(self.feed_list)

    def start(self):
        return None

    def reset(self):
        return None
