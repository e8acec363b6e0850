"""Batching data loader with background workers and device prefetch.

TPU-native equivalent of the reference's
``DataLoader(dataset, batch_size, num_workers=8, pin_memory=True,
sampler=sampler, drop_last=True)`` (reference ``README.md:84-91``):

* ``num_workers`` background threads fetch+decode samples ahead of the
  training loop (the C++ staging ring buffer in ``native/`` provides the
  zero-copy fast path; this module is the portable engine);
* ``pin_memory``'s role — staging batches so the accelerator copy is
  async — is played by :func:`device_prefetch`, which ``jax.device_put``\\ s
  the next batch(es) onto the chips while the current step runs (double
  buffering), the idiomatic TPU input pipeline (SURVEY §2 native-equivalents
  item 5);
* ``drop_last=True`` at the batch level keeps per-step shapes static — on
  TPU this is not just a convergence nicety but a compile-cache requirement
  (dynamic shapes retrigger XLA compilation).
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterator, Sequence

import jax
import numpy as np

from tpu_syncbn.data.dataset import Dataset
from tpu_syncbn.data.sampler import Sampler, SequentialSampler
from tpu_syncbn.obs import stepstats as obs_stepstats
from tpu_syncbn.obs import telemetry, tracing


class WorkerError(RuntimeError):
    """A dataset/collate error raised inside a worker process, carrying
    the worker's traceback text."""


class WorkerInfo:
    """What :func:`get_worker_info` returns inside a worker process —
    torch's ``get_worker_info()`` contract. ``dataset`` is the worker's
    OWN (unpickled) copy: mutate/reseed THIS object in a
    ``worker_init_fn``; any transform object captured in the init fn's
    closure would be an unrelated third pickle copy."""

    def __init__(self, id: int, num_workers: int, dataset):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset


_worker_info: WorkerInfo | None = None


def get_worker_info() -> WorkerInfo | None:
    """Inside a process worker: this worker's :class:`WorkerInfo`; in the
    main process (or thread workers, which share objects): ``None``."""
    return _worker_info


# Worker wire protocol, shared by thread and process paths:
#   index queue:  ("batch", epoch, seq, idxs) | ("epoch_end", epoch) |
#                 ("stop",)
#   out queue:    ("ok", epoch, seq, batch) | ("err", epoch, seq, err) |
#                 ("epoch_end", epoch) | ("init_err", traceback_text)
# Threads use epoch=0 throughout (workers die with the iterator, so no
# staleness); persistent process workers tag everything with the live
# epoch so batches from an abandoned iteration are dropped, not yielded.


def _build_batch(dataset, collate_fn, idxs, *, seq: int, worker: int):
    """One batch, where every worker model builds it: the samples
    (dataset + transforms), then the collation. Under tracing
    (``obs.tracing``) a ``loader.build`` span with ``loader.collate``
    as its child, on the building thread (what of the build is not
    collation is the samples); a process worker records in its own
    process."""
    with tracing.span("loader.build", seq=seq, worker=worker):
        samples = [dataset[i] for i in idxs]
        with tracing.span("loader.collate"):
            return collate_fn(samples)


def _persistent_process_worker(
    wid, num_workers, dataset, collate_fn, worker_init_fn, index_q, out_q
):
    """Top-level (spawn-picklable) body for ``worker_type="process"``
    workers. Lives across epochs: ``epoch_end`` is echoed and the loop
    continues; only ``stop`` (or parent exit — daemon) ends it."""
    import traceback

    global _worker_info
    _worker_info = WorkerInfo(id=wid, num_workers=num_workers, dataset=dataset)
    try:
        if worker_init_fn is not None:
            worker_init_fn(wid)
    except Exception:
        out_q.put(("init_err", traceback.format_exc()))
        return
    while True:
        item = index_q.get()
        tag = item[0]
        if tag == "stop":
            return
        if tag == "epoch_end":
            out_q.put(("epoch_end", item[1]))
            continue
        _, epoch, seq, idxs = item
        try:
            out_q.put(("ok", epoch, seq,
                       _build_batch(dataset, collate_fn, idxs,
                                    seq=seq, worker=wid)))
        except Exception:
            out_q.put(("err", epoch, seq, traceback.format_exc()))


def _bounded_put(q, item, stop: threading.Event) -> bool:
    """put() that gives up when the consumer abandoned the iterator, so
    no producer can block forever on a full queue no one will drain."""
    while not stop.is_set():
        try:
            q.put(item, timeout=0.05)
            return True
        except queue.Full:
            continue
    return False


def _queue_depth(out_queues) -> int:
    """Total batches currently buffered across worker out-queues; -1
    where the platform's mp.Queue cannot answer (macOS qsize)."""
    try:
        return sum(q.qsize() for q in out_queues)
    except (NotImplementedError, OSError):
        return -1


def _consume_ordered(out_queues, dispatch_error, *, epoch=0, idle_check=None):
    """Yield batches in dispatch order from per-worker out queues (batch
    ``seq`` was dispatched to worker ``seq % n`` round-robin, so reading
    the queues round-robin restores global order). ``idle_check(wid)``
    may return a final drained item or raise for a dead worker.

    Telemetry (when enabled): per-batch ``loader.fetch_wait_s`` (time the
    consumer spent inside this generator waiting on workers — queue
    starvation shows up here), a ``loader.queue_depth`` gauge sampled at
    each yield (0 with a step-bound consumer means the loader is the
    bottleneck), and a ``loader.batches`` counter.

    Tracing (when on): one ``loader.fetch`` span per batch on the
    consumer's thread, from resuming to having the batch in hand, with
    its ``seq`` and ``worker`` and the batches buffered across all
    queues when the wait began (``depth_before``) and when it ended
    (``depth``, the gauge's sample): a wait that ends with ``depth`` > 0
    waited for ITS worker while others had batches ready."""
    n = len(out_queues)
    done = [False] * n
    seq = 0

    def take():
        """The next batch in dispatch order as (seq, worker, payload);
        None once every worker has ended its epoch."""
        nonlocal seq
        while not all(done):
            wid = seq % n
            if done[wid]:
                seq += 1
                continue
            try:
                item = out_queues[wid].get(timeout=0.05)
            except queue.Empty:
                if dispatch_error:
                    raise dispatch_error[0]
                item = idle_check(wid) if idle_check is not None else None
                if item is None:
                    continue
            tag = item[0]
            if tag == "init_err":
                raise WorkerError(f"worker {wid} init failed:\n{item[1]}")
            if item[1] != epoch:
                continue  # stale output from an abandoned iteration: drop
            if tag == "epoch_end":
                done[wid] = True
                seq += 1
                continue
            _, _, got_seq, payload = item
            assert got_seq == seq, f"order violation: {got_seq} != {seq}"
            if tag == "err":
                if isinstance(payload, BaseException):
                    raise payload  # thread worker: original exception object
                raise WorkerError(f"error in worker {wid}:\n{payload}")
            seq += 1
            return got_seq, wid, payload
        return None

    while True:
        t_resume = time.perf_counter()
        record = telemetry.enabled()
        tracer = tracing.get()
        # begin/end, not a with block: the span has to close before the
        # yield, and what it found is known only at its end
        token = None if tracer is None else tracer.begin(
            "loader.fetch", depth_before=_queue_depth(out_queues))
        found: dict = {}
        try:
            got = take()
            if got is not None and (record or token is not None):
                # one sample for the span and the gauge
                found = {"seq": got[0], "worker": got[1],
                         "depth": _queue_depth(out_queues)}
        finally:
            if token is not None:
                tracer.end(token, **found)
        if got is None:
            return
        if record:
            telemetry.observe(
                "loader.fetch_wait_s", time.perf_counter() - t_resume
            )
            telemetry.set_gauge("loader.queue_depth", found["depth"])
            telemetry.count("loader.batches")
        yield got[2]


def _close_pool(pool) -> None:
    """Terminate a process-worker pool. Reached from THREE owners —
    explicit ``close()``, the ``weakref.finalize`` GC/atexit finalizer,
    and interpreter shutdown — so it must be idempotent and must not
    assume queue liveness (a dead worker's queue can already be closed);
    a cleanup path that can crash orphans the very workers it exists to
    reap."""
    if pool.get("closed"):
        return
    pool["closed"] = True
    for q in pool["index_queues"]:
        try:
            q.put_nowait(("stop",))
        except (queue.Full, ValueError, OSError):
            pass  # full, or queue already closed
    for p in pool["procs"]:
        p.join(timeout=0.5)
        if p.is_alive():
            p.terminate()
            p.join(timeout=5)
    for q in (*pool["index_queues"], *pool["out_queues"]):
        try:
            q.cancel_join_thread()
            q.close()
        except (ValueError, OSError):
            pass


def default_collate(samples: Sequence[Any]):
    """Stack a list of samples into batched numpy arrays (mirrors torch's
    default_collate for array/tuple/dict/scalar structures)."""
    first = samples[0]
    if isinstance(first, tuple) and hasattr(first, "_fields"):  # namedtuple
        return type(first)(*(default_collate(list(s)) for s in zip(*samples)))
    if isinstance(first, (tuple, list)):
        return type(first)(default_collate(list(s)) for s in zip(*samples))
    if isinstance(first, dict):
        return {k: default_collate([s[k] for s in samples]) for k in first}
    return np.stack([np.asarray(s) for s in samples])


class DataLoader:
    """Iterates batches of collated samples.

    ``num_workers`` workers run ``dataset[i]`` concurrently; batch order
    is deterministic — identical to the single-threaded order — because
    workers fill a slot-addressed reorder window, not a free-for-all
    queue.

    ``worker_type`` selects the concurrency model. ``"thread"`` (default)
    matches TPU-host reality: PIL's JPEG decode and numpy's transforms
    release the GIL, so threads parallelize the real work without
    process-spawn or pickling overhead. ``"process"`` is the reference's
    literal model (8 worker *processes*, ``README.md:87``) for
    Python-heavy, GIL-bound per-sample work: the dataset and collate_fn
    must be picklable, workers are spawned ONCE per loader and persist
    across epochs (each worker owns a frozen pickle-copy of the dataset
    — parent-side mutations after the first iteration are not seen), and
    ``worker_init_fn(worker_id)`` (torch's ``worker_init_fn``) runs once
    per worker — reseed per-worker augmentation RNGs there via
    ``get_worker_info().dataset``, which is the worker's own copy.
    ``close()`` (or GC) shuts the pool down. Spawn's standard contract
    applies (as for torch's workers on spawn platforms): the training
    script's ``__main__`` must be importable — guard entry with
    ``if __name__ == "__main__":`` and don't drive from a REPL/stdin.
    """

    def __init__(
        self,
        dataset: Dataset,
        batch_size: int,
        *,
        sampler: Sampler | None = None,
        num_workers: int = 0,
        drop_last: bool = False,
        collate_fn: Callable = default_collate,
        prefetch_batches: int = 2,
        worker_type: str = "thread",
        worker_init_fn: Callable[[int], None] | None = None,
    ):
        if batch_size < 1:
            raise ValueError("batch_size must be >= 1")
        if num_workers < 0:
            raise ValueError("num_workers must be >= 0")
        if worker_type not in ("thread", "process"):
            raise ValueError(
                f"worker_type must be 'thread' or 'process', got {worker_type!r}"
            )
        self.dataset = dataset
        self.batch_size = batch_size
        self.sampler = sampler if sampler is not None else SequentialSampler(len(dataset))
        self.num_workers = num_workers
        self.drop_last = drop_last
        self.collate_fn = collate_fn
        self.prefetch_batches = max(1, prefetch_batches)
        self.worker_type = worker_type
        self.worker_init_fn = worker_init_fn
        self._pool: dict | None = None
        self._pool_finalizer = None
        self._epoch = 0
        self._iterating = False

    def _batches_of_indices(self) -> Iterator[list[int]]:
        batch: list[int] = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self) -> int:
        n = len(self.sampler)
        if self.drop_last:
            return n // self.batch_size
        return -(-n // self.batch_size)

    def __iter__(self):
        if self.num_workers == 0:
            for seq, idxs in enumerate(self._batches_of_indices()):
                yield _build_batch(self.dataset, self.collate_fn, idxs,
                                   seq=seq, worker=0)
            return
        if self.worker_type == "process":
            yield from self._iter_processes()
            return
        yield from self._iter_threaded()

    def _start_dispatcher(self, index_queues, stop, epoch):
        """Feed (epoch, seq)-tagged index batches round-robin, then an
        epoch_end marker per worker. Returns the error box the consumer
        polls (a user sampler raising mid-iteration must surface, not
        hang the loop)."""
        dispatch_error: list[BaseException] = []

        def run():
            seq = 0
            try:
                for idxs in self._batches_of_indices():
                    q = index_queues[seq % len(index_queues)]
                    if not _bounded_put(q, ("batch", epoch, seq, idxs), stop):
                        return
                    seq += 1
            except BaseException as e:
                dispatch_error.append(e)
                return
            for q in index_queues:
                if not _bounded_put(q, ("epoch_end", epoch), stop):
                    return

        threading.Thread(target=run, daemon=True).start()
        return dispatch_error

    # -- process workers ---------------------------------------------------

    def _ensure_pool(self) -> dict:
        """Spawn the persistent worker processes once per loader: spawn
        (fork is unsafe once jax's thread pools exist) re-imports the
        interpreter per worker, so paying it per epoch would stall every
        epoch boundary. Workers live until close()/GC."""
        if self._pool is not None:
            return self._pool
        import multiprocessing as mp
        import weakref

        ctx = mp.get_context("spawn")
        n = self.num_workers
        pool = {
            "index_queues": [
                ctx.Queue(maxsize=self.prefetch_batches) for _ in range(n)
            ],
            "out_queues": [
                ctx.Queue(maxsize=self.prefetch_batches) for _ in range(n)
            ],
        }
        pool["procs"] = [
            ctx.Process(
                target=_persistent_process_worker,
                args=(w, n, self.dataset, self.collate_fn,
                      self.worker_init_fn,
                      pool["index_queues"][w], pool["out_queues"][w]),
                daemon=True,
            )
            for w in range(n)
        ]
        for p in pool["procs"]:
            p.start()
        self._pool = pool
        self._pool_finalizer = weakref.finalize(self, _close_pool, pool)
        return pool

    def close(self) -> None:
        """Shut down persistent process workers. Idempotent: double
        close, close-after-GC-finalize, and close on a thread-mode loader
        (which has no pool) are all safe no-ops. A loader dropped
        *without* close() is reaped by the ``weakref.finalize`` installed
        at pool spawn (which also runs at interpreter exit), so abandoned
        loaders never orphan worker processes."""
        if self._pool is not None:
            if self._pool_finalizer is not None:
                # detach() is None-safe and False when the finalizer
                # already ran (GC beat us): _close_pool is idempotent
                # either way
                self._pool_finalizer.detach()
            _close_pool(self._pool)
            self._pool = None
            self._pool_finalizer = None

    def _iter_processes(self):
        """The reference's worker-process model (``README.md:87``): same
        slot-addressed reorder pipeline as the threaded path, over the
        persistent spawn pool; epoch tags keep outputs of an abandoned
        iteration from leaking into the next."""
        if self._iterating:
            # concurrent iterators would share the pool's queues under
            # different epoch tags and silently starve each other — the
            # thread path supports this (fresh queues per iterator), the
            # persistent pool cannot; fail loudly instead of hanging
            raise RuntimeError(
                "a process-mode DataLoader supports ONE active iterator; "
                "exhaust or abandon the previous iteration first (or use "
                "worker_type='thread' for concurrent iterators)"
            )
        pool = self._ensure_pool()
        self._epoch += 1
        epoch = self._epoch
        self._iterating = True
        stop = threading.Event()
        dispatch_error = self._start_dispatcher(
            pool["index_queues"], stop, epoch
        )

        def idle_check(wid):
            if not pool["procs"][wid].is_alive():
                try:
                    # the worker's final items can still be in the pipe
                    # when the process exits — drain before declaring death
                    return pool["out_queues"][wid].get_nowait()
                except queue.Empty:
                    raise WorkerError(
                        f"worker process {wid} died (exit code "
                        f"{pool['procs'][wid].exitcode}) without reporting"
                    ) from None
            return None

        try:
            yield from _consume_ordered(
                pool["out_queues"], dispatch_error,
                epoch=epoch, idle_check=idle_check,
            )
        finally:
            stop.set()
            self._iterating = False

    # -- thread workers ----------------------------------------------------

    def _iter_threaded(self):
        """Ordered pipeline: a dispatcher assigns batch slots round-robin;
        each worker collates its own batches; the consumer reassembles in
        slot order so output order matches the sequential loader."""
        n_workers = self.num_workers
        index_queues = [
            queue.Queue(maxsize=self.prefetch_batches) for _ in range(n_workers)
        ]
        out_queues = [
            queue.Queue(maxsize=self.prefetch_batches) for _ in range(n_workers)
        ]
        stop = threading.Event()

        def worker(wid: int):
            while True:
                try:
                    item = index_queues[wid].get(timeout=0.05)
                except queue.Empty:
                    if stop.is_set():
                        return
                    continue
                if item[0] == "epoch_end":
                    _bounded_put(out_queues[wid], ("epoch_end", 0), stop)
                    return  # thread workers are per-iteration
                _, _, seq, idxs = item
                try:
                    out = (
                        "ok", 0, seq,
                        _build_batch(self.dataset, self.collate_fn, idxs,
                                     seq=seq, worker=wid),
                    )
                except Exception as e:  # same-process: keep the object
                    out = ("err", 0, seq, e)
                if not _bounded_put(out_queues[wid], out, stop):
                    return

        threads = [
            threading.Thread(target=worker, args=(w,), daemon=True)
            for w in range(n_workers)
        ]
        for t in threads:
            t.start()
        dispatch_error = self._start_dispatcher(index_queues, stop, epoch=0)

        try:
            yield from _consume_ordered(out_queues, dispatch_error, epoch=0)
        finally:
            stop.set()
            # drain so workers blocked on put() can exit (the dispatcher's
            # puts poll `stop` and exit on their own)
            for q in out_queues:
                while not q.empty():
                    q.get_nowait()


def staged_iter(iterator, *, slots: int = 3, slot_mb: int = 64):
    """Route host batches through the native C++ staging ring
    (``native/csrc/staging.cc``) — the pinned-memory staging thread of the
    reference's ``pin_memory=True`` loader (``README.md:88``): a producer
    thread serializes each batch into a reusable 64-byte-aligned slot
    while the consumer devours the previous one, so collation/copy overlap
    the training step without per-batch allocation.

    Batches must be pytrees of numpy arrays (the loader's output). Falls
    back to passing batches through unchanged when the native library is
    unavailable or a batch exceeds ``slot_mb``.
    """
    from tpu_syncbn.runtime import native

    if not native.available():
        yield from iterator
        return

    ring = native.StagingRing(slots, slot_mb << 20)
    SENTINEL = object()
    ERROR = object()
    meta_q: queue.Queue = queue.Queue(maxsize=slots)
    stop = threading.Event()
    # Python-side permit per ring slot: the producer only enters the C++
    # acquire when a slot is guaranteed free, so it can never block inside
    # native code where stop/teardown couldn't reach it (the consumer
    # releases a permit after ring.release).
    free_slots = threading.Semaphore(slots)

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                meta_q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def pack(batch):
        leaves, treedef = jax.tree_util.tree_flatten(batch)
        total = sum(l.nbytes for l in leaves)
        if total > (slot_mb << 20):
            return None  # too big for a slot: bypass
        while not free_slots.acquire(timeout=0.05):
            if stop.is_set():
                return False
        slot, addr = ring.acquire()  # guaranteed non-blocking: permit held
        view = ring.view(addr, total)
        offset = 0
        metas = []
        for l in leaves:
            arr = np.ascontiguousarray(l)
            view[offset : offset + arr.nbytes] = arr.view(np.uint8).ravel()
            metas.append((arr.dtype.str, arr.shape, offset, arr.nbytes))
            offset += arr.nbytes
        ring.commit(slot, total)
        return treedef, metas

    def producer():
        try:
            for batch in iterator:
                packed = pack(batch)
                if packed is False:  # stop requested
                    return
                item = ("bypass", batch) if packed is None else ("slot", packed)
                if not _put(item):
                    return
        except BaseException as e:  # surface at the consumer, don't truncate
            _put((ERROR, e))
            return
        _put((SENTINEL, None))

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    try:
        while True:
            # bounded wait + producer-liveness check: the producer's
            # except/sentinel protocol *should* always enqueue a final
            # item, but a thread torn down without one (interpreter
            # shutdown, native crash) must surface as an error here,
            # never as a consumer blocked forever (srclint
            # unbounded_blocking — the PR 9 serving-hardening sweep)
            while True:
                try:
                    kind, payload = meta_q.get(timeout=1.0)
                    break
                except queue.Empty:
                    if not t.is_alive():
                        # the producer can enqueue its final item and
                        # exit between our timeout and this liveness
                        # check — drain once before declaring it dead
                        try:
                            kind, payload = meta_q.get_nowait()
                            break
                        except queue.Empty:
                            raise RuntimeError(
                                "staging producer thread died without "
                                "enqueuing a sentinel or error"
                            ) from None
            if kind is SENTINEL:
                break
            if kind is ERROR:
                raise payload
            if kind == "bypass":
                yield payload
                continue
            treedef, metas = payload
            slot, addr, size = ring.consume()
            leaves = []
            full = ring.view(addr, size)
            for dtype, shape, offset, nbytes in metas:
                raw = full[offset : offset + nbytes]
                # one copy out of the slot (writable, like every other
                # loader path) so the slot can be recycled immediately
                leaves.append(
                    raw.copy().view(np.dtype(dtype)).reshape(shape)
                )
            ring.release(slot)
            free_slots.release()
            yield jax.tree_util.tree_unflatten(treedef, leaves)
    finally:
        stop.set()
        t.join(timeout=5)  # producer can always observe stop (never blocks
        # in native code), so this join terminates before the ring dies
        ring.close()


def _traced_bytes(batch) -> dict:
    """The ``h2d`` span's ``bytes`` (the summed size of the batch's
    leaves); nothing is computed while tracing is off."""
    if tracing.get() is None:
        return {}
    return {"bytes": sum(int(getattr(leaf, "nbytes", 0))
                         for leaf in jax.tree_util.tree_leaves(batch))}


def device_prefetch(
    iterator,
    *,
    size: int = 2,
    sharding=None,
    to_device: bool = True,
    scan_steps: int = 1,
):
    """Wrap a host-batch iterator with device staging — the pinned-memory +
    async-H2D role of the reference's ``pin_memory=True`` loader thread
    (``README.md:88``; torch's pin thread + ``.to(device)`` at
    ``README.md:57-60``).

    Keeps ``size`` batches in flight: ``jax.device_put`` is async, so the
    next batch's host→HBM DMA overlaps the current step's compute. With
    ``sharding`` (a ``NamedSharding`` over the data axis) the put lands
    each shard directly on its chip — the global-batch feed for the
    data-parallel trainer.

    ``scan_steps=K > 1`` turns the stream into a K-deep device staging
    queue for the fused multi-step driver (docs/PERFORMANCE.md): each
    yielded item stacks K consecutive batches along a new leading axis —
    the layout ``DataParallel.train_steps_batches`` scans over — staged
    with the leading axis unsharded and the per-step batch axis on the
    mesh, while ``size`` chunks stay in flight so the next chunk's h2d
    overlaps the current chunk's K steps. Ownership is donation-safe by
    construction: the host-side stack copies (the source iterator may
    recycle its buffers immediately) and the device chunk is a fresh
    array the trainers never donate. A terminal ``StopIteration`` with a
    non-full staging queue yields one final *partial* chunk (leading
    axis < K — its own compile; feed step counts divisible by K, e.g.
    ``drop_last`` at the chunk level, to avoid it).
    """
    if size < 1:
        raise ValueError("size must be >= 1")
    if scan_steps < 1:
        raise ValueError("scan_steps must be >= 1")
    multi_host = jax.process_count() > 1
    if scan_steps > 1 and sharding is not None:
        from jax.sharding import NamedSharding

        if not isinstance(sharding, NamedSharding):
            raise TypeError(
                "device_prefetch(scan_steps>1) needs a NamedSharding to "
                "derive the K-stacked chunk layout (leading scan axis "
                f"unsharded), got {type(sharding).__name__} — pass the "
                "trainer's batch_sharding"
            )
        # ONE definition of the K-stacked layout rule, shared with
        # DataParallel.scan_batch_sharding — drift here would stage
        # chunks train_steps_batches can't consume without a reshard
        from tpu_syncbn.parallel.layout import SpecLayout
        from tpu_syncbn.parallel.scan_driver import stack_batch_spec

        sharding = SpecLayout.from_mesh(
            sharding.mesh, param_shard_axis=None
        ).sharding(stack_batch_spec(sharding.spec))

    def put(batch):
        if not to_device:
            return batch
        if sharding is None:
            return jax.tree_util.tree_map(jax.device_put, batch)
        if multi_host:
            # each host feeds its shard of the global batch (the
            # DistributedSampler gave it a disjoint index shard); assemble
            # the logically-global array from per-process local data —
            # jax.device_put can't target non-addressable devices
            return jax.tree_util.tree_map(
                lambda a: jax.make_array_from_process_local_data(sharding, a),
                batch,
            )
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, sharding), batch
        )

    def staged(it):
        """Fetch + stage the next batch (or K-chunk), instrumented
        (obs.stepstats): ``data_wait`` is the blocking wait on the host
        iterator, ``h2d`` the device_put *dispatch* of ``bytes`` bytes
        (the DMA itself is async — overlap is the point, so the span
        measures dispatch, not transfer completion). The terminal
        StopIteration fetch is NOT a wait sample (stepstats.timed_fetch)
        — recording it would add one end-of-epoch outlier per epoch."""
        if scan_steps == 1:
            batch = obs_stepstats.timed_fetch(
                it, "data_wait", "loader.data_wait_s"
            )
            with obs_stepstats.timed_span("h2d", "loader.h2d_s",
                                          **_traced_bytes(batch)):
                return put(batch)
        # K-slot staging buffer, filled incrementally: each batch is
        # copied into its slot AT FETCH TIME, so the chunk owns its
        # bytes from the moment a batch arrives — a source that recycles
        # one backing buffer across batches (the native staging ring's
        # pattern) cannot retroactively mutate staged slots, and the
        # whole chunk costs one host copy, not two
        slots: list | None = None
        treedef = None
        count = 0
        while count < scan_steps:
            try:
                b = obs_stepstats.timed_fetch(
                    it, "data_wait", "loader.data_wait_s"
                )
            except StopIteration:
                if count == 0:
                    raise  # queue empty: the stream really is over
                break  # partial terminal chunk (leading axis < K)
            leaves, treedef = jax.tree_util.tree_flatten(b)
            if slots is None:
                slots = [
                    np.empty((scan_steps,) + np.shape(l),
                             np.asarray(l).dtype)
                    for l in leaves
                ]
            for s, l in zip(slots, leaves):
                if (np.shape(l) != s.shape[1:]
                        or np.asarray(l).dtype != s.dtype):
                    raise ValueError(
                        f"scan_steps={scan_steps} staging needs static "
                        "batch shapes and dtypes, got "
                        f"{np.shape(l)}/{np.asarray(l).dtype} after "
                        f"{s.shape[1:]}/{s.dtype} — use drop_last=True "
                        "(ragged batches would retrigger XLA compilation "
                        "anyway; a dtype drift would be silently cast)"
                    )
                s[count] = l
            count += 1
        stacked = jax.tree_util.tree_unflatten(
            treedef,
            [s if count == scan_steps else s[:count] for s in slots],
        )
        with obs_stepstats.timed_span("h2d", "loader.h2d_s",
                                      **_traced_bytes(stacked)):
            if telemetry.enabled():
                telemetry.set_gauge("loader.stage_depth", count)
            return put(stacked)

    buf: list = []
    it = iter(iterator)
    try:
        while len(buf) < size:
            buf.append(staged(it))
    except StopIteration:
        pass
    while buf:
        yield buf.pop(0)
        try:
            buf.append(staged(it))
        except StopIteration:
            continue
