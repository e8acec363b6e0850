"""Bucketed AOT inference engine: the serving-side execution core.

Training ends with parameters in a *training* layout — replicated pytrees
(or, under ``DataParallel(zero=True)``, dtype-grouped flat vectors sharded
1/world across the data axis) with BN statistics accumulated into
``BatchStat`` buffers. Serving needs the opposite arrangement: params
gathered out of their shards and re-replicated once
(:func:`tpu_syncbn.parallel.zero.unshard_params` — the layout-change
problem of "Memory-efficient array redistribution through portable
collective communication", arxiv 2112.01075, at whole-model granularity),
the model pinned in eval mode so BatchNorm normalizes with running stats
(``nn/normalization.py`` eval fallback: zero collectives — which is what
makes eval embarrassingly parallel over the ``data`` axis), and a small,
*fixed* set of compiled programs so request traffic never waits on XLA.

:class:`InferenceEngine` owns that arrangement:

* **shape buckets** — incoming batches are padded up to the nearest
  configured bucket size, so the compile cache sees a handful of shapes
  no matter what sizes clients send; bucket sizes are normalized up to
  multiples of the mesh world so every program shards evenly over
  ``DATA_AXIS``;
* **AOT compilation** — each bucket's eval program is lowered and
  compiled ahead of its first request (``jit.lower(...).compile()``);
  the compiled executable is what requests run, so the request path
  never traces;
* **size-aware LRU program retention** — compiled programs are cached
  through :func:`tpu_syncbn.parallel.scan_driver.cached_program` into a
  :class:`~tpu_syncbn.parallel.scan_driver.ProgramCache`: at most
  :data:`~tpu_syncbn.parallel.scan_driver.MAX_CACHED_PROGRAMS` live
  (optionally also a byte budget via ``program_cache_bytes``, sized
  from XLA's per-program ``memory_analysis``), least-recently-used
  evicted first — so a client sending pathological shape traffic cannot
  grow device memory without bound, while the hot bucket set stays
  compiled;
* **sharded eval** — the padded global batch is split over the data
  axis (``P('data')`` in / ``P('data')`` out), each replica runs the
  collective-free eval forward on its shard, and results are gathered
  back to host numpy.

The request-coalescing half (queueing, admission policy, backpressure,
drain) lives in :mod:`tpu_syncbn.serve.batcher`.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Sequence

import numpy as np

from tpu_syncbn.runtime.distributed import DATA_AXIS

__all__ = ["InferenceEngine", "VersionSkewError"]


class VersionSkewError(ValueError):
    """A proposed weight swap's parameter tree does not match the
    serving structure (treedef, leaf shapes, or dtypes) — the publisher
    is running a different model schema than this engine. Rejected
    *before* any serving state is touched: the compiled bucket programs
    were lowered against the current structure, so a skewed swap could
    never reuse them."""


def _leading_dim(batch) -> int:
    """The (validated) shared leading-axis length of a batch pytree."""
    import jax

    leaves = jax.tree_util.tree_leaves(batch)
    if not leaves:
        raise ValueError("batch pytree has no array leaves")
    ns = {int(np.shape(l)[0]) if np.ndim(l) else None for l in leaves}
    if len(ns) != 1 or None in ns:
        raise ValueError(
            f"batch leaves disagree on the leading (batch) axis: {ns}"
        )
    return ns.pop()


class InferenceEngine:
    """Throughput-oriented eval executor for a converted model.

    ``model`` is a trained nnx module (typically
    ``convert_sync_batchnorm``-converted, then trained through
    ``DataParallel``); the engine flips it to eval mode — nnx's
    ``model.eval()`` propagates ``use_running_average=True`` through
    every converted submodule (regression-pinned in
    tests/test_nn_modules.py) — splits it once, and re-replicates the
    state onto ``mesh``. Build one from a live trainer with
    :meth:`from_trainer`, which routes ZeRO flat shards through
    ``parallel.zero.unshard_params`` before replicating.

    ``apply_fn(model, batch) -> outputs`` is the eval forward (default:
    ``model(batch)``); every output leaf must carry the batch axis
    leading — outputs are sharded ``P(data)`` and gathered to host.

    ``buckets`` are *global* batch sizes; each is rounded up to a
    multiple of the mesh world (the data axis must divide the padded
    batch). :meth:`predict` pads a request batch up to the smallest
    bucket that fits, runs that bucket's AOT-compiled program, and
    slices the padding back off; batches larger than the biggest bucket
    are chunked through it.

    Telemetry (``TPU_SYNCBN_TELEMETRY``):
    ``serve.infer_s`` per-program-call histogram, ``serve.compiles``
    counter + ``serve.compile_s`` histogram, and a ``serve.infer`` trace
    span per call (docs/OBSERVABILITY.md).
    """

    def __init__(
        self,
        model,
        *,
        mesh=None,
        axis_name: str = DATA_AXIS,
        layout=None,
        apply_fn: Callable[[Any, Any], Any] | None = None,
        buckets: Sequence[int] = (8, 32, 128),
        program_cache_bytes: int | None = None,
        model_label: str | None = None,
    ):
        import jax
        from flax import nnx
        from jax.sharding import PartitionSpec as P

        from tpu_syncbn.parallel.layout import SpecLayout
        from tpu_syncbn.parallel.trainer import _pallas_forces_vma_off
        from tpu_syncbn.runtime import distributed as dist

        if layout is None:
            layout = SpecLayout.from_mesh(
                mesh if mesh is not None else dist.data_parallel_mesh(),
                param_shard_axis=None,
            )
        elif mesh is not None and mesh != layout.mesh:
            raise ValueError(
                "InferenceEngine: both mesh= and layout= given and they "
                "disagree — pass the layout alone (it carries its mesh)"
            )
        self.layout = layout
        self.mesh = layout.mesh
        self.axis_name = (
            layout.batch_entry if layout.batch_entry is not None
            else axis_name
        )
        self.world = int(layout.replica_world)
        self._apply_fn = apply_fn if apply_fn is not None else (
            lambda m, b: m(b)
        )
        if not buckets:
            raise ValueError("need at least one bucket size")
        norm = sorted({
            int(b) + (-int(b)) % self.world for b in buckets if int(b) >= 1
        })
        if not norm:
            raise ValueError(f"no usable bucket sizes in {buckets!r}")
        #: normalized global bucket sizes (ascending, multiples of world)
        self.buckets: tuple[int, ...] = tuple(norm)

        # eval mode ONCE, at the seam where training state becomes
        # serving state: BN on running stats, dropout-style flags off.
        # The module itself is NOT retained — only the split graphdef +
        # device-put state, so the host-side param tree can be freed.
        model.eval()
        self.graphdef, params, rest = nnx.split(model, nnx.Param, ...)
        self._replicated = layout.replicated
        self.batch_sharding = layout.batch_sharding
        # restore/reshard once: whatever layout the state arrived in
        # (host pytree from unshard_params, trainer-replicated arrays),
        # serving storage is owned by THIS mesh. Under a param-sharding
        # layout (fsdp-composed trainers) the params are stored as flat
        # 1/shard_world dtype-group shards — the eval program gathers
        # them on the wire, so no device ever holds a replicated copy
        # (the max_replicated_bytes the sharding goldens pin shrinks
        # accordingly). Otherwise params replicate as before.
        self._shard_axis = layout.param_shard_axis
        self._shard_world = int(layout.shard_world)
        if self._shard_axis is not None:
            from tpu_syncbn.parallel.zero import FlatLayout

            self._flat = FlatLayout(params, self._shard_world)
            self._store_sharding = layout.sharding(P(self._shard_axis))
            # full-tree structure template: swap_params validates
            # incoming trees against the model, not the flat store
            self._param_template_specs = self._struct_specs(params)
            params_store = self._own_store(self._flat.flatten(params))
        else:
            self._flat = None
            self._store_sharding = self._replicated
            self._param_template_specs = None
            params_store = self._own_replicated(params)
        # Versioned storage: ONE attribute holds (version, params, rest)
        # so a predict call captures a consistent triple with a single
        # atomic read — in-flight batches finish on the version they
        # started on while a concurrent swap_params() lands the next one
        # (the double-buffer half of serve.publish's zero-downtime swap)
        self._state: tuple[int, Any, Any] = (
            0,
            params_store,
            self._own_replicated(rest),
        )
        self._previous: tuple[int, Any, Any] | None = None
        self._swap_lock = threading.Lock()
        # same interpret-lowering concession as the trainer (see
        # DataParallel.__init__): eval BN on running stats never traces
        # the Pallas train kernels, but track_running_stats=False models
        # eval on the batch-stats path, which can trace them — so the
        # VMA checker follows the trainer's gate
        self._check_vma = not _pallas_forces_vma_off(model)

        from tpu_syncbn.parallel import scan_driver

        # size-aware LRU via scan_driver (ROADMAP 4: smarter than
        # FIFO-4); hit/miss/eviction accounted so the bucket-program
        # cache hit rate is measurable
        self._programs = scan_driver.ProgramCache(
            name="serve", max_bytes=program_cache_bytes
        )
        self._programs_compiled = 0
        #: optional ``model`` label: when set, the engine publishes
        #: labeled twins of its serve.* series alongside the unlabeled
        #: process-wide ones (multi-model tenancy attribution)
        self.model_label = model_label
        self._model_labels = (
            {"model": model_label} if model_label else None
        )

    # -- versioned state ---------------------------------------------------

    @property
    def _params(self):
        return self._state[1]

    @property
    def _rest(self):
        return self._state[2]

    @property
    def version(self) -> int:
        """The weight version new requests run on (0 = as-constructed)."""
        return self._state[0]

    @property
    def previous_version(self) -> int | None:
        """The retained rollback target's version, or None."""
        prev = self._previous
        return prev[0] if prev is not None else None

    def _struct_specs(self, tree):
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(tree)
        # metadata only (shape/dtype attributes) — no host transfer per
        # leaf on the swap path, and no touching possibly-donated data
        return treedef, tuple(
            (tuple(np.shape(l)),
             str(getattr(l, "dtype", None) or np.asarray(l).dtype))
            for l in leaves
        )

    def _own_replicated(self, tree):
        """``device_put`` to the replicated serving layout, COPYING any
        leaf the put would merely alias: a no-op ``device_put`` returns
        the caller's own array object, and a trainer that later donates
        that buffer (``train_step``) would delete the serving state out
        from under in-flight requests. The engine owns every buffer it
        serves."""
        import jax

        def one(leaf):
            arr = jax.device_put(leaf, self._replicated)
            return arr.copy() if arr is leaf else arr

        return jax.tree_util.tree_map(one, tree)

    def _own_store(self, vecs):
        """``device_put`` flat param vectors to the sharded serving
        layout (``P(shard_axis)``), with the same copy-on-alias
        ownership rule as :meth:`_own_replicated`."""
        import jax

        def one(leaf):
            arr = jax.device_put(leaf, self._store_sharding)
            return arr.copy() if arr is leaf else arr

        return {dt: one(v) for dt, v in vecs.items()}

    def param_template(self):
        """The serving parameters as a FULL pytree (the model's
        structure) regardless of storage layout — the checkpoint/
        publication template. Replicated engines return the store
        itself; sharded engines gather the flat shards through host
        memory (publication load is a host path anyway)."""
        if self._flat is None:
            return self._params
        return self._flat.unflatten_host(self._params)

    def params_nbytes(self) -> int:
        """Per-device bytes of the replicated serving state (params +
        rest) — what a swap's transient double-buffer adds on top while
        old and new versions coexist (the ``memwatch`` pre-flight bound
        in :mod:`tpu_syncbn.serve.publish`)."""
        import jax

        def total(tree):
            return sum(
                int(getattr(l, "nbytes", np.asarray(l).nbytes))
                for l in jax.tree_util.tree_leaves(tree)
            )

        pb = total(self._params)
        if self._flat is not None:
            # flat store: each device holds a 1/shard_world slice
            pb //= self._shard_world
        return pb + total(self._rest)

    def swap_params(self, params, rest=None, *, version: int) -> int:
        """Atomically replace the serving weights with ``params`` (and
        ``rest`` — BN running stats etc. — when given), as weight
        version ``version``. Returns the version swapped out.

        The new state must match the current structure exactly (treedef
        + per-leaf shape/dtype) — the AOT bucket programs were lowered
        against that structure and take the state as *runtime
        arguments*, so a matching swap reuses every compiled program
        with zero recompiles, while a mismatch raises
        :class:`VersionSkewError` before anything is touched. The
        outgoing version is retained as the rollback target
        (:meth:`rollback`); in-flight batches that already captured the
        old triple finish on it untouched (the ``_state`` single-read
        contract)."""
        import jax

        with self._swap_lock:
            old = self._state
            # sharded store: validate against the model's FULL tree
            # template (the flat shards are an internal layout), then
            # flatten and re-shard; replicated store compares directly
            expect = (
                self._param_template_specs if self._flat is not None
                else self._struct_specs(old[1])
            )
            if self._struct_specs(params) != expect:
                raise VersionSkewError(
                    "swap_params: new params tree does not match the "
                    "serving structure (treedef/shape/dtype) — "
                    "publisher schema skew; swap rejected"
                )
            if self._flat is not None:
                new_params = self._own_store(self._flat.flatten(params))
            else:
                new_params = self._own_replicated(params)
            if rest is not None:
                if self._struct_specs(rest) != self._struct_specs(old[2]):
                    raise VersionSkewError(
                        "swap_params: new rest state does not match the "
                        "serving structure — swap rejected"
                    )
                new_rest = self._own_replicated(rest)
            else:
                new_rest = old[2]
            self._previous = old
            self._state = (int(version), new_params, new_rest)
            return old[0]

    def rollback(self) -> int:
        """Restore the retained previous version (bit-identical device
        arrays — they were never freed). Returns the version now
        serving; raises ``RuntimeError`` when there is nothing to roll
        back to."""
        with self._swap_lock:
            if self._previous is None:
                raise RuntimeError(
                    "rollback: no previous weight version retained"
                )
            bad = self._state
            self._state = self._previous
            # keep the rolled-back-from state referenced (not serving):
            # a post-mortem may want it, and re-rolling forward is the
            # controller's job, not an implicit ping-pong here
            self._previous = bad
            return self._state[0]

    # -- construction ------------------------------------------------------

    @classmethod
    def from_trainer(cls, trainer, **kwargs) -> "InferenceEngine":
        """Build an engine from a live trainer (``DataParallel``-shaped:
        ``sync_to_model``, ``mesh``, ``axis_name``; for a ``GANTrainer``
        pass one of ``sync_to_models()``'s modules to the constructor
        directly). This is the params-out-of-training-layout path:
        ``sync_to_model`` assembles the full parameter tree — under
        ``zero=True`` that is the ``parallel.zero.unshard_params``
        gather of the flat 1/world shards — and the engine re-replicates
        it for eval. The trainer keeps training; the engine owns copies
        on device.

        On a multi-device mesh this cold-start gather materializes the
        whole model in host memory (the ``max_replicated_bytes`` the
        sharding goldens keep pinned so it cannot silently grow) — use
        it for the FIRST engine build, then roll new versions in through
        the publication path (:mod:`tpu_syncbn.serve.publish`), whose
        on-mesh ``portable_redistribute`` + :meth:`swap_params` never
        leaves the device fabric; a deprecation-style warning below
        points there."""
        from tpu_syncbn.runtime import distributed as dist

        # composed layouts (anything beyond the 1-D data mesh) flow
        # through whole: the engine derives its batch spec from the
        # layout, and a param-sharding (fsdp) layout makes the engine
        # store flat shards instead of a replicated copy — the
        # satellite bugfix that shrinks the pinned max_replicated_bytes
        # for fsdp-composed trainers. Plain 1-D trainers keep the
        # byte-identical legacy replicated path.
        tl = getattr(trainer, "layout", None)
        if ("layout" not in kwargs and "mesh" not in kwargs
                and "axis_name" not in kwargs and tl is not None
                and tuple(tl.mesh.axis_names) != (DATA_AXIS,)):
            kwargs["layout"] = tl
        mesh = kwargs.get("mesh", trainer.mesh)
        if int(mesh.size) > 1:
            dist.get_logger("tpu_syncbn.serve").warning(
                "InferenceEngine.from_trainer on a %d-device mesh "
                "gathers the full parameter tree through host memory — "
                "a cold-start cost. For rolling weight updates use the "
                "zero-downtime publication path instead "
                "(tpu_syncbn.serve.publish.SwapController.swap_from_"
                "trainer: on-mesh redistribution + hot swap, no host "
                "gather, no restart).", int(mesh.size),
            )
        model = trainer.sync_to_model()
        if "layout" not in kwargs:
            kwargs.setdefault("mesh", trainer.mesh)
            kwargs.setdefault(
                "axis_name", getattr(trainer, "axis_name", DATA_AXIS)
            )
        return cls(model, **kwargs)

    # -- buckets / programs ------------------------------------------------

    @property
    def max_bucket(self) -> int:
        return self.buckets[-1]

    def bucket_for(self, n: int) -> int:
        """The smallest configured bucket that fits a global batch of
        ``n`` — the pad target. ``n`` beyond the largest bucket is a
        caller error (:meth:`predict` chunks before asking)."""
        if n < 1:
            raise ValueError(f"batch size must be >= 1, got {n}")
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(
            f"batch of {n} exceeds the largest bucket {self.max_bucket}"
        )

    def _struct_key(self, batch):
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(batch)
        return treedef, tuple(
            (tuple(np.shape(l)[1:]), str(np.asarray(l).dtype)) for l in leaves
        )

    def _sharded_fwd(self):
        """The uncompiled sharded eval function ``(params, rest, batch)
        -> out``: replicated state in, batch split over the data axis
        (the batch's structure flows in through the argument, not the
        program text). This is what the audit layer traces
        (:mod:`tpu_syncbn.audit.jaxpr_audit`) — :meth:`_program` compiles
        exactly this, so the pinned contract is the shipped program."""
        from jax.sharding import PartitionSpec as P

        from tpu_syncbn import compat
        from tpu_syncbn.compat import shard_map
        from tpu_syncbn.parallel import collectives

        flat, shard_axis = self._flat, self._shard_axis

        def fwd(params, rest, b):
            if flat is not None:
                # flat 1/shard_world store: ONE all_gather per dtype
                # group rebuilds the tree inside the program — params
                # cross the wire once per call instead of living
                # replicated on every device
                params = flat.unflatten({
                    dt: collectives.all_gather(v, shard_axis, axis=0,
                                               tiled=True)
                    for dt, v in params.items()
                })
            model = compat.nnx_merge(self.graphdef, params, rest, copy=True)
            model.eval()
            return self._apply_fn(model, b)

        param_spec = (
            {dt: P(shard_axis) for dt in flat.shard_sizes}
            if flat is not None else P()
        )
        return shard_map(
            fwd,
            mesh=self.mesh,
            in_specs=(param_spec, P(), P(self.axis_name)),
            out_specs=P(self.axis_name),
            check_vma=self._check_vma,
        )

    def _bucket_struct(self, bucket: int, treedef, leafspecs):
        """``ShapeDtypeStruct`` pytree for a padded ``bucket``-sized batch
        of this structure, sharded like the real input."""
        import jax

        return jax.tree_util.tree_unflatten(treedef, [
            jax.ShapeDtypeStruct(
                (bucket,) + shape, np.dtype(dtype),
                sharding=self.batch_sharding,
            )
            for shape, dtype in leafspecs
        ])

    @staticmethod
    def _program_nbytes(compiled) -> int | None:
        """Best-effort compiled-program footprint from XLA's
        ``memory_analysis`` (temp + output + code size — the parts that
        scale with the bucket; arguments are the shared replicated
        params). ``None`` on backends that don't report one — the
        cache's entry bound still applies."""
        try:
            mem = compiled.memory_analysis()
        except Exception:
            return None
        if mem is None:
            return None
        total = 0
        for attr in ("temp_size_in_bytes", "output_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(mem, attr, None)
            if isinstance(v, int) and v > 0:
                total += v
        return total or None

    def _program(self, bucket: int, batch):
        """The AOT-compiled eval executable for ``bucket`` and this
        batch's structure (leaf shapes beyond the batch axis + dtypes).
        Cached through ``scan_driver.cached_program`` — size-aware LRU:
        at most ``MAX_CACHED_PROGRAMS`` distinct programs (and, when
        the engine was built with ``program_cache_bytes``, at most that
        many measured bytes) stay live; least-recently-used evicted
        first."""
        import jax

        from tpu_syncbn.obs import telemetry
        from tpu_syncbn.parallel import scan_driver

        treedef, leafspecs = self._struct_key(batch)
        key = (bucket, treedef, leafspecs)

        def build():
            import time

            sharded = self._sharded_fwd()
            sds = self._bucket_struct(bucket, treedef, leafspecs)
            t0 = time.perf_counter()
            with telemetry.timed("serve.compile_s"):
                compiled = jax.jit(sharded).lower(
                    self._params, self._rest, sds
                ).compile()
            telemetry.count("serve.compiles")
            if self._model_labels is not None:
                telemetry.observe("serve.compile_s",
                                  time.perf_counter() - t0,
                                  labels=self._model_labels)
                telemetry.count("serve.compiles",
                                labels=self._model_labels)
            # int bump on the GIL, read only by stats(); _swap_lock
            # guards the version triple, not the program cache
            self._programs_compiled += 1  # audit: ok[unlocked_shared_state]
            return compiled

        return scan_driver.cached_program(
            self._programs, key, build, size_of=self._program_nbytes
        )

    def warm(self, example_batch) -> None:
        """AOT-compile every bucket's program for ``example_batch``'s
        structure (any leading-axis length), off the request path — so
        the first real request of each bucket is an execute, not a
        compile."""
        for b in self.buckets:
            self._program(b, example_batch)

    def stats(self) -> dict:
        """Program-cache accounting for the serve block / monitoring:
        configured buckets, total programs ever compiled, programs
        currently live (FIFO bound), and the cache's lifetime
        hits/misses/evictions (hit rate = hits / (hits + misses))."""
        return {
            "buckets": list(self.buckets),
            "programs_compiled": self._programs_compiled,
            "programs_live": len(self._programs),
            "program_cache": self._programs.stats(),
            "version": self.version,
            "previous_version": self.previous_version,
        }

    def health(self) -> dict:
        """Compact JSON-ready health summary for readiness probes (the
        batcher folds it into its ``/readyz`` detail): bucket coverage
        and program-cache state — a climbing ``compiled`` with a capped
        ``live`` under steady traffic means shape churn is recompiling
        on the request path."""
        return {
            "buckets": list(self.buckets),
            "programs_live": len(self._programs),
            "programs_compiled": self._programs_compiled,
            "version": self.version,
        }

    # -- execution ---------------------------------------------------------

    def _run_one(self, batch, n: int):
        import time

        import jax

        from tpu_syncbn.obs import stepstats as obs_stepstats
        from tpu_syncbn.obs import telemetry

        bucket = self.bucket_for(n)
        pad = bucket - n

        def pad_leaf(l):
            a = np.asarray(l)
            if pad == 0:
                return a
            return np.concatenate(
                [a, np.zeros((pad,) + a.shape[1:], a.dtype)], axis=0
            )

        # ONE atomic read pins this call's weight version: a concurrent
        # swap_params() replaces self._state but cannot touch the triple
        # already captured here — in-flight batches finish on the
        # version they started on (tests/test_publish.py pins this)
        _, params, rest = self._state
        fn = self._program(bucket, batch)
        padded = jax.tree_util.tree_map(pad_leaf, batch)
        # level gauge, not set(): concurrent callers each inc/dec their
        # own contribution atomically (obs.telemetry.Gauge.inc)
        telemetry.inc_gauge("serve.inflight")
        if self._model_labels is not None:
            telemetry.inc_gauge("serve.inflight",
                                labels=self._model_labels)
        t0 = time.perf_counter()
        try:
            with obs_stepstats.timed_span(
                "serve.infer", "serve.infer_s", n=n, bucket=bucket
            ):
                dev = jax.device_put(padded, self.batch_sharding)
                out = fn(params, rest, dev)
                # gather: host numpy, padding sliced back off — the
                # engine's callers (the batcher's response path) want
                # settled bytes
                return jax.tree_util.tree_map(
                    lambda a: np.asarray(a)[:n], out
                )
        finally:
            if self._model_labels is not None:
                telemetry.observe("serve.infer_s",
                                  time.perf_counter() - t0,
                                  labels=self._model_labels)
                telemetry.inc_gauge("serve.inflight", -1,
                                    labels=self._model_labels)
            telemetry.inc_gauge("serve.inflight", -1)

    def predict(self, batch):
        """Run the eval forward on a host batch pytree (leading axis =
        global batch). Pads to the nearest bucket, executes that
        bucket's compiled program sharded over the data axis, returns
        host numpy outputs of the *original* length. Batches beyond the
        largest bucket are chunked through it."""
        import jax

        n = _leading_dim(batch)
        if n <= self.max_bucket:
            return self._run_one(batch, n)
        outs = []
        for off in range(0, n, self.max_bucket):
            take = min(self.max_bucket, n - off)
            part = jax.tree_util.tree_map(
                lambda a: np.asarray(a)[off:off + take], batch
            )
            outs.append(self._run_one(part, take))
        return jax.tree_util.tree_map(
            lambda *ls: np.concatenate(ls, axis=0), *outs
        )

    __call__ = predict
