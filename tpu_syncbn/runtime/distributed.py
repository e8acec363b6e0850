"""Distributed runtime: initialization, device mesh construction, process
identity, and rank-0 conventions.

This module is the TPU-native replacement for the reference recipe's entire
process/rendezvous stack (reference ``README.md:22-36``):

* ``argparse --local_rank`` (``README.md:11-19``) — not needed. TPU training
  is single-program multi-device: one Python process per *host*, all chips
  driven from it. Process identity comes from the TPU slice metadata via
  :func:`process_index`, not from a launcher-injected CLI argument.
* ``torch.cuda.set_device(local_rank)`` (``README.md:27``) — not needed.
  Each host process owns its local chips implicitly from slice topology.
* ``init_process_group('nccl', init_method='env://', world_size, rank)``
  (``README.md:29-35``) — replaced by :func:`initialize`, which (on
  multi-host) calls ``jax.distributed.initialize`` to join the slice's
  coordination service, then builds a :class:`jax.sharding.Mesh` over all
  chips. Collectives become XLA AllReduce/AllGather HLOs over ICI/DCN
  instead of runtime-issued NCCL calls.
* rank-0 "master" logging convention (``README.md:9``) — :func:`is_master` /
  :func:`master_print`.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import os
import sys
from typing import Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from tpu_syncbn.obs import tracing

_loggers: dict[str, logging.Logger] = {}
_initialized: bool = False
_jax_distributed_active: bool = False

#: Name of the data-parallel mesh axis used throughout the framework. The
#: reference's "process group" of N single-GPU processes (README.md:5)
#: becomes this one named axis spanning every chip in the slice.
#: Canonically defined in :mod:`tpu_syncbn.mesh_axes` (the one module
#: allowed to spell axis names as literals — srclint
#: ``hardcoded_mesh_axis``); re-exported here for the historical import
#: path every trainer uses.
from tpu_syncbn.mesh_axes import DATA_AXIS  # noqa: E402


@dataclasses.dataclass(frozen=True)
class DistributedConfig:
    """Explicit multi-host wiring, mirroring the env contract the reference's
    launcher sets (``MASTER_ADDR/MASTER_PORT/RANK/WORLD_SIZE``; reference
    ``README.md:32-35`` reads them via ``init_method='env://'``).

    All fields default to ``None`` meaning "autodetect from the environment"
    — on a real TPU slice, ``jax.distributed.initialize`` discovers
    everything from slice metadata and none of this is needed.
    """

    coordinator_address: str | None = None  # MASTER_ADDR:MASTER_PORT analogue
    num_processes: int | None = None        # WORLD_SIZE analogue (hosts, not chips)
    process_id: int | None = None           # RANK analogue

    @staticmethod
    def from_env() -> "DistributedConfig":
        """Read the reference-compatible env contract if present.

        Honors both our names (``TPU_SYNCBN_COORDINATOR`` etc.) and the
        reference's torchrun names (``MASTER_ADDR``/``MASTER_PORT``/``RANK``/
        ``WORLD_SIZE``; documented in the reference at ``README.md:32-35``)
        so scripts written against the recipe's env contract keep working.
        """
        addr = os.environ.get("TPU_SYNCBN_COORDINATOR")
        if addr is None and "MASTER_ADDR" in os.environ:
            port = os.environ.get("MASTER_PORT", "12355")
            addr = f"{os.environ['MASTER_ADDR']}:{port}"
        nproc = os.environ.get("TPU_SYNCBN_NUM_PROCESSES", os.environ.get("WORLD_SIZE"))
        pid = os.environ.get("TPU_SYNCBN_PROCESS_ID", os.environ.get("RANK"))
        return DistributedConfig(
            coordinator_address=addr,
            num_processes=int(nproc) if nproc is not None else None,
            process_id=int(pid) if pid is not None else None,
        )


#: Where compiled programs are kept when ``JAX_COMPILATION_CACHE_DIR``
#: does not say: one fixed, git-ignored path at the root of the checkout.
#: The directory is part of what lets a later process find an entry
#: again, so it is never built from the home directory, a temp name, a
#: pid or a time.
_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_persistent_compilation_cache() -> str:
    """Turn on XLA's persistent compilation cache and return its
    directory. :func:`initialize` calls this, so every entry point
    shares one cache: a ResNet-50 train step costs about 50 s to compile
    for a v5e chip, a disk hit a second or two. Entries are keyed by
    HLO + compile options + backend, so reuse is correctness-safe.

    ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside: when it
    is set JAX reads it and no directory is set here.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        # jax's floor (1 s) skips every mid-size program; 0.25 s keeps
        # the sharded step programs and the kernels without persisting
        # thousands of sub-millisecond jits
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.25)
    return path


def initialize(
    config: DistributedConfig | None = None,
    *,
    rendezvous_attempts: int | None = None,
    rendezvous_timeout_s: float | None = None,
    rendezvous_backoff_s: float | None = None,
) -> None:
    """Join the distributed job. One call replaces the reference's step 1+2
    (``--local_rank`` parse, ``cuda.set_device``, ``init_process_group``;
    ``README.md:11-36``).

    Single-host (including the 1-chip and forced-host-device test cases):
    turns on the persistent compilation cache
    (:func:`enable_persistent_compilation_cache`), starts the record of
    the garbage collector's pauses (``obs.tracing.watch_collector``) and
    marks the runtime initialized — JAX already sees all local devices.

    Multi-host: calls ``jax.distributed.initialize``, which performs the
    rendezvous the reference does through ``env://`` + TCPStore
    (``[torch] distributed/distributed_c10d.py:1889``) but against the TPU
    coordination service. On a Cloud TPU slice all arguments are discovered
    from slice metadata and ``config`` may be ``None``.

    The rendezvous is retried with exponential backoff and deterministic
    per-host jitter (docs/RESILIENCE.md): coordinator DNS that isn't up
    yet, a coordinator restarting after preemption, or a slow-starting
    peer should cost a retry, not the job. Knobs (argument > env >
    default): ``rendezvous_attempts`` / ``TPU_SYNCBN_RENDEZVOUS_ATTEMPTS``
    (default 3), ``rendezvous_timeout_s`` /
    ``TPU_SYNCBN_RENDEZVOUS_TIMEOUT_S`` (per-attempt timeout handed to
    ``jax.distributed.initialize`` where supported; jax's default
    otherwise), ``rendezvous_backoff_s`` /
    ``TPU_SYNCBN_RENDEZVOUS_BACKOFF_S`` (base backoff, default 1.0).
    """
    global _initialized, _jax_distributed_active
    if _initialized:
        return
    enable_persistent_compilation_cache()
    tracing.watch_collector()
    if config is None:
        config = DistributedConfig.from_env()

    def _env_num(name, cast, default):
        v = os.environ.get(name)
        return cast(v) if v is not None else default

    attempts = (rendezvous_attempts if rendezvous_attempts is not None
                else _env_num("TPU_SYNCBN_RENDEZVOUS_ATTEMPTS", int, 3))
    timeout_s = (rendezvous_timeout_s if rendezvous_timeout_s is not None
                 else _env_num("TPU_SYNCBN_RENDEZVOUS_TIMEOUT_S", float, None))
    backoff_s = (rendezvous_backoff_s if rendezvous_backoff_s is not None
                 else _env_num("TPU_SYNCBN_RENDEZVOUS_BACKOFF_S", float, 1.0))
    # A coordinator address alone (e.g. a stale MASTER_ADDR export from an
    # old GPU script) must not force the multi-host path: require an actual
    # world size > 1, or TPU slice metadata advertising multiple workers
    # (in which case jax.distributed.initialize autodetects everything).
    explicit_multi = (config.num_processes or 1) > 1 or (
        os.environ.get("TPU_SYNCBN_FORCE_DIST") == "1"
    )
    slice_multi = _tpu_slice_is_multihost()
    if explicit_multi:
        kwargs = dict(
            coordinator_address=config.coordinator_address,
            num_processes=config.num_processes,
            process_id=config.process_id,
        )
    elif slice_multi:
        # Argless: every parameter is discovered from slice metadata — the
        # TPU-native replacement for env:// rendezvous (README.md:32-35).
        kwargs = {}
    else:
        _initialized = True
        return
    # per-host jitter identity: explicit rank when configured; otherwise
    # slice metadata or the hostname (the argless TPU-slice path discovers
    # rank from metadata, so process_id is None on every host — keying off
    # it alone would put all hosts on an identical retry schedule)
    ident = config.process_id
    if ident is None:
        import socket

        ident = os.environ.get("TPU_WORKER_ID") or socket.gethostname()
    _rendezvous_with_retry(
        kwargs, attempts=attempts, timeout_s=timeout_s, backoff_s=backoff_s,
        jitter_key=f"host{ident}",
    )
    _jax_distributed_active = True
    _initialized = True


def _rendezvous_with_retry(
    kwargs: dict,
    *,
    attempts: int,
    timeout_s: float | None,
    backoff_s: float,
    jitter_key: str,
) -> None:
    """``jax.distributed.initialize(**kwargs)`` under bounded exponential
    backoff with deterministic per-host jitter — N restarted hosts must
    not re-storm a recovering coordinator in lockstep. A per-attempt
    ``initialization_timeout`` is forwarded when this jax supports it."""
    import inspect

    from tpu_syncbn.runtime import resilience

    if timeout_s is not None:
        try:
            params = inspect.signature(jax.distributed.initialize).parameters
        except (TypeError, ValueError):  # builtins without signatures
            params = {}
        if "initialization_timeout" in params:
            kwargs = {**kwargs, "initialization_timeout": int(timeout_s)}

    from tpu_syncbn.obs import telemetry

    def attempt():
        # attempt/failure counters ride telemetry so a flaky coordinator
        # is countable from the summary export, not only from the
        # retry log lines (docs/OBSERVABILITY.md)
        telemetry.count("rendezvous.attempts")
        try:
            jax.distributed.initialize(**kwargs)
        except Exception:
            telemetry.count("rendezvous.failures")
            # a half-open coordination client would poison the next try
            with contextlib.suppress(Exception):
                jax.distributed.shutdown()
            raise

    resilience.retry_with_backoff(
        attempt,
        attempts=attempts,
        base_s=backoff_s,
        key=jitter_key,
        describe="distributed rendezvous",
    )


def _tpu_slice_is_multihost() -> bool:
    """True when TPU slice metadata in the environment advertises more than
    one worker host (the case where ``jax.distributed.initialize`` must run
    before any computation)."""
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if "," in hostnames:
        return True
    if os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"):
        return True
    return False


def is_initialized() -> bool:
    """Analogue of ``torch.distributed.is_initialized`` (consulted by the
    reference's SyncBN sync-or-fallback check,
    ``[torch] nn/modules/batchnorm.py:837-860``)."""
    return _initialized


def shutdown() -> None:
    """Tear down the coordination client (tests / clean exit)."""
    global _initialized, _jax_distributed_active
    if _jax_distributed_active:
        jax.distributed.shutdown()
        _jax_distributed_active = False
    _initialized = False
    _loggers.clear()
    _barrier_cache.clear()


def process_index() -> int:
    """This host's index — the analogue of the recipe's ``RANK`` env var
    (``README.md:34``), except it indexes *hosts*, not chips: TPU is one
    process per host, many chips per process."""
    return jax.process_index()


def process_count() -> int:
    """Number of host processes — analogue of ``WORLD_SIZE`` (``README.md:33``)
    at host granularity."""
    return jax.process_count()


def local_device_count() -> int:
    return jax.local_device_count()


def global_device_count() -> int:
    """Total chips in the slice: the true replica count for data parallelism
    (what the reference calls ``world_size`` = ``nproc_per_node`` × nodes,
    ``README.md:96-100``)."""
    return jax.device_count()


def is_master() -> bool:
    """True on the rank-0 host. The reference's convention: "print losses and
    stuff to the console only on the master process" (``README.md:9``)."""
    return jax.process_index() == 0


def master_print(*args, **kwargs) -> None:
    """``print`` gated to the master host (``README.md:9``)."""
    if is_master():
        print(*args, **kwargs)
        sys.stdout.flush()


class _MasterOnlyFilter(logging.Filter):
    """Drops sub-WARNING records on non-master hosts, deciding at *emit*
    time so master-ness is never frozen before ``initialize()`` has run
    (``jax.process_index`` is only consulted once a record is logged)."""

    def filter(self, record: logging.LogRecord) -> bool:
        return record.levelno >= logging.WARNING or is_master()


def get_logger(name: str = "tpu_syncbn") -> logging.Logger:
    """A logger that emits on the master host only and is silenced (WARNING+)
    elsewhere — the structured version of the rank-0 print convention
    (``README.md:9``)."""
    global _loggers
    if name not in _loggers:
        logger = logging.getLogger(name)
        if not logger.handlers:
            # default stream is stdout (the reference's master-print
            # console convention); TPU_SYNCBN_LOG_STREAM=stderr reroutes
            # for callers whose stdout is a parsed result channel
            stream = (
                sys.stderr
                if os.environ.get("TPU_SYNCBN_LOG_STREAM", "").lower()
                == "stderr" else sys.stdout
            )
            handler = logging.StreamHandler(stream)
            handler.setFormatter(
                logging.Formatter(
                    "%(asctime)s [%(levelname)s %(name)s] %(message)s",
                    datefmt="%H:%M:%S",
                )
            )
            logger.addHandler(handler)
        logger.setLevel(logging.INFO)
        if not any(isinstance(f, _MasterOnlyFilter) for f in logger.filters):
            logger.addFilter(_MasterOnlyFilter())
        logger.propagate = False
        _loggers[name] = logger
    return _loggers[name]


def make_mesh(
    axis_sizes: Mapping[str, int] | None = None,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a named device mesh over the slice.

    With ``axis_sizes=None`` (the common case) this returns the pure
    data-parallel mesh: one ``'data'`` axis spanning every chip — the
    TPU-native form of the reference's process group of N single-GPU
    replicas (``README.md:5, 96-100``). Arbitrary extra axes (``'model'``
    etc.) may be requested; a size of ``-1`` on at most one axis means
    "everything left", like a reshape wildcard.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if axis_sizes is None:
        axis_sizes = {DATA_AXIS: n}
    names = tuple(axis_sizes.keys())
    sizes = list(axis_sizes.values())
    if any(s != -1 and s < 1 for s in sizes):
        raise ValueError(f"mesh axis sizes must be positive (or -1): {axis_sizes}")
    wild = [i for i, s in enumerate(sizes) if s == -1]
    if len(wild) > 1:
        raise ValueError("at most one mesh axis may have size -1")
    if wild:
        known = int(np.prod([s for s in sizes if s != -1]))
        if n % known:
            raise ValueError(f"{n} devices not divisible by fixed axes {axis_sizes}")
        sizes[wild[0]] = n // known
    if int(np.prod(sizes)) != n:
        raise ValueError(
            f"mesh axes {dict(zip(names, sizes))} do not cover {n} devices"
        )
    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, names)


def data_parallel_mesh(num_replicas: int | None = None) -> Mesh:
    """The framework's default mesh: ``('data',)`` over all chips (or the
    first ``num_replicas`` chips, for tests that model a smaller world)."""
    devices = jax.devices()
    if num_replicas is not None:
        if num_replicas > len(devices):
            raise ValueError(
                f"requested {num_replicas} replicas but only "
                f"{len(devices)} devices are present"
            )
        devices = devices[:num_replicas]
    return make_mesh({DATA_AXIS: len(devices)}, devices=devices)


_barrier_cache: dict = {}


def barrier(name: str = "barrier") -> None:
    """Block until every replica reaches this point.

    The reference gets barriers implicitly from blocking NCCL collectives.
    Here: multi-host uses the coordination-service barrier
    (``multihost_utils.sync_global_devices``); single-host runs a cached,
    jit-compiled sum over a local-device-sharded array and blocks on it,
    forcing a cross-device AllReduce to complete. The jitted fn and mesh
    are cached so repeated barriers don't retrace.
    """
    if process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)
        return
    key = tuple(jax.local_devices())
    if key not in _barrier_cache:
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = make_mesh({DATA_AXIS: len(key)}, devices=key)
        fn = jax.jit(
            jax.numpy.sum, out_shardings=NamedSharding(mesh, P())
        )
        _barrier_cache[key] = (mesh, fn)
    mesh, fn = _barrier_cache[key]
    from jax.sharding import NamedSharding, PartitionSpec as P

    ones = jax.numpy.ones((len(key),), dtype=jax.numpy.int32)
    sharded = jax.device_put(ones, NamedSharding(mesh, P(DATA_AXIS)))
    fn(sharded).block_until_ready()
