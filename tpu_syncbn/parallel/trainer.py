"""Data-parallel trainer — the TPU-native replacement for
``DistributedDataParallel`` (reference ``README.md:62-72``; implementation
``[torch] nn/parallel/distributed.py:466-2666``).

DDP's machinery maps onto the compiled step as follows (SURVEY §7):

=====================================================  ======================
DDP mechanism                                          here
=====================================================  ======================
init-time param/buffer broadcast from rank 0           :func:`sync_module_states`
(``_sync_module_states``, ``distributed.py:1066``)     (+ identical-by-
                                                       construction init)
autograd-hook bucketing + overlapped all_reduce        ``lax.pmean`` of grads
(C++ Reducer, ``distributed.py:1437``; 25 MiB           inside the jitted
buckets ``:31``)                                       step; XLA's latency-
                                                       hiding scheduler
                                                       overlaps it with the
                                                       backward automatically
gradient averaging by world size                       ``pmean`` (sum/world)
per-forward buffer broadcast                           rank-0 buffer
(``forward_sync_buffers``, ``:793``)                   broadcast of BatchStats
                                                       inside the step
``no_sync()`` gradient accumulation (``:1659``)        ``accum_steps`` —
                                                       lax.scan microbatches,
                                                       one pmean at the end
``find_unused_parameters`` (``:719``)                  unnecessary: autodiff
                                                       yields zero grads for
                                                       unused params, every
                                                       replica identically
=====================================================  ======================

The key structural difference: DDP is a runtime wrapper issuing collectives
from autograd hooks; here the *compiler* sees the whole step (forward,
backward, stat sync, grad sync, optimizer) as one XLA program and schedules
the collectives over ICI itself — which is what subsumes bucketing/overlap
tuning.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import nnx
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_syncbn import compat
from tpu_syncbn.compat import shard_map
from tpu_syncbn.obs import numerics as obs_numerics, stepstats as obs_stepstats
from tpu_syncbn.obs import tracing as obs_tracing
from tpu_syncbn.parallel import collectives
from tpu_syncbn.parallel.collectives import pcast_varying as _pcast_varying
from tpu_syncbn.runtime import distributed as dist
from tpu_syncbn.runtime.distributed import DATA_AXIS


def sync_module_states(model: nnx.Module, src: int = 0) -> None:
    """Broadcast parameters and buffers from host ``src`` to all hosts —
    DDP's init-time ``_sync_module_states``
    (``[torch] nn/parallel/distributed.py:1066-1072``).

    In single-program SPMD, replicas created from the same PRNG key are
    identical by construction, so this matters only for multi-host jobs
    where hosts may have diverged (e.g. loaded different checkpoints).
    Single-host: no-op.
    """
    if dist.process_count() <= 1:
        return
    from jax.experimental import multihost_utils

    graphdef, state = nnx.split(model)
    state = multihost_utils.broadcast_one_to_all(
        state, is_source=dist.process_index() == src
    )
    nnx.update(model, state)


def _model_traces_pallas_bn(model: nnx.Module) -> bool:
    """Will compiling a step over ``model`` actually trace the Pallas BN
    kernels? True only when the global mode selects Pallas AND the model
    contains a channel-last, ungrouped BatchNorm (the fast-path gate in
    ops/batch_norm.py) — so e.g. group-scoped or channel-first models
    keep the VMA checker even on TPU."""
    from tpu_syncbn.nn.normalization import BatchNorm
    from tpu_syncbn.ops import batch_norm as bn_ops

    if not bn_ops._use_pallas():
        return False
    for _, node in nnx.iter_graph(model):
        if (
            isinstance(node, BatchNorm)
            and node.channel_axis == -1
            and node.group_size is None
            and getattr(node, "stats_compress", "none") == "none"
        ):
            return True
    return False


def _pallas_forces_vma_off(*models: nnx.Module) -> bool:
    """Should the VMA checker be dropped because Pallas BN kernels will
    trace for one of ``models``?

    Scoped to the INTERPRET lowering only: the hlo_interpreter's
    dynamic_slice rejects ``check_vma=True`` around pallas bodies on the
    CPU test mesh (the round-3 observation that motivated the blanket
    concession). The real TPU lowering keeps the checker ON — the very
    checker that caught round 1's 8x-gradient bug — pending the
    ``vma_probe`` battery stage recording a TPU-lowering rejection, which
    would be the evidence to widen this again."""
    from tpu_syncbn.ops import _pallas_common

    if not _pallas_common.interpret():
        return False
    return any(_model_traces_pallas_bn(m) for m in models)


def _rewire_syncbn_axes(model: nnx.Module, axes: tuple) -> None:
    """Point every default-axis SyncBatchNorm at the composed layout's
    stat axes: the paper's contract is that BN statistics sync over ALL
    batch replicas, and a composed layout shards the batch over more
    than one mesh axis — a module still syncing over ``'data'`` alone
    would compute partial statistics. Modules carrying a non-default
    axis are left alone (deliberate sub-world scoping)."""
    from tpu_syncbn.nn.normalization import SyncBatchNorm

    for _, node in nnx.iter_graph(model):
        if isinstance(node, SyncBatchNorm) and node.axis_name == DATA_AXIS:
            if node.group_size is not None:
                raise ValueError(
                    "group-scoped SyncBN cannot ride a composed layout: "
                    "the butterfly group reduction is single-axis "
                    f"(module syncs groups of {node.group_size})"
                )
            node.axis_name = axes


def _stats_replicated_by_construction(model: nnx.Module) -> bool:
    """True when every non-Param Variable in the model is owned by a
    full-world SyncBatchNorm: such stats are computed from psum'd global
    moments, hence bit-identical on every replica — a per-step buffer
    broadcast would be a pure waste of ICI bandwidth.

    Conservative on purpose: the per-step broadcast legalizes ALL of the
    ``rest`` state (anything non-Param), so any leaf whose owner is not a
    full-world SyncBatchNorm — group-scoped SyncBN, plain BN, RNG state,
    custom mutable Variables, stats nested in containers — keeps DDP's
    broadcast-from-replica-0."""
    from tpu_syncbn.nn.normalization import SyncBatchNorm

    modules: dict[tuple, nnx.Module] = {}
    var_paths: list[tuple] = []
    for path, node in nnx.iter_graph(model):
        if isinstance(node, nnx.Module):
            modules[tuple(path)] = node
        elif isinstance(node, nnx.Variable) and not isinstance(node, nnx.Param):
            var_paths.append(tuple(path))
    for vpath in var_paths:
        owner = None
        for k in range(len(vpath), -1, -1):
            if vpath[:k] in modules:
                owner = modules[vpath[:k]]
                break
        if not isinstance(owner, SyncBatchNorm) or owner.group_size is not None:
            return False
    return True




@dataclasses.dataclass
class StepOutput:
    """What a compiled train step returns to the host.

    ``monitors`` carries the on-device health scalars
    (``obs.stepstats``: grad global-norm, non-finite counts, BN
    running-stat health) computed inside the compiled step — they are
    ordinary async step outputs, so reading the struct costs no extra
    device sync until a value is actually fetched."""

    loss: jax.Array
    metrics: dict[str, jax.Array]
    monitors: dict[str, jax.Array] = dataclasses.field(default_factory=dict)


class DataParallel:
    """Compiled data-parallel training for an nnx model over the ``data``
    mesh axis — the reference's step 4
    (``ddp_net = nn.parallel.DistributedDataParallel(net, ...)``,
    ``README.md:67-71``) as a step-factory.

    Usage (the recipe's loop, ``README.md:57-60``)::

        model = convert_sync_batchnorm(Net(rngs))
        dp = DataParallel(model, optax.sgd(1e-2), loss_fn)
        for epoch in range(E):
            sampler.set_epoch(epoch)
            for batch in device_prefetch(iter(loader), sharding=dp.batch_sharding):
                out = dp.train_step(batch)       # loss already pmean'd
        dp.sync_to_model()                        # pull state back into `model`

    ``loss_fn(model, batch)`` returns a scalar local-mean loss or
    ``(loss, metrics_dict)``. Gradients are ``pmean``'d across replicas, so
    with equal shards (``drop_last=True``, ``README.md:90``) the update
    equals single-device large-batch SGD — DDP's contract.

    ``accum_steps > 1`` reproduces DDP's ``no_sync()`` pattern: the local
    batch is split into microbatches scanned sequentially with local grad
    accumulation and ONE cross-replica grad reduction at the end
    (``[torch] nn/parallel/distributed.py:1659``).

    ``broadcast_buffers`` (default ``"auto"``): ``True`` broadcasts
    BatchStat buffers from replica 0 inside every step (DDP's default
    ``forward_sync_buffers``, ``:793``), keeping plain-BN buffers
    replicated exactly as DDP does; ``False`` stores buffers honestly
    per-replica. ``"auto"`` detects the converted-model case — every
    stat-owning module a full-world SyncBatchNorm, whose stats are
    already identical on all replicas by construction — and skips the
    per-step broadcast there (XLA cannot fold a value-dependent no-op
    all-reduce, so on hardware the DDP-parity broadcast is a real
    per-step cost), broadcasting otherwise.
    """

    def __init__(
        self,
        model: nnx.Module,
        optimizer: optax.GradientTransformation,
        loss_fn: Callable[[nnx.Module, Any], Any],
        *,
        mesh: Mesh | None = None,
        axis_name: str = DATA_AXIS,
        layout: Any | None = None,
        broadcast_buffers: bool | str = "auto",
        accum_steps: int = 1,
        donate: bool = True,
        remat: bool = False,
        grad_compression: str | None = None,
        compress: str = "none",
        error_feedback: bool | None = None,
        zero: bool = False,
        divergence_guard: str | None = None,
        monitors: bool | str = True,
    ):
        """``remat=True`` rematerializes the forward during backward
        (``jax.checkpoint``) — trades ~1/3 more FLOPs for activation
        memory, the standard HBM-pressure lever on TPU; step numerics are
        unchanged (tested).

        ``grad_compression="bf16"`` casts gradients to bfloat16 for the
        cross-replica all-reduce and back — DDP's
        ``bf16_compress_hook`` communication hook
        (``[torch] distributed/algorithms/ddp_comm_hooks``), halving the
        gradient traffic over ICI/DCN at a small precision cost. This is
        the legacy stateless hook; prefer ``compress=``.

        ``compress`` (default ``"none"``) opts the gradient all-reduce
        into a compressed wire dtype (docs/PERFORMANCE.md "Compressed
        collectives"): ``"bf16"`` halves, ``"int8"`` quarters the bytes
        on the wire (chunk-quantized shared-range s8 AllReduce —
        ``collectives.compressed_psum``). The step's loss/metric pmean
        rides bf16 under any lossy mode (reporting scalars, not training
        state); the divergence guard's pmin/finiteness collective and
        SyncBN's count census ALWAYS stay exact fp32, and SyncBN moment
        stats compress only via their own explicit opt-in
        (``convert_sync_batchnorm(stats_compress=...)``) — never
        implicitly with the gradients.

        ``error_feedback`` (default: on for ``compress="int8"``, off for
        ``"bf16"``) arms the persistent error-feedback residual: each
        replica reduces ``grads + residual`` and re-captures its own
        quantization error, so compression error is re-sent until it
        lands instead of accumulating across steps. The residual is
        per-replica state riding inside ``opt_state`` (like the
        divergence-guard state), so it persists through checkpoints, is
        rolled back on a guarded non-finite step, and is zeroed by
        ``restore_last_good`` rollbacks (``reset_compression_residual``).
        Memory cost: one f32 copy of the gradients per device.

        ``zero=True`` shards parameters and optimizer state across the
        data axis (ZeRO; beyond reference scope — DDP replicates both,
        ``[torch] nn/parallel/distributed.py:466``). Params live as
        dtype-grouped flat vectors sharded 1/world per device; each step
        all_gathers params once, ``psum_scatter``s the flat gradients
        (same wire cost as DDP's all-reduce, since all-reduce =
        reduce-scatter + all-gather), and the optimizer touches only the
        local shard — Adam's f32 moments never exist in full on any
        device. Numerics are identical to ``zero=False`` for
        *elementwise* optimizer transforms (SGD/momentum/Adam/AdamW,
        schedules, per-leaf clipping); transforms needing a global view
        across parameters (``clip_by_global_norm``) would compute their
        statistic per-shard and are unsupported under ``zero``.

        ``divergence_guard`` (default ``None``) arms the on-device
        non-finite guard (docs/RESILIENCE.md): every step computes a
        world-consensus "loss and all grads finite" flag; a non-finite
        step NEVER reaches the weights — params, optimizer state, and BN
        buffers are rolled back to their pre-step values inside the
        compiled step (an exact skip, not a zero-grad update: Adam
        moments and step counts are untouched). The policy string picks
        what else happens: ``"skip_step"`` nothing; ``"halve_lr"``
        additionally halves a persistent update scale each non-finite
        step (applied multiplicatively to every subsequent update);
        ``"restore_last_good"`` behaves like skip on-device and signals
        the host loop (``runtime.resilience.ResilientLoop``) to reload
        the last verified checkpoint. The step's metrics gain
        ``nonfinite`` (1.0 on a skipped step) and ``lr_scale``; the
        occurrence count persists in the guard state (and therefore in
        checkpoints).

        ``monitors`` (default ``True``) computes on-device health
        scalars inside the compiled step and returns them through
        ``StepOutput.monitors``: grad global-norm and non-finite count
        (``obs.stepstats.grad_monitors``) plus BN running-stat health
        (``state_health``). ``"full"`` adds per-layer BN buffer
        monitors; ``False`` turns the block off (``monitors == {}``).
        They ride the step's existing outputs — no extra per-step
        host→device syncs (under ``zero`` the grad norm needs one
        scalar device-side psum, since grads exist only as shards).

        Monitors include the numerics drift/compression family
        (``obs.numerics``, docs/OBSERVABILITY.md "Numerics & drift"):
        ``bn_mean_skew``/``bn_var_skew``/``bn_skew_layers`` (per-replica
        BN batch moments vs the synced value), ``replica_grad_norm`` /
        ``replica_grad_norm_disp`` (cross-replica grad-norm dispersion)
        and — on the compressed paths — ``clip_fraction`` /
        ``overflow_headroom`` (int8) and ``ef_residual_ratio`` (error
        feedback). The whole family costs exactly ONE extra fused
        scalar psum per compiled program (device↔device, never a host
        sync), a bound the golden program contracts machine-check."""
        if accum_steps < 1:
            raise ValueError("accum_steps must be >= 1")
        if divergence_guard not in (
            None, "skip_step", "halve_lr", "restore_last_good"
        ):
            raise ValueError(
                "divergence_guard must be None, 'skip_step', 'halve_lr', "
                f"or 'restore_last_good', got {divergence_guard!r}"
            )
        if grad_compression not in (None, "bf16"):
            raise ValueError(
                f"grad_compression must be None or 'bf16', got {grad_compression!r}"
            )
        collectives.check_compress_mode(compress)
        if grad_compression is not None and compress != "none":
            raise ValueError(
                "grad_compression (legacy bf16 hook) and compress are "
                "mutually exclusive — use compress='bf16'"
            )
        self.compress = compress
        if error_feedback and compress == "none":
            raise ValueError(
                "error_feedback=True needs a lossy compress mode "
                "('bf16'/'int8') — there is no compression error to "
                "feed back on the exact fp32 wire"
            )
        #: error feedback defaults on only where the quantization error
        #: is large enough to matter (int8's shared-range budget); bf16
        #: rounding is benign and the residual costs params-sized f32
        #: state per device
        self._ef = compress != "none" and (
            error_feedback if error_feedback is not None
            else compress == "int8"
        )
        if broadcast_buffers not in (True, False, "auto"):
            raise ValueError(
                "broadcast_buffers must be True, False, or 'auto', got "
                f"{broadcast_buffers!r}"
            )
        if monitors not in (True, False, "full"):
            raise ValueError(
                f"monitors must be True, False, or 'full', got {monitors!r}"
            )
        self.monitors = monitors
        self.remat = remat
        self.grad_compression = grad_compression
        self._model = model
        from tpu_syncbn.parallel.layout import SpecLayout

        # The SpecLayout owns the mesh and every derived reduce/scatter
        # axis (ROADMAP item 1). The legacy kwargs remain the
        # single-axis surface: no layout → plain DP (or the ZeRO preset
        # when zero=True) on the historical 1-D data mesh, byte-identical
        # programs. A composed layout (SpecLayout.fsdp(...)) shards the
        # batch over P(('data','fsdp')) and the flat param/opt store over
        # the fsdp axis only.
        if layout is None:
            if mesh is not None:
                layout = SpecLayout.from_mesh(
                    mesh, param_shard_axis=axis_name if zero else "auto"
                )
            elif zero:
                layout = SpecLayout.zero()
            else:
                layout = SpecLayout.data_parallel()
        else:
            if mesh is not None and mesh != layout.mesh:
                raise ValueError(
                    "pass either layout= or mesh=, not both — the layout "
                    "owns the mesh"
                )
            if zero and layout.param_shard_axis is None:
                raise ValueError(
                    "zero=True needs a param-sharding layout: use "
                    "SpecLayout.zero() or SpecLayout.fsdp()"
                )
        layout.check(compress=compress)
        if layout.rules:
            if monitors:
                raise ValueError(
                    "tensor-parallel param rules currently require "
                    "monitors=False (the grad monitors assume replicated "
                    "or flat-sharded params)"
                )
            if self._ef:
                raise ValueError(
                    "tensor-parallel param rules do not compose with "
                    "error feedback (the residual store assumes "
                    "replicated param shapes) — pass error_feedback=False"
                )
        self.layout = layout
        self.mesh = layout.mesh
        #: the mesh axis — or tuple of axes under a composed layout —
        #: every batch-scoped reduction (grad reduce, SyncBN stats,
        #: loss/metric pmean, guard consensus) runs over
        self.axis_name = (
            layout.stat_axes if layout.stat_axes is not None else axis_name
        )
        if isinstance(self.axis_name, tuple):
            _rewire_syncbn_axes(model, self.axis_name)
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.accum_steps = accum_steps
        if broadcast_buffers == "auto":
            # replicated storage either way; skip the per-step broadcast
            # when the stats are replicated by construction
            self._per_step_broadcast = not _stats_replicated_by_construction(
                model
            )
            broadcast_buffers = True
        else:
            self._per_step_broadcast = bool(broadcast_buffers)
        self.broadcast_buffers = broadcast_buffers
        # VMA checker on, EXCEPT when the Pallas BN kernels will trace
        # for THIS model *under the interpret lowering* (CPU test mesh),
        # whose dynamic_slice rejects the checker regardless of kernel
        # correctness. On TPU the checker stays on even with Pallas
        # bodies. With the checker off, replication is guaranteed
        # structurally, exactly as in round 1. Snapshotted at
        # construction — set_pallas_mode() must be called before building
        # the trainer (its docstring says so).
        self._check_vma = not _pallas_forces_vma_off(model)

        self.zero = layout.param_shard_axis is not None
        self.graphdef, params, rest = nnx.split(model, nnx.Param, ...)
        self.rest = rest  # BatchStats + any other non-Param state

        self.batch_sharding = layout.batch_sharding
        self._replicated = layout.replicated
        self._per_replica = layout.sharding(P(self.axis_name))
        #: total batch replicas — the gradient-mean divisor; the product
        #: of the batch axes under a composed layout
        self.world = layout.replica_world
        #: flat param/opt shard axis and its size (ZeRO/FSDP): the whole
        #: data axis for the zero preset, the dedicated fsdp axis when
        #: composed
        self._shard_axis = layout.grad_scatter_axis
        self._shard_world = layout.shard_world
        #: batch axes left to psum after the gradient reduce-scatter
        self._cross_axes = layout.grad_cross_axes

        # put state on the mesh once. Params/opt replicated (or flat +
        # 1/world-sharded under zero); buffers replicated when
        # broadcast_buffers keeps them in sync, otherwise stored honestly
        # per-replica ((world, ...) sharded on the data axis) — torch's
        # broadcast_buffers=False keeps local buffers per replica, and
        # declaring divergent buffers "replicated" would let any host
        # read return an arbitrary replica's stats.
        if self.zero:
            from tpu_syncbn.parallel.zero import FlatLayout, check_elementwise

            check_elementwise(optimizer)
            self._layout = FlatLayout(params, self._shard_world)
            self._pspec = {
                dt: P(self._shard_axis) for dt in self._layout.groups
            }
            self._store_sharding = layout.sharding(P(self._shard_axis))
            self._param_store = jax.device_put(
                self._layout.flatten(params), self._store_sharding
            )
            # optimizer state is born sharded: init runs per-shard under
            # shard_map; vector leaves (moments etc., shaped like the
            # shard) shard along the axis, scalar leaves (step counts)
            # replicate.
            shard_tpl = {
                dt: jax.ShapeDtypeStruct((n,), jnp.dtype(dt))
                for dt, n in self._layout.shard_sizes.items()
            }
            opt_shapes = jax.eval_shape(optimizer.init, shard_tpl)
            self._opt_spec = jax.tree_util.tree_map(
                lambda l: P() if l.ndim == 0 else P(self._shard_axis),
                opt_shapes,
            )
            init_sharded = shard_map(
                optimizer.init,
                mesh=self.mesh,
                in_specs=(self._pspec,),
                out_specs=self._opt_spec,
                check_vma=self._check_vma,
            )
            self.opt_state = jax.jit(init_sharded)(self._param_store)
        elif layout.rules:
            # tensor-parallel rules: per-param specs from the layout's
            # wildcard matching; the optimizer state inherits the param
            # shardings through the compiler (elementwise init
            # propagates input shardings; scalar leaves replicate)
            self._pspec = layout.param_specs(params)
            shardings = jax.tree_util.tree_map(
                layout.sharding, self._pspec,
                is_leaf=lambda x: isinstance(x, P),
            )
            self._param_store = jax.device_put(params, shardings)
            self.opt_state = jax.jit(
                optimizer.init, in_shardings=(shardings,)
            )(self._param_store)
            self._opt_spec = jax.tree_util.tree_map(
                lambda a: a.sharding.spec, self.opt_state
            )
        else:
            self._pspec = P()
            self._opt_spec = P()
            self._param_store = jax.device_put(params, self._replicated)
            self.opt_state = jax.device_put(
                optimizer.init(params), self._replicated
            )
        self.divergence_guard = divergence_guard
        if divergence_guard is not None:
            # guard state rides inside opt_state so every existing code
            # path (donation, scan carries, state_dict/load, shard specs)
            # treats it as optimizer state — which semantically it is:
            # per-update bookkeeping that must survive checkpoints
            guard0 = jax.device_put(
                {
                    "lr_scale": jnp.ones((), jnp.float32),
                    "nonfinite_count": jnp.zeros((), jnp.int32),
                },
                self._replicated,
            )
            self.opt_state = (self.opt_state, guard0)
            if self.zero:
                self._opt_spec = (
                    self._opt_spec,
                    {"lr_scale": P(), "nonfinite_count": P()},
                )
            # non-zero mode: _opt_spec is the single prefix spec P(),
            # which covers the (opt_state, guard) tuple unchanged
        if self._ef:
            # error-feedback residual rides OUTSIDE the guard wrap in
            # opt_state: (inner_opt[, guard], residual). Per-replica
            # state (every replica's quantization error differs), stored
            # honestly with a leading world axis sharded on the data
            # axis — the broadcast_buffers=False storage pattern.
            if self.zero:
                res0 = {
                    dt: jnp.zeros(
                        (self.world,
                         n if jnp.issubdtype(jnp.dtype(dt), jnp.floating)
                         else 0),
                        jnp.float32,
                    )
                    for dt, n in self._layout.padded.items()
                }
            else:
                res0 = jax.tree_util.tree_map(
                    lambda z: jnp.zeros((self.world,) + z.shape, z.dtype),
                    collectives.init_error_feedback(params),
                )
            self.opt_state = (
                self.opt_state, jax.device_put(res0, self._per_replica)
            )
            # self.axis_name, not the ctor arg: under a composed layout
            # the per-replica store spans ALL batch axes — a 'data'-only
            # spec would silently share residuals across the fsdp axis
            # (and shrink the stored leading dim, breaking state_dict)
            self._opt_spec = (self._opt_spec, P(self.axis_name))
        if broadcast_buffers:
            self.rest = jax.device_put(self.rest, self._replicated)
        else:
            self.rest = jax.device_put(
                jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(x[None], (self.world,) + x.shape),
                    self.rest,
                ),
                self._per_replica,
            )
        self._rest_spec = P() if broadcast_buffers else P(self.axis_name)

        self._donate = donate
        self._train_step = self._build_train_step(donate)
        # first-dispatch compile latch (obs.profiling): the jit above
        # compiles on its first call, which is a compile seam the
        # recompile-storm detector must see (a hot weight swap that
        # rebuilds the trainer re-pays it)
        self._first_dispatch_noted = False
        # host-side count of step dispatches: the ``step`` of the
        # ``train_step`` span (obs.tracing)
        self._calls = 0
        from tpu_syncbn.parallel import scan_driver

        # n_steps -> scanned jit (FIFO-bounded, hit/miss/eviction counted)
        self._train_steps_cache = scan_driver.ProgramCache(name="train")
        # compress mode -> parked (jit, scan cache, compile latch):
        # set_compress() swaps whole program sets so a mode revisited
        # mid-run reuses its already-compiled executables
        self._mode_programs: dict[str, tuple] = {}
        self._eval_step = self._build_eval_step()

    # -- step builders ----------------------------------------------------

    def _microbatch_grads(self, params, rest, batch):
        """value_and_grad over one microbatch; returns (loss, metrics,
        new_rest, grads, numx) — ``numx`` being the numerics drift
        scalars (BN batch-moment skew vs the synced value) the forward's
        SyncBN reductions recorded under the monitor collector; ``{}``
        with monitors off, so the traced program is unchanged."""
        collect_numerics = bool(self.monitors)

        def lossed(p, r, b):
            # copy=True: fresh trace-local Variables, so BN's BatchStat
            # mutation happens at this trace level (nnx 0.12 merge
            # otherwise aliases the original module's variables)
            model = compat.nnx_merge(self.graphdef, p, r, copy=True)
            model.train()
            # the skew scalars are traced INSIDE the differentiated
            # function, so they must exit through its aux (a module-level
            # side channel would leak VJP-trace tracers)
            with obs_numerics.collect(enabled=collect_numerics) as col:
                out = self.loss_fn(model, b)
            loss, metrics = out if isinstance(out, tuple) else (out, {})
            _, _, new_r = nnx.split(model, nnx.Param, ...)
            return loss, (metrics, new_r, col.summary())

        if self.remat:
            lossed = jax.checkpoint(lossed)
        # Cast replicated params to device-varying OUTSIDE the
        # differentiated function. Under shard_map's VMA type system an
        # *unvarying* param meeting varying data gets an implicit pvary
        # whose transpose is a psum — value_and_grad would then return
        # grads already summed across replicas, and the explicit pmean
        # below would double-count by the world size (the "8x off"
        # discrepancy of round 1). With the cast outside the VJP, grads
        # stay local and the explicit pmean is the one aggregation —
        # DDP's semantics, and check_vma=True validates the whole step.
        # (With the checker off — pallas mode — grads are local anyway.)
        if self._check_vma:
            params = _pcast_varying(params, self.axis_name)
        with jax.named_scope("forward_backward"):
            (loss, (metrics, new_rest, numx)), grads = jax.value_and_grad(
                lossed, has_aux=True
            )(params, rest, batch)
        return loss, metrics, new_rest, grads, numx

    def _gather_params(self, store):
        """ZeRO/FSDP path: rebuild the full param tree from this
        device's flat shards — ONE all_gather per dtype group, over the
        shard axis only (a composed layout's data axis already holds the
        value replicated)."""
        full = {
            dt: collectives.all_gather(v, self._shard_axis, axis=0, tiled=True)
            for dt, v in store.items()
        }
        return self._layout.unflatten(full)

    def _build_train_step(self, donate: bool):
        step = self._make_step_fn()
        sharded = shard_map(
            step,
            mesh=self.mesh,
            in_specs=(self._pspec, self._rest_spec, self._opt_spec,
                      P(self.axis_name)),
            out_specs=(self._pspec, self._rest_spec, self._opt_spec,
                       P(), P(), P()),
            # VMA checker ON (unless pallas traces — see __init__):
            # validates that params/opt_state/loss really are replicated
            # after the step. Requires the explicit varying-cast of params
            # in _microbatch_grads — see the comment there for the
            # round-1 "8x off" root cause.
            check_vma=self._check_vma,
        )
        donate_argnums = (0, 1, 2) if donate else ()
        return jax.jit(sharded, donate_argnums=donate_argnums)

    def _make_step_fn(self):
        """The pure per-device step body (params, rest, opt_state, batch)
        -> (params, rest, opt_state, loss, metrics) — shared by the
        single-step jit and the scanned multi-step jit (``train_steps``);
        its in/out trees keep a stable VMA type, which is what makes it a
        legal ``lax.scan`` carry."""
        axis = self.axis_name

        def step(pstore, rest, opt_state, batch):
            monitors: dict = {}
            guard_in = None
            ef_in = ef_out = None
            if self._ef:
                # residual rides outermost in opt_state; strip the
                # per-replica storage axis of 1 (like honest buffers)
                opt_state, ef_stored = opt_state
                ef_in = jax.tree_util.tree_map(lambda x: x[0], ef_stored)
            if self.divergence_guard is not None:
                opt_state, guard_in = opt_state
            pstore_in, opt_in = pstore, opt_state
            params = self._gather_params(pstore) if self.zero else pstore
            if not self.broadcast_buffers:
                # per-replica storage: strip the local leading axis of 1
                rest = jax.tree_util.tree_map(lambda x: x[0], rest)
            rest_in = rest
            if self.accum_steps == 1:
                loss, metrics, rest, grads, numx = self._microbatch_grads(
                    params, rest, batch
                )
            else:
                # no_sync() pattern: scan microbatches, accumulate local
                # grads, single cross-replica reduction afterwards
                local_bs = jax.tree_util.tree_leaves(batch)[0].shape[0]
                if local_bs % self.accum_steps:
                    raise ValueError(
                        f"per-replica batch size {local_bs} is not divisible "
                        f"by accum_steps={self.accum_steps}"
                    )
                micro = jax.tree_util.tree_map(
                    lambda x: x.reshape(
                        (self.accum_steps, x.shape[0] // self.accum_steps)
                        + x.shape[1:]
                    ),
                    batch,
                )

                # scan carries must keep a stable VMA type: local grads are
                # device-varying, and BN stats flip between unvarying
                # (SyncBN: psum'd) and varying (plain BN). Pin the grad
                # accumulator to varying always; pin the buffer carry to
                # varying only when a post-scan broadcast (or per-replica
                # out-spec) will legalize it — in the skip-broadcast mode
                # the stats stay unvarying through every iteration.
                if self._check_vma:
                    def to_varying(tree):
                        return _pcast_varying(tree, axis)
                else:
                    def to_varying(tree):
                        return tree

                pin_rest = self._per_step_broadcast or not self.broadcast_buffers

                def body(carry, mb):
                    rest, acc = carry
                    loss, metrics, rest, grads, numx = (
                        self._microbatch_grads(params, rest, mb)
                    )
                    acc = jax.tree_util.tree_map(jnp.add, acc, grads)
                    rest = to_varying(rest) if pin_rest else rest
                    return (rest, acc), (loss, metrics, numx)

                zero = to_varying(
                    jax.tree_util.tree_map(jnp.zeros_like, params)
                )
                rest = to_varying(rest) if pin_rest else rest
                (rest, grads), (losses, metricses, numxes) = jax.lax.scan(
                    body, (rest, zero), micro
                )
                grads = jax.tree_util.tree_map(
                    lambda g: g / self.accum_steps, grads
                )
                loss = jnp.mean(losses)
                metrics = jax.tree_util.tree_map(jnp.mean, metricses)
                # worst microbatch wins: skew anywhere in the accum
                # window is drift (same fold as Collector.summary)
                numx = jax.tree_util.tree_map(
                    lambda a: jnp.max(a, axis=0), numxes
                )

            if self.compress != "none":
                # reporting scalars ride the wire in bf16 under any
                # lossy mode — they are telemetry, not training state
                loss = collectives.compressed_pmean(loss, axis, mode="bf16")
                metrics = collectives.compressed_pmean(
                    metrics, axis, mode="bf16"
                )
            else:
                loss = collectives.pmean(loss, axis)
                metrics = collectives.pmean(metrics, axis)

            ok = None
            if guard_in is not None:
                # world-consensus finiteness: the pmean'd loss catches a
                # NaN loss on ANY replica, but grads can blow up (inf in
                # the backward) with a finite loss — and a replica-local
                # verdict would let replicas take different branches and
                # diverge. pmin over the local flags is the consensus.
                gfin = jnp.bool_(True)
                for leaf in jax.tree_util.tree_leaves(grads):
                    gfin &= jnp.all(jnp.isfinite(leaf))
                gfin = collectives.pmin(gfin.astype(jnp.int32), axis) > 0
                ok = jnp.isfinite(loss) & gfin

            if self.zero:
                # average + shard the gradients in ONE collective: a
                # psum_scatter is the reduce-scatter half of the
                # all-reduce DDP would issue, and the optimizer only
                # needs this device's shard
                flat_g = self._layout.flatten(grads)
                new_ef: dict = {}
                if self.monitors:
                    # per-replica grad norm BEFORE the reduce-scatter:
                    # the local half of the dispersion monitor
                    numx["replica_grad_norm"] = (
                        obs_numerics.grad_norm_scalar(flat_g)
                    )
                ccol_ctx = obs_numerics.collect(enabled=bool(self.monitors))

                shard_axis = self._shard_axis
                cross = self._cross_axes

                def scatter(dt, g):
                    floating = jnp.issubdtype(g.dtype, jnp.floating)
                    if self.compress != "none" and floating:
                        # compressed reduce-scatter (one quantization
                        # chunk per scatter shard); with EF the residual
                        # is re-sent with the next step's gradients
                        p = g.astype(jnp.float32)
                        if self._ef:
                            p = p + ef_in[dt]
                        shard, res = collectives.compressed_reduce_scatter(
                            p, shard_axis, mode=self.compress,
                            want_residual=self._ef,
                        )
                        if self._ef:
                            new_ef[dt] = res
                        if cross:
                            # composed layout: finish the reduction over
                            # the remaining batch axes on the 1/F shard
                            # — the wire bytes were already cut by the
                            # scatter, and the compressed wire stays
                            # legal over the cross axes. (EF covers the
                            # scatter stage only; the cross stage's
                            # quantization error is unfed — int8
                            # composed is convergence-tested, not
                            # bit-parity-pinned.)
                            shard = collectives.compressed_psum(
                                shard, cross, mode=self.compress
                            )
                        return (shard / self.world).astype(g.dtype)
                    if self._ef:
                        new_ef[dt] = ef_in[dt]  # exact group: no error
                    if self.grad_compression == "bf16":
                        d = g.dtype
                        g = collectives.reduce_scatter(
                            g.astype(jnp.bfloat16), shard_axis
                        ).astype(d)
                    else:
                        g = collectives.reduce_scatter(g, shard_axis)
                    if cross:
                        # exact completion of the mean over the other
                        # batch axes, on shard-sized operands
                        g = collectives.psum(g, cross)
                    return g / self.world

                with jax.named_scope("grad_allreduce"), ccol_ctx as ccol:
                    # the compressed reduce-scatters record their int8
                    # clip fraction / overflow headroom into the active
                    # collector (parallel.collectives)
                    gshard = {dt: scatter(dt, g) for dt, g in flat_g.items()}
                if self._ef:
                    ef_out = new_ef
                if self.monitors:
                    numx.update(ccol.summary())
                    if self._ef:
                        numx["ef_residual_ratio"] = obs_numerics.residual_ratio(
                            new_ef, numx["replica_grad_norm"]
                        )
                    # shards only: one scalar device-side psum (over the
                    # shard axis — the cross axes already hold the
                    # reduced value replicated) globalizes
                    with jax.named_scope("monitors"):
                        monitors.update(obs_stepstats.grad_monitors(
                            gshard, shard_axis, sharded=True
                        ))
                with jax.named_scope("optimizer"):
                    updates, opt_state = self.optimizer.update(
                        gshard, opt_state, pstore
                    )
                    if (self.divergence_guard == "halve_lr"
                            and guard_in is not None):
                        updates = jax.tree_util.tree_map(
                            lambda u: u * guard_in["lr_scale"], updates
                        )
                    pstore = optax.apply_updates(pstore, updates)
            else:
                if self.monitors:
                    # per-replica grad norm BEFORE the all-reduce: the
                    # local half of the dispersion monitor
                    numx["replica_grad_norm"] = (
                        obs_numerics.grad_norm_scalar(grads)
                    )
                # DDP gradient averaging: one compiler-scheduled
                # all-reduce; the compressed paths record their int8
                # clip fraction / overflow headroom into the collector
                with jax.named_scope("grad_allreduce"), obs_numerics.collect(
                    enabled=bool(self.monitors)
                ) as ccol:
                    if self._ef:
                        grads, ef_out = collectives.ef_compressed_pmean(
                            grads, ef_in, axis, mode=self.compress
                        )
                    elif self.compress != "none":
                        grads = collectives.compressed_pmean(
                            grads, axis, mode=self.compress
                        )
                    elif self.grad_compression == "bf16":
                        # bf16_compress_hook parity: halve the wire traffic
                        dtypes = jax.tree_util.tree_map(
                            lambda g: g.dtype, grads
                        )
                        grads = jax.tree_util.tree_map(
                            lambda g: g.astype(jnp.bfloat16), grads
                        )
                        grads = collectives.pmean(grads, axis)
                        grads = jax.tree_util.tree_map(
                            lambda g, d: g.astype(d), grads, dtypes
                        )
                    else:
                        grads = collectives.pmean(grads, axis)
                if self.monitors:
                    numx.update(ccol.summary())
                    if self._ef:
                        numx["ef_residual_ratio"] = (
                            obs_numerics.residual_ratio(
                                ef_out, numx["replica_grad_norm"]
                            )
                        )
                    # post-pmean grads are replicated: pure arithmetic,
                    # no collective needed
                    with jax.named_scope("monitors"):
                        monitors.update(obs_stepstats.grad_monitors(grads))
                with jax.named_scope("optimizer"):
                    updates, opt_state = self.optimizer.update(
                        grads, opt_state, params
                    )
                    if (self.divergence_guard == "halve_lr"
                            and guard_in is not None):
                        updates = jax.tree_util.tree_map(
                            lambda u: u * guard_in["lr_scale"], updates
                        )
                    pstore = optax.apply_updates(params, updates)

            if self.monitors and numx:
                # numerics drift/compression monitors (obs.numerics): the
                # per-replica local scalars — BN batch-moment skew, local
                # grad norm, int8 clip/headroom, EF residual ratio — fused
                # into ONE scalar psum. That single collective is the
                # monitors' whole wire cost, pinned by the golden program
                # contracts and tests/test_numerics.py's one-psum gate.
                with jax.named_scope("monitors"):
                    monitors.update(obs_numerics.cross_replica_monitors(
                        numx, axis, disp_keys=("replica_grad_norm",),
                        varying_cast=self._check_vma,
                    ))

            if guard_in is not None:
                # exact skip of a non-finite step: params, optimizer
                # state, and BN buffers all roll back to their pre-step
                # values — jnp.where never propagates the not-taken
                # branch's NaNs
                def sel(new, old):
                    return jax.tree_util.tree_map(
                        lambda n, o: jnp.where(ok, n, o.astype(n.dtype)),
                        new, old,
                    )

                pstore = sel(pstore, pstore_in)
                opt_state = sel(opt_state, opt_in)
                rest = sel(rest, rest_in)
                if ef_out is not None:
                    # a skipped step must not consume the residual: the
                    # gradients it absorbed never reached the weights
                    ef_out = sel(ef_out, ef_in)
                notok_i = 1 - ok.astype(jnp.int32)
                lr_scale = guard_in["lr_scale"]
                if self.divergence_guard == "halve_lr":
                    lr_scale = jnp.where(ok, lr_scale, lr_scale * 0.5)
                guard_out = {
                    "lr_scale": lr_scale,
                    "nonfinite_count":
                        guard_in["nonfinite_count"] + notok_i,
                }
                metrics = {
                    **metrics,
                    "nonfinite": notok_i.astype(jnp.float32),
                    "lr_scale": guard_in["lr_scale"],
                }
                opt_state = (opt_state, guard_out)

            if self.broadcast_buffers:
                if self._per_step_broadcast:
                    # per-step buffer broadcast (DDP forward_sync_buffers
                    # :793)
                    rest = collectives.broadcast(rest, src=0, axis_name=axis)
                # else: full-world SyncBN stats are replicated by
                # construction (psum'd moments) — already unvarying, and
                # an explicit broadcast would be a wasted all-reduce
                if self.monitors:
                    # post-broadcast (or by-construction-replicated)
                    # buffers: pure arithmetic yields replicated monitors
                    with jax.named_scope("monitors"):
                        monitors.update(obs_stepstats.state_health(
                            rest, per_layer=self.monitors == "full"
                        ))
            else:
                if self.monitors:
                    # per-replica buffers: reduce to the worst replica so
                    # the monitors stay legal replicated outputs
                    with jax.named_scope("monitors"):
                        monitors.update(obs_stepstats.state_health(
                            rest, axis, reduce=True,
                            per_layer=self.monitors == "full",
                        ))
                # re-stack for honest per-replica storage (P(axis) output:
                # declare varying even when SyncBN stats are replicated)
                if self._check_vma:
                    rest = _pcast_varying(rest, axis)
                rest = jax.tree_util.tree_map(lambda x: x[None], rest)
            if self._ef:
                # re-stack the per-replica residual (honest P(data)
                # storage, stable scan carry) and re-wrap outermost
                if self._check_vma:
                    ef_out = _pcast_varying(ef_out, axis)
                ef_out = jax.tree_util.tree_map(lambda x: x[None], ef_out)
                opt_state = (opt_state, ef_out)
            return pstore, rest, opt_state, loss, metrics, monitors

        return step

    def _build_train_steps(self, n_steps: int, *, stacked: bool = False):
        """``n_steps`` optimizer steps in ONE compiled program:
        ``lax.scan`` of the step body (``parallel.scan_driver`` is the
        shared builder — GANTrainer compiles through the same one).

        The idiomatic TPU training-loop shape (the step loop lives
        on-device; the chip never waits on the host between steps).
        An equivalence-proven alternative to the host loop, not a known
        speedup (not measured on the chip; JAX's async dispatch already
        keeps two steps in flight): it matters where dispatch IS the
        bottleneck (many tiny steps, slow hosts, multi-process
        contention). The step body's
        stable VMA-typed in/out trees (see ``_make_step_fn``) are what
        make it a legal scan carry."""
        from tpu_syncbn.parallel import scan_driver

        return scan_driver.build_scan_steps(
            self._make_step_fn(),
            mesh=self.mesh,
            state_specs=(self._pspec, self._rest_spec, self._opt_spec),
            batch_specs=(P(self.axis_name),),
            out_specs=(P(), P(), P()),
            n_steps=n_steps,
            stacked=stacked,
            check_vma=self._check_vma,
            donate=self._donate,
        )

    def _run_scanned(self, key, batch) -> StepOutput:
        from tpu_syncbn.parallel import scan_driver

        n_steps, stacked = key
        fn = scan_driver.cached_program(
            self._train_steps_cache,
            # repeat-mode keys stay plain ints (the historical cache
            # shape); stacked programs key on the pair
            n_steps if not stacked else key,
            lambda: self._build_train_steps(n_steps, stacked=stacked),
        )
        self._calls += 1
        with obs_tracing.span("train_step", step=self._calls,
                              n_steps=n_steps):
            (
                self._param_store,
                self.rest,
                self.opt_state,
                losses,
                metrics,
                monitors,
            ) = fn(self._param_store, self.rest, self.opt_state, batch)
        return StepOutput(loss=losses, metrics=metrics, monitors=monitors)

    def train_steps(self, batch, n_steps: int) -> StepOutput:
        """Run ``n_steps`` optimizer steps on the SAME global batch in
        one compiled program (on-device ``lax.scan`` — no per-step host
        dispatch). Returns per-step stacked ``loss``/``metrics`` of
        leading dimension ``n_steps``.

        For distinct data per step use :meth:`train_steps_batches` with
        a staged chunk (``data.device_prefetch(scan_steps=K)``), or the
        ordinary ``train_step`` host loop; this entry point is for
        dispatch-free inner loops on one batch and honest
        device-throughput measurement.

        Each distinct ``n_steps`` compiles (and caches) its own XLA
        program — call it with a FIXED n; the cache holds the most
        recent few and evicts beyond that, so a varying n pays a fresh
        compile every call."""
        if n_steps < 1:
            raise ValueError(f"n_steps must be >= 1, got {n_steps}")
        return self._run_scanned((n_steps, False), batch)

    @property
    def scan_batch_sharding(self):
        """Sharding for a K-stacked batch (leading scan axis unsharded,
        per-step batch axis over the mesh) — what
        :meth:`train_steps_batches` expects and
        ``data.device_prefetch(scan_steps=K, sharding=dp.batch_sharding)``
        produces."""
        from tpu_syncbn.parallel import scan_driver

        return self.layout.sharding(
            scan_driver.stack_batch_spec(P(self.axis_name))
        )

    def train_steps_batches(self, batches) -> StepOutput:
        """Run one optimizer step per leading-axis slice of ``batches``
        — a pytree stacked to ``(K, global_batch, ...)``, e.g. one
        staged chunk from ``data.device_prefetch(scan_steps=K)`` — in
        ONE compiled program (``lax.scan``; one host dispatch per K
        steps, docs/PERFORMANCE.md). Returns stacked per-step
        ``loss``/``metrics``/``monitors`` of leading dimension K.

        Exactly K sequential ``train_step`` calls on the K slices:
        params, optimizer state, BN buffers, the divergence guard's
        rollbacks, and the monitors all match the step-by-step loop
        (tests/test_scan_driver.py pins this across DataParallel, ZeRO
        mode, and GANTrainer). The chunk itself is never donated — the
        staging queue may still own its buffer."""
        from tpu_syncbn.parallel import scan_driver

        k = scan_driver.scan_length(batches)
        if k < 1:
            raise ValueError(f"stacked batch needs a leading axis >= 1, got {k}")
        return self._run_scanned((k, True), batches)

    def _build_eval_step(self):
        def step(pstore, rest, batch):
            params = self._gather_params(pstore) if self.zero else pstore
            if not self.broadcast_buffers:
                rest = jax.tree_util.tree_map(lambda x: x[0], rest)
            model = compat.nnx_merge(self.graphdef, params, rest, copy=True)
            model.eval()
            out = self.loss_fn(model, batch)
            loss, metrics = out if isinstance(out, tuple) else (out, {})
            loss = collectives.pmean(loss, self.axis_name)
            metrics = collectives.pmean(metrics, self.axis_name)
            return loss, metrics

        sharded = shard_map(
            step,
            mesh=self.mesh,
            in_specs=(self._pspec, self._rest_spec, P(self.axis_name)),
            out_specs=(P(), P()),
            check_vma=self._check_vma,
        )
        return jax.jit(sharded)

    # -- public API -------------------------------------------------------

    @property
    def params(self):
        """The parameter pytree. Under ``zero`` the canonical storage is
        flat + sharded; reading this property assembles the full tree on
        the host (cheap relative to a checkpoint write, the main reader).
        Assigning accepts a param tree in either mode."""
        if self.zero:
            from tpu_syncbn.parallel.zero import unshard_params

            return unshard_params(self._layout, self._param_store)
        return self._param_store

    @params.setter
    def params(self, tree):
        if self.zero:
            self._param_store = jax.device_put(
                self._layout.flatten(tree), self._store_sharding
            )
        else:
            self._param_store = jax.device_put(tree, self._replicated)

    def reset_compression_residual(self) -> bool:
        """Zero the error-feedback residual (no-op without one; returns
        whether there was state to reset). Called by
        ``ResilientLoop._restore_last_good``: after a divergence
        rollback the restored checkpoint's residual encodes compression
        error of a gradient trajectory that has been UNWOUND — re-sending
        it would inject stale updates into the recovered run. Ordinary
        resume keeps the checkpointed residual (it belongs to the
        trajectory being continued)."""
        if not self._ef:
            return False
        inner, ef = self.opt_state
        zero = jax.tree_util.tree_map(jnp.zeros_like, ef)
        self.opt_state = (inner, jax.device_put(zero, self._per_replica))
        return True

    @property
    def program_caches(self) -> tuple:
        """Every scan :class:`~tpu_syncbn.parallel.scan_driver.ProgramCache`
        this trainer owns — the live mode's first, then any parked by
        :meth:`set_compress`. The autopilot's cache-budget actuator
        adjusts ``max_bytes`` on all of them so a parked mode cannot
        hold memory the pressure signal asked back."""
        parked = [
            cache for (_step, cache, _noted) in self._mode_programs.values()
            if cache is not self._train_steps_cache
        ]
        return (self._train_steps_cache, *parked)

    def set_compress(self, mode: str) -> bool:
        """Switch the collective compression wire format at a step
        boundary; returns whether anything changed. The autopilot's
        compression actuator — but equally a manual knob.

        The optimizer-state *structure* is pinned at construction:
        ``self._ef`` (whether an error-feedback residual rides in
        ``opt_state``) never changes here, so checkpoints, fused-scan
        carries, and donation all see one stable pytree across mode
        switches. Under exact modes the residual passes through
        untouched (:func:`collectives.ef_compressed_pmean` with
        ``mode="none"`` degrades to the exact pmean) — construct the
        trainer at the lossiest rung you intend to select (e.g.
        ``compress="int8"``) so the residual exists on every rung.

        Each mode's programs (the per-step jit and the fused-scan
        cache) are parked on switch-away and recalled on switch-back:
        a mode revisited recompiles nothing, which is what keeps the
        recompile-storm detector quiet while the autopilot moves
        between golden-pinned variants. The residual *content* is
        wire-format-specific (int8 quantization error replayed onto a
        bf16 wire is just noise), so it is zeroed at every switch."""
        collectives.check_compress_mode(mode)
        if self.grad_compression is not None:
            raise ValueError(
                "set_compress does not apply to the legacy "
                "grad_compression hook — construct with compress= instead"
            )
        if mode == self.compress:
            return False
        self._mode_programs[self.compress] = (
            self._train_step,
            self._train_steps_cache,
            self._first_dispatch_noted,
        )
        self.compress = mode
        parked = self._mode_programs.get(mode)
        if parked is not None:
            (
                self._train_step,
                self._train_steps_cache,
                self._first_dispatch_noted,
            ) = parked
        else:
            from tpu_syncbn.parallel import scan_driver

            self._train_step = self._build_train_step(self._donate)
            self._train_steps_cache = scan_driver.ProgramCache(name="train")
            self._first_dispatch_noted = False
        self.reset_compression_residual()
        return True

    def train_step(self, batch) -> StepOutput:
        """One optimizer step on a *global* batch (sharded or shardable
        along axis 0 across the mesh)."""
        t0 = time.perf_counter() if not self._first_dispatch_noted else None
        self._calls += 1
        # the enqueue: wall time against cpu_us says whether the host
        # thread worked or waited (obs.tracing; off unless tracing is on)
        with obs_tracing.span("train_step", step=self._calls):
            (
                self._param_store,
                self.rest,
                self.opt_state,
                loss,
                metrics,
                monitors,
            ) = self._train_step(
                self._param_store, self.rest, self.opt_state, batch)
        if t0 is not None:
            # first dispatch = XLA compile (+ one execution, async on
            # real hardware): one compile.train event, time tagged
            self._first_dispatch_noted = True
            from tpu_syncbn.obs import profiling

            profiling.note_compile("train", time.perf_counter() - t0)
        return StepOutput(loss=loss, metrics=metrics, monitors=monitors)

    def eval_step(self, batch) -> StepOutput:
        loss, metrics = self._eval_step(self._param_store, self.rest, batch)
        return StepOutput(loss=loss, metrics=metrics)

    def lowered_train_step(self, batch):
        """AOT-lower the train step for the current state and ``batch``
        without executing it — e.g. ``.cost_analysis()['flops']`` for MFU
        reporting, or ``.as_text()`` for HLO inspection. Keeps the
        (params, rest, opt_state, batch) calling convention private."""
        return self._train_step.lower(
            self._param_store, self.rest, self.opt_state, batch
        )

    def sync_to_model(self) -> nnx.Module:
        """Write the trained state back into the wrapped nnx model (the
        object the user built and may want to eval/save directly) and
        return it. With per-replica buffers (broadcast_buffers=False),
        replica 0's buffers win — matching torch's rank-0 checkpoint
        convention."""
        rest = self.rest
        if not self.broadcast_buffers:
            rest = jax.tree_util.tree_map(lambda x: np.asarray(x)[0], rest)
        nnx.update(self._model, self.params, rest)
        return self._model

    @property
    def model(self) -> nnx.Module:
        return self.sync_to_model()

    # -- checkpointing ----------------------------------------------------

    def state_dict(self) -> dict:
        """Full training state as a pytree (params, buffers, optimizer) —
        feed to utils.checkpoint.save_checkpoint on the master host.

        Returns *copies*: with ``donate=True`` (the default) the live
        buffers are invalidated by the next train_step, so a snapshot that
        merely referenced them would be unreadable afterwards. (Under
        ``zero`` the params property already assembles fresh host arrays
        — copying those again would double the full-model allocation.)"""
        params = self.params
        if not self.zero:
            params = jax.tree_util.tree_map(jnp.copy, params)
        return {
            "params": params,
            "rest": jax.tree_util.tree_map(jnp.copy, self.rest),
            "opt_state": jax.tree_util.tree_map(jnp.copy, self.opt_state),
        }

    def load_state_dict(self, state: dict) -> None:
        """Restore a pytree produced by :meth:`state_dict` (or deserialized
        into its structure), re-placing it on the mesh. The checkpoint
        format is mode-independent for params (always the full tree);
        opt_state is NOT — under ``zero`` its flat vectors carry the
        world-size-dependent padded layout, so resume into a trainer
        built with the same ``zero`` flag AND world size (checked)."""
        want_def = jax.tree_util.tree_structure(self.opt_state)
        got_def = jax.tree_util.tree_structure(state["opt_state"])
        if want_def != got_def:
            raise ValueError(
                "opt_state structure mismatch: this checkpoint was saved "
                "by a trainer with a different optimizer or a different "
                f"`zero` setting than this one (zero={self.zero}). Rebuild "
                "the trainer with the same optimizer and zero flag to "
                "resume the optimizer state."
            )
        if self.zero:
            want = jax.tree_util.tree_map(lambda l: l.shape, self.opt_state)
            got = jax.tree_util.tree_map(
                lambda l: jnp.shape(l), state["opt_state"]
            )
            if want != got:
                raise ValueError(
                    "zero=True opt_state layout mismatch: this checkpoint "
                    "was saved with a different world size (flat shard "
                    "padding is world-dependent). Resume on the same "
                    f"shard world ({self._shard_world}) or retrain the "
                    "optimizer state."
                )
        self.params = state["params"]  # setter re-shards per mode
        rest_sharding = (
            self._replicated if self.broadcast_buffers else self._per_replica
        )
        self.rest = jax.device_put(state["rest"], rest_sharding)
        if self.zero:
            shardings = jax.tree_util.tree_map(
                self.layout.sharding, self._opt_spec,
                is_leaf=lambda x: isinstance(x, P),
            )
            self.opt_state = jax.device_put(state["opt_state"], shardings)
        elif self._ef:
            # the residual is per-replica state: re-place it sharded on
            # the data axis, everything inside it replicated
            inner, ef = state["opt_state"]
            self.opt_state = (
                jax.device_put(inner, self._replicated),
                jax.device_put(ef, self._per_replica),
            )
        else:
            self.opt_state = jax.device_put(
                state["opt_state"], self._replicated
            )


def resume_latest(trainer, directory: str) -> int:
    """Restore ``trainer`` from the newest *verified* checkpoint in
    ``directory`` (manifest-certified; corrupt/truncated candidates are
    skipped by ``utils.checkpoint.load_checkpoint``'s fallback chain).
    Returns the restored step, or 0 when the directory holds no
    checkpoints at all — the "first boot or resume, caller doesn't care
    which" orchestration a preemptible job wants::

        dp = DataParallel(model, opt, loss_fn)
        start = resume_latest(dp, ckpt_dir)   # 0 on first boot
        for step in range(start, total_steps): ...

    Works with any trainer exposing ``state_dict``/``load_state_dict``
    (``DataParallel``, ``GANTrainer``). A directory where every candidate
    fails verification raises ``CheckpointCorruptError`` — that is an
    operator problem, not a fresh start."""
    from tpu_syncbn.utils import checkpoint as ckpt

    try:
        state, step = ckpt.load_checkpoint(directory, trainer.state_dict())
    except FileNotFoundError:
        return 0
    trainer.load_state_dict(state)
    dist.get_logger("tpu_syncbn.resilience").info(
        "resumed from verified checkpoint step %d in %s", step, directory
    )
    return step
