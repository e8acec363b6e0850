"""Pipeline parallelism: a GPipe-style microbatch schedule over a ``pipe``
mesh axis.

Absent from the reference (SURVEY §2's parallelism inventory — the recipe
is pure DP); implemented as the final member of the beyond-reference set
(sequence, expert, tensor, ZeRO). The TPU-native shape:

* each device owns ONE stage's parameters (sharded ``P("pipe", ...)``
  with a leading stage axis — no device ever holds another stage);
* microbatches stream through the ring: at schedule tick ``t`` device
  ``s`` runs its stage on microbatch ``t - s`` (when in range) and
  passes the activation to its right neighbor with ``ppermute`` — the
  same neighbor cycle as ring attention and ``ring_all_reduce``;
* the schedule is a single ``lax.scan`` of ``M + N - 1`` ticks (compile
  size O(1) in both microbatch count and world size), every device
  executing the identical program each tick — SPMD lockstep, the GPipe
  "fill/drain bubble" appearing as masked ticks rather than idle
  processes.

Exactness: the pipeline output equals running the N stages sequentially
on each microbatch — forward and gradients (autodiff transposes the
``ppermute`` schedule into the reverse-direction backward pipeline
automatically). Pinned in ``tests/test_pipeline_parallel.py``.

Two layers live here (ISSUE 15):

* the forward-only *schedule primitive* (``pipeline_apply`` /
  ``pipeline_parallel``) — the original GPipe fill/drain ring;
* :class:`PipelineTrainer` — real microbatch pipeline *training*,
  driven by the static tick tables of
  :mod:`tpu_syncbn.parallel.pipeline_schedule` (GPipe and 1F1B):
  forward ring + backward ring over the transposed ppermute schedule,
  gradient accumulation, one optimizer update per step, composed with
  the DP axis on a 2-D (data × pipe) mesh and compiled through
  ``scan_driver.build_scan_steps`` so K optimizer steps × M
  microbatches are ONE program — zero per-microbatch host dispatch
  (docs/PERFORMANCE.md "Pipeline schedules").
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_syncbn.compat import axis_size as _compat_axis_size
from tpu_syncbn.parallel import pipeline_schedule
from tpu_syncbn.parallel.collectives import pcast_varying

# canonical home: tpu_syncbn.mesh_axes (srclint hardcoded_mesh_axis)
from tpu_syncbn.mesh_axes import DATA_AXIS, PIPE_AXIS  # noqa: E402

Pytree = Any


def pipeline_apply(
    stage_fn: Callable[[Pytree, jax.Array], jax.Array],
    stage_params: Pytree,
    microbatches: jax.Array,
    axis_name: str = PIPE_AXIS,
) -> jax.Array:
    """Run ``N = axis_size`` stages over ``M`` microbatches, GPipe-style.

    Shard-level function (call inside ``shard_map``):

    Args:
      stage_fn: ``(params_for_my_stage, x) -> y`` — one stage. Every
        stage must map activations of the same shape/dtype (the shape
        that travels the ring); project in/out around the pipeline.
      stage_params: THIS device's stage parameters (under ``shard_map``,
        pass the stacked ``(N, ...)`` tree with ``P(axis, ...)`` specs
        and strip the local leading axis of 1 before calling, or pass
        already-local params — see the wrapper in the tests).
      microbatches: ``(M, mb, ...)`` — identical on every device
        (replicated in-spec); device 0 consumes them in order.

    Returns:
      ``(M, mb, ...)`` outputs. Only stage ``N-1``'s copy is the true
      pipeline output (under shard_map, use an out-spec of
      ``P(axis, ...)`` on a leading stage axis and take the last row —
      the array-level helper below does exactly that).

    SPMD-lockstep cost: every device executes ``stage_fn`` on EVERY
    tick, including its fill/drain ticks — there is no per-device
    control flow in SPMD, so an "idle" tick runs the stage on a
    clipped/garbage input (the zero ring payload, or a re-read feed
    slot) and masks the result. Two consequences, both deliberate:

    * a schedule of ``M + N - 1`` ticks costs ``(M + N - 1) x N`` stage
      executions even though only ``M x N`` are useful — the GPipe
      bubble shows up as wasted compute, not idle devices (the fused
      1F1B trainer in this module reclaims it by packing a forward and
      a backward into each steady-state tick);
    * garbage can NEVER corrupt the result: the banked accumulator only
      accepts ``y`` under ``active & (s == n-1)``, and ``jnp.where`` is
      an elementwise select — a NaN/Inf in the not-taken branch does
      not propagate (pinned by the adversarial NaN-feed test in
      ``tests/test_pipeline_parallel.py``).
    """
    n = _compat_axis_size(axis_name)
    s = lax.axis_index(axis_name)
    m = microbatches.shape[0]
    mb_shape = microbatches.shape[1:]
    right = [(i, (i + 1) % n) for i in range(n)]

    def tick(carry, t):
        acc, inbound = carry
        # device s works on microbatch t - s at tick t
        mb_idx = t - s
        active = (mb_idx >= 0) & (mb_idx < m)
        # stage 0 reads from the feed; others read the neighbor hand-off
        feed = lax.dynamic_index_in_dim(
            microbatches, jnp.clip(mb_idx, 0, m - 1), keepdims=False
        )
        x = jnp.where(s == 0, feed, inbound)
        y = stage_fn(stage_params, x)
        # keep the ring clean: inactive ticks forward zeros
        y = jnp.where(active, y, jnp.zeros_like(y))
        # last stage banks its result; every device runs the same update
        acc = lax.dynamic_update_index_in_dim(
            acc,
            jnp.where(active & (s == n - 1), y, lax.dynamic_index_in_dim(
                acc, jnp.clip(mb_idx, 0, m - 1), keepdims=False
            )),
            jnp.clip(mb_idx, 0, m - 1),
            axis=0,
        )
        outbound = lax.ppermute(y, axis_name, right)
        return (acc, outbound), None

    acc0 = jnp.zeros((m,) + mb_shape, microbatches.dtype)
    inb0 = jnp.zeros(mb_shape, microbatches.dtype)
    (acc, _), _ = lax.scan(
        tick, pcast_varying((acc0, inb0), axis_name), jnp.arange(m + n - 1)
    )
    return acc


def pipeline_parallel(
    stage_fn: Callable[[Pytree, jax.Array], jax.Array],
    mesh,
    axis_name: str = PIPE_AXIS,
):
    """Array-level wrapper: returns ``f(stacked_params, microbatches)``
    where ``stacked_params`` has a leading stage axis on every leaf and
    ``microbatches`` is ``(M, mb, ...)``. The result is
    ``(N, M, mb, ...)`` — every stage's accumulator row, sharded
    ``P(axis)`` on the leading stage axis; row ``N-1`` is the true
    pipeline output (:func:`last_stage_output` slices it).

    The historical extraction was a psum over a one-hot stage mask —
    which replicated the FULL ``(M, mb, ...)`` output on every stage,
    putting its bytes on the wire once per call (and GSPMD lowers an
    in-program "slice row N-1 and replicate" to the very same
    all-reduce). The sharded out-spec moves NOTHING: each stage keeps
    its own row, so the compiled program's only collective is the
    ppermute ring (pinned by the ``pipeline.gpipe`` golden contract and
    the ``contract.pipeline_ring`` invariant). Slice the last row
    OUTSIDE your jit boundary — the bytes then move only when (and
    where) the result is actually consumed."""
    from tpu_syncbn.compat import shard_map

    def shardwise(stacked_local, microbatches):
        params = jax.tree_util.tree_map(lambda x: x[0], stacked_local)
        acc = pipeline_apply(stage_fn, params, microbatches, axis_name)
        return acc[None]  # local stage row; out-spec P(axis) stacks them

    return shard_map(
        shardwise,
        mesh=mesh,
        in_specs=(P(axis_name), P()),  # spec broadcasts over the param tree
        out_specs=P(axis_name),
    )


def last_stage_output(stacked_out: jax.Array) -> jax.Array:
    """The true pipeline output from :func:`pipeline_parallel`'s
    stage-stacked result: row ``N-1``. Call it outside the compiled
    program — inside one, GSPMD must re-replicate the row and the
    one-hot-psum wire cost this layout exists to remove comes back."""
    return stacked_out[-1]


def split_microbatches(batch: Pytree, num_microbatches: int) -> Pytree:
    """Reshape a ``(global_batch, ...)`` pytree into the trainer's
    ``(M, global_batch / M, ...)`` microbatch layout (raises when the
    leading axis does not divide)."""

    def leaf(x):
        b = x.shape[0]
        if b % num_microbatches:
            raise ValueError(
                f"global batch {b} is not divisible by "
                f"num_microbatches={num_microbatches}"
            )
        return x.reshape(
            (num_microbatches, b // num_microbatches) + x.shape[1:]
        )

    return jax.tree_util.tree_map(leaf, batch)


def pipeline_mesh(
    n_stages: int,
    data_axis: str = DATA_AXIS,
    pipe_axis: str = PIPE_AXIS,
) -> Mesh:
    """The 2-D (data x pipe) mesh the trainer composes over: all
    devices reshaped to ``(world // n_stages, n_stages)``, the data
    axis outermost (DP replicas of the whole pipeline, each pipeline a
    contiguous ring of ``n_stages`` devices)."""
    from tpu_syncbn.runtime import distributed as dist

    ndev = len(jax.devices())
    if ndev % n_stages:
        raise ValueError(
            f"{ndev} devices do not split into pipelines of "
            f"{n_stages} stages"
        )
    return dist.make_mesh(
        {data_axis: ndev // n_stages, pipe_axis: n_stages}
    )


class PipelineTrainer:
    """Microbatch pipeline *training* over a 2-D (data x pipe) mesh,
    fused into the scan driver: the whole schedule — forward microbatch
    ring, backward ring over the transposed ``ppermute`` schedule,
    gradient accumulation across microbatches, ONE optimizer update —
    is a single tick-``lax.scan`` inside the step body, and K optimizer
    steps compile into one program through
    ``scan_driver.build_scan_steps`` (``train_steps_batches``). Zero
    per-microbatch host dispatch: the host dispatches once per K steps.

    Model contract (the pipeline shape, not the nnx trainer's):

    * ``stage_fn(stage_params, x) -> y`` — one stage, pure. Every stage
      maps activations of ONE fixed shape/dtype (the payload that
      travels the ring); project in/out around the pipeline.
    * ``loss_fn(y, target) -> scalar`` — the loss head, applied by the
      last stage per microbatch; the reported loss is the mean over the
      M microbatches (pmean'd across data replicas), matching a
      sequential pass over the global batch.
    * ``stacked_params`` — every leaf with a leading ``n_stages`` axis,
      stored sharded ``P(pipe)``: each device owns one stage's slice
      and its optimizer state; there is NO cross-stage parameter
      collective. Gradients pay one ``pmean`` over the data axis (the
      DP all-reduce), activations/cotangents pay exactly two
      ``ppermute``s per tick (forward ring right, backward ring left) —
      pinned by the ``pipeline.train_*`` golden contracts.

    Schedules are static tick tables (``parallel.pipeline_schedule``):
    ``"gpipe"`` fill/drain or ``"1f1b"`` (default — fused steady-state
    ticks, strictly fewer ticks; its O(N) *scheduled* in-flight bound
    is not yet a memory win here: this trainer statically allocates
    full ``(M, mb, ...)`` activation/grad-inbox buffers for EITHER
    schedule, so 1F1B buys wall-clock today and a bounded ring buffer
    is the follow-up that would buy memory). The body
    executes BOTH op slots of every tick on every device (SPMD
    lockstep): inactive slots compute on masked garbage and are
    select-masked before touching the accumulators, so a NaN produced
    from garbage can never corrupt training state
    (tests/test_pipeline_trainer.py's adversarial NaN-feed fixture).
    Backward recomputes the stage forward under ``jax.vjp`` from the
    saved *input* activation (per-stage rematerialization — the memory
    cost is one ``(M, mb, ...)`` activation buffer plus the grad inbox,
    not the autodiff tape of the whole schedule).

    ``divergence_guard="skip_step"`` arms the PR 1 world-consensus
    finiteness gate INSIDE the compiled step: the guard state rides in
    ``opt_state`` (a legal scan carry, exactly the scan-driver
    contract), a non-finite step rolls params/opt back on-device and
    the ``nonfinite`` metric flags the skipped slot.

    Usage::

        params = stack_stage_params(...)          # leading axis N
        tr = PipelineTrainer(stage_fn, loss_fn, params, optax.sgd(1e-2),
                             num_microbatches=8, schedule="1f1b")
        x_mb = split_microbatches(x, 8)           # (M, global_mb, ...)
        t_mb = split_microbatches(t, 8)
        out = tr.train_step((x_mb, t_mb))         # one update
        out = tr.train_steps_batches(chunk)       # K updates, ONE dispatch
    """

    def __init__(
        self,
        stage_fn: Callable[[Pytree, jax.Array], jax.Array],
        loss_fn: Callable[[jax.Array, jax.Array], jax.Array],
        stacked_params: Pytree,
        optimizer,
        *,
        num_microbatches: int,
        schedule="1f1b",
        mesh: Mesh | None = None,
        layout=None,
        data_axis: str = DATA_AXIS,
        pipe_axis: str = PIPE_AXIS,
        divergence_guard: str | None = None,
        donate: bool = True,
    ):
        from tpu_syncbn.parallel import scan_driver
        from tpu_syncbn.parallel.zero import check_elementwise

        if divergence_guard not in (None, "skip_step"):
            raise ValueError(
                "divergence_guard must be None or 'skip_step', got "
                f"{divergence_guard!r}"
            )
        leaves = jax.tree_util.tree_leaves(stacked_params)
        if not leaves:
            raise ValueError("stacked_params has no array leaves")
        stage_dims = {leaf.shape[0] for leaf in leaves}
        if len(stage_dims) != 1:
            raise ValueError(
                "every stacked_params leaf needs the same leading stage "
                f"axis, got leading dims {sorted(stage_dims)}"
            )
        (self.n_stages,) = stage_dims
        self.num_microbatches = int(num_microbatches)
        # named schedules (gpipe/1f1b) can be re-derived at a new M by
        # set_microbatches(); an explicit Schedule instance cannot
        self._schedule_name = schedule if isinstance(schedule, str) else None
        self.schedule = pipeline_schedule.get_schedule(
            schedule, self.num_microbatches, self.n_stages
        )
        if not self.schedule.name.startswith("_"):
            pipeline_schedule.validate_schedule(self.schedule)
        self.stage_fn = stage_fn
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.data_axis = data_axis
        self.pipe_axis = pipe_axis
        from tpu_syncbn.parallel.layout import SpecLayout

        # the one mesh + sharding source (ROADMAP item 1): an explicit
        # SpecLayout, a wrapped legacy mesh, or the default 2-D
        # (data x pipe) pipeline mesh. Stage params shard over the pipe
        # axis by per-leaf staging, not by flat ZeRO shards, so the
        # layout stays param_shard_axis=None here (fsdp×pipe is a named
        # illegal composition — SpecLayout.reject_reasons).
        if layout is None:
            layout = SpecLayout.from_mesh(
                mesh if mesh is not None else pipeline_mesh(
                    self.n_stages, data_axis, pipe_axis
                ),
                param_shard_axis=None,
            )
        elif mesh is not None and mesh != layout.mesh:
            raise ValueError(
                "pass either layout= or mesh=, not both — the layout "
                "owns the mesh"
            )
        if layout.param_shard_axis is not None:
            raise ValueError(
                "; ".join(layout.reject_reasons()) or
                "PipelineTrainer needs a layout without a param shard axis"
            )
        self.layout = layout
        self.mesh = layout.mesh
        for ax in (data_axis, pipe_axis):
            if ax not in self.mesh.shape:
                raise ValueError(
                    f"mesh is missing the {ax!r} axis (has "
                    f"{tuple(self.mesh.shape)})"
                )
        if int(self.mesh.shape[pipe_axis]) != self.n_stages:
            raise ValueError(
                f"mesh {pipe_axis!r} axis has "
                f"{int(self.mesh.shape[pipe_axis])} devices but "
                f"stacked_params has {self.n_stages} stages"
            )
        self.data_world = int(self.mesh.shape[data_axis])

        # per-stage params: each device owns ONE stage's slice (P(pipe)
        # on the leading axis); optimizer state mirrors the layout.
        # Elementwise-only optimizers, same reason as zero=True: each
        # device updates its stage in isolation, so a transform needing
        # a global view across parameters would diverge per-stage.
        check_elementwise(optimizer)
        self._pspec = P(pipe_axis)
        self._param_sharding = self.layout.sharding(self._pspec)
        self._param_store = jax.device_put(
            stacked_params, self._param_sharding
        )
        opt_shapes = jax.eval_shape(optimizer.init, self._param_store)
        self._opt_staged = jax.tree_util.tree_map(
            lambda l: l.ndim > 0 and l.shape[0] == self.n_stages,
            opt_shapes,
        )
        self._opt_spec = jax.tree_util.tree_map(
            lambda staged: P(pipe_axis) if staged else P(),
            self._opt_staged,
        )
        opt_shardings = jax.tree_util.tree_map(
            self.layout.sharding, self._opt_spec,
            is_leaf=lambda x: isinstance(x, P),
        )
        self.opt_state = jax.device_put(
            self.optimizer.init(self._param_store), opt_shardings
        )
        self.divergence_guard = divergence_guard
        if divergence_guard is not None:
            # guard state rides inside opt_state (the scan-driver
            # contract: per-update bookkeeping lives in the carry)
            guard0 = jax.device_put(
                {"nonfinite_count": jnp.zeros((), jnp.int32)},
                self.layout.replicated,
            )
            self.opt_state = (self.opt_state, guard0)
            self._opt_spec = (self._opt_spec, {"nonfinite_count": P()})

        self._donate = donate
        # K -> fused program (size-aware LRU, hit/miss/eviction counted)
        self._train_cache = scan_driver.ProgramCache(name="pipeline")

    # -- sharding helpers -------------------------------------------------

    @property
    def params(self) -> Pytree:
        """The stacked (leading stage axis) parameter tree."""
        return self._param_store

    @property
    def batch_sharding(self) -> NamedSharding:
        """Sharding for one step's ``(M, global_mb, ...)`` microbatch
        pytree: microbatch rows replicated across stages, the per-row
        batch axis sharded over the data axis."""
        return self.layout.sharding(P(None, self.data_axis))

    @property
    def scan_batch_sharding(self) -> NamedSharding:
        """Sharding for a K-stacked chunk ``(K, M, global_mb, ...)`` —
        what :meth:`train_steps_batches` expects."""
        from tpu_syncbn.parallel import scan_driver

        return self.layout.sharding(
            scan_driver.stack_batch_spec(P(None, self.data_axis))
        )

    # -- step body --------------------------------------------------------

    def _make_step_fn(self):
        """The pure per-device step body
        ``(params, opt_state, batch) -> (params, opt_state, loss,
        metrics)`` — a stable-carry ``build_scan_steps`` citizen (same
        in/out trees, shapes, dtypes, and VMA types), so K steps fuse
        into one scanned program exactly like the DP/GAN trainers."""
        stage_fn, loss_fn = self.stage_fn, self.loss_fn
        axis_d, axis_p = self.data_axis, self.pipe_axis
        n, m = self.n_stages, self.num_microbatches
        sched = self.schedule
        guard = self.divergence_guard is not None
        opt_staged = self._opt_staged
        right = [(i, (i + 1) % n) for i in range(n)]
        left = [(i, (i - 1) % n) for i in range(n)]
        idle = pipeline_schedule.IDLE
        # static tick tables + their one-tick-shifted twins: what a
        # neighbor sent LAST tick is what arrives this tick, so the
        # receive index is a table lookup, not a wired payload
        idle_row = np.full((1, n), idle, np.int32)
        fwd_tab = jnp.asarray(sched.fwd)
        bwd_tab = jnp.asarray(sched.bwd)
        fwd_prev = jnp.asarray(np.vstack([idle_row, sched.fwd[:-1]]))
        bwd_prev = jnp.asarray(np.vstack([idle_row, sched.bwd[:-1]]))

        from tpu_syncbn.parallel import collectives

        def varying(tree):
            return pcast_varying(pcast_varying(tree, axis_d), axis_p)

        def row_at(row, s):
            return lax.dynamic_index_in_dim(row, s, keepdims=False)

        def buf_at(buf, j):
            return lax.dynamic_index_in_dim(buf, j, keepdims=False)

        def masked_write(buf, val, j, valid):
            cur = buf_at(buf, j)
            return lax.dynamic_update_index_in_dim(
                buf, jnp.where(valid, val, cur), j, axis=0
            )

        def step(pstack, opt_state, batch):
            x_mb, t_mb = batch
            if x_mb.shape[0] != m:
                raise ValueError(
                    f"batch carries {x_mb.shape[0]} microbatches, trainer "
                    f"was built for num_microbatches={m} (use "
                    "split_microbatches)"
                )
            if guard:
                opt_state, guard_in = opt_state
            params = jax.tree_util.tree_map(lambda p: p[0], pstack)
            opt_local = jax.tree_util.tree_map(
                lambda x, staged: x[0] if staged else x,
                opt_state, opt_staged,
            )
            params_in, opt_in = params, opt_local
            # cast params/feed to device-varying over BOTH axes before
            # the vjp: an unvarying operand meeting varying data gets an
            # implicit pvary whose TRANSPOSE is a psum — grads would
            # come back pre-summed and the explicit pmean below would
            # double-count (the round-1 "8x off" hazard, see
            # DataParallel._microbatch_grads)
            params_c = varying(params)
            x_mb_c, t_mb_c = varying((x_mb, t_mb))

            s = lax.axis_index(axis_p)
            is_last = s == n - 1

            def tick(carry, xs):
                acts, ginbox, gacc, loss_acc, fmsg, bmsg = carry
                row_f, row_b, prow_f, prow_b = xs
                # 1. deliver the ring payloads sent last tick: the
                # sender's slot is static, so the landing microbatch
                # index is a schedule lookup
                fj_in = row_at(prow_f, (s - 1) % n)
                f_land = (s > 0) & (fj_in >= 0)
                acts = masked_write(
                    acts, fmsg, jnp.clip(fj_in, 0, m - 1), f_land
                )
                bj_in = row_at(prow_b, (s + 1) % n)
                b_land = (s < n - 1) & (bj_in >= 0)
                ginbox = masked_write(
                    ginbox, bmsg, jnp.clip(bj_in, 0, m - 1), b_land
                )
                # 2. forward slot (runs on every device every tick —
                # SPMD lockstep; inactive slots compute on garbage and
                # every write below is select-masked)
                fj = row_at(row_f, s)
                af = fj >= 0
                jc = jnp.clip(fj, 0, m - 1)
                x = jnp.where(s == 0, buf_at(x_mb_c, jc), buf_at(acts, jc))
                acts = masked_write(acts, x, jc, af)  # save for backward
                y = stage_fn(params_c, x)
                loss_f = loss_fn(y, buf_at(t_mb_c, jc)).astype(jnp.float32)
                loss_acc = loss_acc + jnp.where(
                    af & is_last, loss_f, jnp.zeros_like(loss_f)
                )
                fout = jnp.where(af & ~is_last, y, jnp.zeros_like(y))
                # 3. backward slot: recompute the stage forward under
                # vjp from the saved input activation; the cotangent is
                # the loss head's gradient on the last stage, the
                # inbound ring payload elsewhere
                bj = row_at(row_b, s)
                ab = bj >= 0
                kc = jnp.clip(bj, 0, m - 1)
                xb = buf_at(acts, kc)
                yb, pull = jax.vjp(stage_fn, params_c, xb)
                gy_loss = jax.grad(
                    lambda yy: loss_fn(yy, buf_at(t_mb_c, kc)).astype(
                        jnp.float32
                    )
                )(yb)
                gy = jnp.where(is_last, gy_loss, buf_at(ginbox, kc))
                gp, gx = pull(gy)
                gacc = jax.tree_util.tree_map(
                    lambda a, g: a + jnp.where(ab, g, jnp.zeros_like(g)),
                    gacc, gp,
                )
                bout = jnp.where(ab & (s > 0), gx, jnp.zeros_like(gx))
                # 4. exactly two collectives per tick: activations ride
                # the ring right, cotangents ride it left
                fmsg = collectives.ppermute(fout, right, axis_p)
                bmsg = collectives.ppermute(bout, left, axis_p)
                return (acts, ginbox, gacc, loss_acc, fmsg, bmsg), None

            zero_msg = jnp.zeros(x_mb.shape[1:], x_mb.dtype)
            carry0 = varying((
                jnp.zeros_like(x_mb),                       # acts
                jnp.zeros_like(x_mb),                       # grad inbox
                jax.tree_util.tree_map(jnp.zeros_like, params_c),
                jnp.zeros((), jnp.float32),                 # loss acc
                zero_msg, zero_msg,
            ))
            (_, _, gacc, loss_acc, _, _), _ = lax.scan(
                tick, carry0, (fwd_tab, bwd_tab, fwd_prev, bwd_prev)
            )

            # loss lives on the last stage only (masked adds): one tiny
            # psum replicates it around the ring, then the DP mean
            loss = collectives.psum(loss_acc, axis_p) / m
            loss = collectives.pmean(loss, axis_d)
            # gradient mean over microbatches, then the DP all-reduce —
            # per-stage, never across stages
            grads = jax.tree_util.tree_map(lambda g: g / m, gacc)
            grads = collectives.pmean(grads, axis_d)

            metrics: dict = {}
            ok = None
            if guard:
                gfin = jnp.bool_(True)
                for leaf in jax.tree_util.tree_leaves(gacc):
                    gfin &= jnp.all(jnp.isfinite(leaf))
                gfin = collectives.pmin(
                    gfin.astype(jnp.int32), (axis_d, axis_p)
                ) > 0
                ok = jnp.isfinite(loss) & gfin

            updates, opt_local = self.optimizer.update(
                grads, opt_local, params
            )
            new_params = jax.tree_util.tree_map(
                lambda p, u: p + u.astype(p.dtype), params, updates
            )

            if guard:
                def sel(new, old):
                    return jax.tree_util.tree_map(
                        lambda nv, ov: jnp.where(ok, nv, ov.astype(nv.dtype)),
                        new, old,
                    )

                new_params = sel(new_params, params_in)
                opt_local = sel(opt_local, opt_in)
                notok_i = 1 - ok.astype(jnp.int32)
                metrics = {"nonfinite": notok_i.astype(jnp.float32)}
                guard_out = {
                    "nonfinite_count":
                        guard_in["nonfinite_count"] + notok_i,
                }

            pstack = jax.tree_util.tree_map(lambda p: p[None], new_params)
            opt_state = jax.tree_util.tree_map(
                lambda x, staged: x[None] if staged else x,
                opt_local, opt_staged,
            )
            if guard:
                opt_state = (opt_state, guard_out)
            return pstack, opt_state, loss, metrics

        return step

    def _build_train_steps(self, n_steps: int, *, stacked: bool):
        from tpu_syncbn.parallel import scan_driver

        return scan_driver.build_scan_steps(
            self._make_step_fn(),
            mesh=self.mesh,
            state_specs=(self._pspec, self._opt_spec),
            batch_specs=(P(None, self.data_axis),),
            out_specs=(P(), P()),
            n_steps=n_steps,
            stacked=stacked,
            check_vma=True,
            donate=self._donate,
        )

    def set_microbatches(self, num_microbatches: int) -> bool:
        """Re-derive the schedule at a new microbatch count M — the
        autopilot's ``microbatch_m`` actuator (docs/PLANNER.md "The M
        actuator"). Only valid for named schedules (``gpipe`` /
        ``1f1b``); an explicit :class:`~tpu_syncbn.parallel.
        pipeline_schedule.Schedule` instance is pinned to its M and
        this returns ``False`` without touching anything. Programs for
        the new M are (re)built lazily by the K->program cache — prior
        Ms stay warm, so flapping between two values does not
        recompile. Callers must feed batches split at the new M."""
        m = int(num_microbatches)
        if self._schedule_name is None:
            return False
        if m == self.num_microbatches:
            return True
        sched = pipeline_schedule.get_schedule(
            self._schedule_name, m, self.n_stages
        )
        if not sched.name.startswith("_"):
            pipeline_schedule.validate_schedule(sched)
        self.num_microbatches = m
        self.schedule = sched
        return True

    def _run(self, key, batch):
        from tpu_syncbn.parallel import scan_driver
        from tpu_syncbn.parallel.trainer import StepOutput

        n_steps, stacked = key
        # M is part of the program identity: set_microbatches() swaps
        # the schedule, and each (K, stacked, M) gets its own fused
        # program in the LRU
        fn = scan_driver.cached_program(
            self._train_cache, key + (self.num_microbatches,),
            lambda: self._build_train_steps(n_steps, stacked=stacked),
        )
        self._param_store, self.opt_state, losses, metrics = fn(
            self._param_store, self.opt_state, batch
        )
        return StepOutput(loss=losses, metrics=metrics)

    # -- public API -------------------------------------------------------

    def train_step(self, batch):
        """One optimizer step over ``batch = (x_mb, t_mb)``, each of
        shape ``(M, global_mb, ...)`` (see :func:`split_microbatches`):
        the full M-microbatch schedule runs inside ONE compiled
        program. Returns :class:`~tpu_syncbn.parallel.trainer.
        StepOutput` with the scalar microbatch-mean loss."""
        out = self._run((1, False), batch)
        squeeze = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)  # noqa: E731
        out.loss = squeeze(out.loss)
        out.metrics = squeeze(out.metrics)
        return out

    def train_steps_batches(self, batches):
        """K optimizer steps — one per leading-axis slice of
        ``batches`` (a ``(K, M, global_mb, ...)`` pytree) — in ONE
        compiled program: ``lax.scan`` over steps around the
        ``lax.scan`` over schedule ticks, a single host dispatch for
        the whole K x M schedule. Returns stacked per-step
        ``loss``/``metrics`` of leading dimension K."""
        from tpu_syncbn.parallel import scan_driver

        k = scan_driver.scan_length(batches)
        return self._run((k, True), batches)
