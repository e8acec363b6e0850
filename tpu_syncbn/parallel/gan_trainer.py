"""Data-parallel GAN trainer: alternating D/G optimization with SyncBN in
both networks — the reference's GAN capability case (``README.md:3``;
BASELINE.json config 5), where tiny per-chip batches make per-replica BN
statistics destabilize training.

Faithful to the torch DCGAN training loop's stat semantics (SURVEY §7
"GAN case" — ordering running-stat updates across the alternating steps):

* D step: ``fake = G(z)`` runs G **in train mode** (G's BN stats update,
  as in torch where ``netG(noise)`` is a train-mode forward), fake is
  detached for D's gradients; D sees real and fake as *separate* forwards,
  so D's BN stats update twice (torch's two ``netD(...)`` calls).
* G step: ``D(G(z))`` updates both G's and D's stats once more.

Both steps run inside ONE compiled function per iteration; gradients are
pmean'd per network (DDP parity), BatchStats broadcast from replica 0.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import optax
from flax import nnx
from jax.sharding import Mesh, PartitionSpec as P

from tpu_syncbn import compat
from tpu_syncbn.compat import shard_map

from tpu_syncbn.models.gan import bce_gan_losses, hinge_gan_losses
from tpu_syncbn.obs import flightrec, numerics as obs_numerics
from tpu_syncbn.parallel import collectives
from tpu_syncbn.parallel.collectives import pcast_varying as _pcast_varying
from tpu_syncbn.runtime import distributed as dist
from tpu_syncbn.runtime.distributed import DATA_AXIS

LOSSES: dict[str, Callable] = {"bce": bce_gan_losses, "hinge": hinge_gan_losses}


@dataclasses.dataclass
class GANStepOutput:
    d_loss: jax.Array
    g_loss: jax.Array
    metrics: dict[str, jax.Array]
    #: on-device health scalars (obs.stepstats) riding the step outputs —
    #: per-network grad norms / non-finite counts + BN stat health
    monitors: dict[str, jax.Array] = dataclasses.field(default_factory=dict)


class GANTrainer:
    """Two-network, two-optimizer DP trainer.

    ``train_step(real, z_d, z_g)`` takes the real global batch and two
    latent global batches (one per sub-step, matching the torch loop which
    draws fresh noise for the G step) and performs one D update then one G
    update.
    """

    def __init__(
        self,
        generator: nnx.Module,
        discriminator: nnx.Module,
        g_optimizer: optax.GradientTransformation,
        d_optimizer: optax.GradientTransformation,
        *,
        loss: str = "bce",
        mesh: Mesh | None = None,
        axis_name: str = DATA_AXIS,
        layout=None,
        donate: bool = True,
        monitors: bool | str = True,
        compress: str = "none",
    ):
        """``monitors`` (default True): compute per-network grad
        norms/non-finite counts and BN running-stat health inside the
        compiled step, returned via ``GANStepOutput.monitors`` — same
        contract (including ``"full"`` per-layer keys and the
        no-extra-host-sync guarantee) as ``DataParallel(monitors=...)``.

        ``compress`` (default ``"none"``): wire dtype of BOTH networks'
        gradient all-reduce (docs/PERFORMANCE.md "Compressed
        collectives"). Stateless here — error feedback is a
        ``DataParallel`` feature (the GAN step's 6-way replicated state
        layout has no per-replica slot; int8 without EF is a larger
        per-step perturbation, so prefer ``"bf16"`` for GANs). Losses,
        D/G probability metrics, and BN-stat buffer broadcasts stay
        exact."""
        if loss not in LOSSES:
            raise ValueError(f"loss must be one of {sorted(LOSSES)}, got {loss!r}")
        collectives.check_compress_mode(compress)
        self.compress = compress
        if monitors not in (True, False, "full"):
            raise ValueError(
                f"monitors must be True, False, or 'full', got {monitors!r}"
            )
        self._generator = generator
        self._discriminator = discriminator
        self.monitors = monitors
        self.loss_pair = LOSSES[loss]
        from tpu_syncbn.parallel.layout import SpecLayout

        # consume a SpecLayout (ROADMAP item 1); the legacy mesh/axis
        # kwargs wrap into the equivalent replicated-param layout. GAN
        # state is replicated (no ZeRO slot — see `compress` above), so
        # only the batch axes compose here.
        if layout is None:
            if mesh is not None:
                layout = SpecLayout.from_mesh(mesh, param_shard_axis=None)
            else:
                layout = SpecLayout.data_parallel()
        elif mesh is not None and mesh != layout.mesh:
            raise ValueError(
                "pass either layout= or mesh=, not both — the layout owns "
                "the mesh"
            )
        if layout.param_shard_axis is not None:
            raise ValueError(
                "GANTrainer keeps params replicated — use a layout "
                "without a param shard axis"
            )
        layout.check(compress=compress)
        self.layout = layout
        self.mesh = layout.mesh
        self.axis_name = (
            layout.stat_axes if layout.stat_axes is not None else axis_name
        )
        if isinstance(self.axis_name, tuple):
            from tpu_syncbn.parallel.trainer import _rewire_syncbn_axes

            _rewire_syncbn_axes(generator, self.axis_name)
            _rewire_syncbn_axes(discriminator, self.axis_name)
        self.g_opt = g_optimizer
        self.d_opt = d_optimizer

        from tpu_syncbn.parallel.trainer import _pallas_forces_vma_off

        # same contract as DataParallel: checker on unless pallas traces
        # for either network under the interpret lowering (snapshotted at
        # construction)
        self._check_vma = not _pallas_forces_vma_off(
            generator, discriminator
        )

        self.g_def, g_params, g_rest = nnx.split(generator, nnx.Param, ...)
        self.d_def, d_params, d_rest = nnx.split(discriminator, nnx.Param, ...)
        self.g_opt_state = g_optimizer.init(g_params)
        self.d_opt_state = d_optimizer.init(d_params)

        replicated = layout.replicated
        self.batch_sharding = layout.batch_sharding
        put = lambda t: jax.device_put(t, replicated)
        self.g_params, self.g_rest = put(g_params), put(g_rest)
        self.d_params, self.d_rest = put(d_params), put(d_rest)
        self.g_opt_state = put(self.g_opt_state)
        self.d_opt_state = put(self.d_opt_state)

        #: host-side iteration counter feeding the flight-recorder step
        #: ring (one D+G update per count) — GAN incidents carry a step
        #: history exactly like DataParallel/ResilientLoop runs
        self.step_count = 0
        self._donate = bool(donate)
        self._step = self._build_step(donate)
        # first-dispatch compile latch (obs.profiling — the
        # DataParallel.train_step precedent)
        self._first_dispatch_noted = False
        from tpu_syncbn.parallel import scan_driver

        # n_steps -> scanned jit (FIFO-bounded, hit/miss/eviction counted)
        self._train_steps_cache = scan_driver.ProgramCache(name="gan")

    def _make_step_fn(self):
        """The pure per-device step body
        ``(gp, gr, dp, dr, og, od, real, z_d, z_g) -> (state..., d_loss,
        g_loss, metrics, monitors)`` — shared by the single-step jit and
        the scanned multi-step jit (``train_steps``). Its state in/out
        trees keep a stable VMA type (params/opt replicated in and out,
        buffers broadcast from replica 0), which is what makes it a
        legal ``lax.scan`` carry (``parallel.scan_driver``)."""
        axis = self.axis_name
        g_def, d_def = self.g_def, self.d_def
        loss_pair = self.loss_pair
        mon = bool(self.monitors)

        def grad_mean(grads):
            # the compressed paths record int8 clip fraction / overflow
            # headroom into the active numerics collector
            with obs_numerics.collect(enabled=mon) as col:
                if self.compress != "none":
                    reduced = collectives.compressed_pmean(
                        grads, axis, mode=self.compress
                    )
                else:
                    reduced = collectives.pmean(grads, axis)
            return reduced, col.summary()

        def step(gp, gr, dp_, dr, og, od, real, z_d, z_g):
            numx: dict = {}

            # ---- D step ------------------------------------------------
            def d_loss_fn(dp_in, gr_in, dr_in):
                # the SyncBN forwards record batch-moment skew into the
                # collector; it must live INSIDE the differentiated
                # function and exit via aux (trainer.py has the VJP
                # tracer-leak rationale)
                with obs_numerics.collect(enabled=mon) as col:
                    G = compat.nnx_merge(g_def, gp, gr_in, copy=True)
                    G.train()
                    fake = G(z_d)  # train-mode forward: G stats update
                    _, _, gr_out = nnx.split(G, nnx.Param, ...)
                    D = compat.nnx_merge(d_def, dp_in, dr_in, copy=True)
                    D.train()
                    real_logits = D(real)
                    fake_logits = D(jax.lax.stop_gradient(fake))
                    _, _, dr_out = nnx.split(D, nnx.Param, ...)
                    d_loss, _ = loss_pair(real_logits, fake_logits)
                aux = (gr_out, dr_out, real_logits, fake_logits,
                       col.summary())
                return d_loss, aux

            # varying-cast OUTSIDE the VJP so grads stay local and the
            # explicit pmean is the one aggregation (see trainer.py's
            # _microbatch_grads for the VMA transpose root cause)
            dp_in = _pcast_varying(dp_, axis) if self._check_vma else dp_
            (d_loss, (gr, dr, real_logits, fake_logits, d_numx)), d_grads = (
                jax.value_and_grad(d_loss_fn, has_aux=True)(dp_in, gr, dr)
            )
            if mon:
                numx["d_replica_grad_norm"] = (
                    obs_numerics.grad_norm_scalar(d_grads)
                )
            d_grads, d_cnumx = grad_mean(d_grads)
            d_updates, od = self.d_opt.update(d_grads, od, dp_)
            dp_ = optax.apply_updates(dp_, d_updates)

            # ---- G step ------------------------------------------------
            def g_loss_fn(gp_in, gr_in, dr_in):
                with obs_numerics.collect(enabled=mon) as col:
                    G = compat.nnx_merge(g_def, gp_in, gr_in, copy=True)
                    G.train()
                    fake = G(z_g)
                    _, _, gr_out = nnx.split(G, nnx.Param, ...)
                    D = compat.nnx_merge(d_def, dp_, dr_in, copy=True)
                    D.train()
                    fake_logits = D(fake)
                    _, _, dr_out = nnx.split(D, nnx.Param, ...)
                    _, g_loss = loss_pair(
                        jnp.zeros_like(fake_logits), fake_logits
                    )
                return g_loss, (gr_out, dr_out, col.summary())

            gp_in = _pcast_varying(gp, axis) if self._check_vma else gp
            (g_loss, (gr, dr, g_numx)), g_grads = jax.value_and_grad(
                g_loss_fn, has_aux=True
            )(gp_in, gr, dr)
            if mon:
                numx["g_replica_grad_norm"] = (
                    obs_numerics.grad_norm_scalar(g_grads)
                )
            g_grads, g_cnumx = grad_mean(g_grads)
            g_updates, og = self.g_opt.update(g_grads, og, gp)
            gp = optax.apply_updates(gp, g_updates)

            d_loss = collectives.pmean(d_loss, axis)
            g_loss = collectives.pmean(g_loss, axis)
            metrics = collectives.pmean(
                {
                    "d_real": jax.nn.sigmoid(real_logits).mean(),
                    "d_fake": jax.nn.sigmoid(fake_logits).mean(),
                },
                axis,
            )
            # replica-0 buffer broadcast (DDP forward_sync_buffers parity)
            gr = collectives.broadcast(gr, src=0, axis_name=axis)
            dr = collectives.broadcast(dr, src=0, axis_name=axis)
            monitors = {}
            if self.monitors:
                from tpu_syncbn.obs import stepstats as obs_stepstats

                # post-pmean grads are replicated; post-broadcast buffers
                # too — pure arithmetic, no extra collectives
                monitors.update({
                    f"d_{k}": v for k, v in
                    obs_stepstats.grad_monitors(d_grads).items()
                })
                monitors.update({
                    f"g_{k}": v for k, v in
                    obs_stepstats.grad_monitors(g_grads).items()
                })
                monitors.update(obs_stepstats.state_health(
                    (gr, dr), per_layer=self.monitors == "full"
                ))
                # numerics drift/compression family (obs.numerics): BN
                # batch-moment skew from both sub-steps (worst wins),
                # per-network grad-norm dispersion, int8 clip/headroom —
                # fused into ONE scalar psum, the family's whole wire
                # cost (pinned by the gan.train_step golden contract)
                numx.update(obs_numerics.merge_max(
                    d_numx, g_numx, d_cnumx, g_cnumx
                ))
                monitors.update(obs_numerics.cross_replica_monitors(
                    numx, axis,
                    disp_keys=("d_replica_grad_norm",
                               "g_replica_grad_norm"),
                    varying_cast=self._check_vma,
                ))
            return gp, gr, dp_, dr, og, od, d_loss, g_loss, metrics, monitors

        return step

    def _build_step(self, donate: bool):
        sharded = shard_map(
            self._make_step_fn(),
            mesh=self.mesh,
            in_specs=(P(), P(), P(), P(), P(), P(),
                      P(self.axis_name), P(self.axis_name), P(self.axis_name)),
            out_specs=(P(),) * 6 + (P(), P(), P(), P()),
            check_vma=self._check_vma,
        )
        donate_argnums = tuple(range(6)) if donate else ()
        return jax.jit(sharded, donate_argnums=donate_argnums)

    def train_steps(self, real, z_d, z_g) -> GANStepOutput:
        """K fused iterations (one D update + one G update each) in ONE
        compiled program: every input carries a leading K axis — one
        slice per iteration (``real`` a staged chunk from
        ``data.device_prefetch(scan_steps=K)``, the latents stacked the
        same way). Returns stacked per-iteration
        ``d_loss``/``g_loss``/``metrics``/``monitors`` of leading
        dimension K. One host dispatch per K iterations; exactly K
        sequential ``train_step`` calls in params, buffers, optimizer
        state, and monitors (tests/test_scan_driver.py).

        Each distinct K compiles (and caches) its own XLA program —
        feed a FIXED chunk size (``parallel.scan_driver`` bounds the
        retained programs FIFO)."""
        from tpu_syncbn.parallel import scan_driver

        k = scan_driver.scan_length(real)
        fn = scan_driver.cached_program(
            self._train_steps_cache, k,
            lambda: scan_driver.build_scan_steps(
                self._make_step_fn(),
                mesh=self.mesh,
                state_specs=(P(),) * 6,
                batch_specs=(P(self.axis_name),) * 3,
                out_specs=(P(), P(), P(), P()),
                n_steps=k,
                stacked=True,
                check_vma=self._check_vma,
                donate=self._donate,
            ),
        )
        (
            self.g_params, self.g_rest, self.d_params, self.d_rest,
            self.g_opt_state, self.d_opt_state, d_loss, g_loss, metrics,
            monitors,
        ) = fn(
            self.g_params, self.g_rest, self.d_params, self.d_rest,
            self.g_opt_state, self.d_opt_state, real, z_d, z_g,
        )
        self.step_count += k
        if flightrec.get() is not None:
            # chunk-final slice: lazy device-side indexing, no host sync
            # (the ring scalarizes at dump time, like every record_step)
            last = lambda a: a[-1]
            flightrec.record_step(
                self.step_count,
                metrics={"d_loss": last(d_loss), "g_loss": last(g_loss),
                         **{k_: last(v) for k_, v in metrics.items()}},
                monitors=jax.tree_util.tree_map(last, monitors),
            )
        return GANStepOutput(d_loss=d_loss, g_loss=g_loss, metrics=metrics,
                             monitors=monitors)

    def train_step(self, real, z_d, z_g) -> GANStepOutput:
        t0 = time.perf_counter() if not self._first_dispatch_noted else None
        (
            self.g_params, self.g_rest, self.d_params, self.d_rest,
            self.g_opt_state, self.d_opt_state, d_loss, g_loss, metrics,
            monitors,
        ) = self._step(
            self.g_params, self.g_rest, self.d_params, self.d_rest,
            self.g_opt_state, self.d_opt_state, real, z_d, z_g,
        )
        if t0 is not None:
            self._first_dispatch_noted = True
            from tpu_syncbn.obs import profiling

            profiling.note_compile("gan", time.perf_counter() - t0)
        self.step_count += 1
        if flightrec.get() is not None:
            # step ring (ISSUE 13 satellite): GAN incidents used to dump
            # an empty step history — record the async device scalars
            # as-is, no host sync (scalarized at dump time)
            flightrec.record_step(
                self.step_count,
                metrics={"d_loss": d_loss, "g_loss": g_loss, **metrics},
                monitors=monitors,
            )
        return GANStepOutput(d_loss=d_loss, g_loss=g_loss, metrics=metrics,
                             monitors=monitors)

    def sync_to_models(self) -> tuple[nnx.Module, nnx.Module]:
        nnx.update(self._generator, self.g_params, self.g_rest)
        nnx.update(self._discriminator, self.d_params, self.d_rest)
        return self._generator, self._discriminator

    def state_dict(self) -> dict:
        # copies: donated buffers are invalidated by the next train_step.
        # step_count rides along (host int, outside the device-copy map)
        # so the flight-recorder step-ring numbering survives a resume —
        # a post-restart incident must not relabel step 10000 as step 1.
        return {
            **jax.tree_util.tree_map(
                jnp.copy,
                {
                    "g_params": self.g_params, "g_rest": self.g_rest,
                    "d_params": self.d_params, "d_rest": self.d_rest,
                    "g_opt_state": self.g_opt_state,
                    "d_opt_state": self.d_opt_state,
                },
            ),
            "step_count": self.step_count,
        }

    def load_state_dict(self, state: dict) -> None:
        put = lambda t: jax.device_put(t, self.layout.replicated)
        self.g_params, self.g_rest = put(state["g_params"]), put(state["g_rest"])
        self.d_params, self.d_rest = put(state["d_params"]), put(state["d_rest"])
        self.g_opt_state = put(state["g_opt_state"])
        self.d_opt_state = put(state["d_opt_state"])
        # absent in pre-ISSUE-13 checkpoints: resume ring numbering at 0
        self.step_count = int(state.get("step_count", 0))

    def generate(self, z) -> jax.Array:
        """Sample images with the current generator state (eval mode; the
        caller's module mode flags are untouched).

        Runs as a compiled sharded forward over the mesh, so it works on
        multi-host worlds where the replicated params are not fully
        addressable and eager computation would be rejected. ``z`` may be
        host-local (its rows are treated as this host's shard of the
        global latent batch) or an already-global sharded array.
        """
        if getattr(self, "_gen_step", None) is None:
            def gen(gp, gr, zs):
                G = compat.nnx_merge(self.g_def, gp, gr, copy=True)
                G.eval()
                return G(zs)

            self._gen_step = jax.jit(
                shard_map(
                    gen, mesh=self.mesh,
                    in_specs=(P(), P(), P(self.axis_name)),
                    out_specs=P(self.axis_name),
                    check_vma=self._check_vma,
                )
            )
        world = self.layout.replica_world
        n = None
        if not (hasattr(z, "sharding") and getattr(z, "is_fully_addressable", True) is False):
            z = jnp.asarray(z)
            n = z.shape[0]
            pad = (-n) % world  # shard axis must divide the world size
            if pad:
                z = jnp.concatenate([z, jnp.zeros((pad,) + z.shape[1:], z.dtype)])
            if dist.process_count() > 1:
                z = jax.make_array_from_process_local_data(self.batch_sharding, z)
            else:
                z = jax.device_put(z, self.batch_sharding)
        out = self._gen_step(self.g_params, self.g_rest, z)
        return out[:n] if n is not None else out
