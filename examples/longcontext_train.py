"""Long-context LM training with the sequence sharded across the mesh.

The reference recipe's scope is data parallelism for conv nets
(``README.md:1-104``); long-context sequence parallelism is this
framework's beyond-reference axis (PARITY.md §5.7). This example is the
*training application* (reference layer L5) for that axis: a causal
transformer LM whose sequence dimension is sharded over a ``seq`` mesh
axis, so no device ever holds the full sequence — attention (ring or
Ulysses) is the only cross-shard op, exactly as in the SP literature.

The task is a learnable synthetic one (periodic token sequences: the
next token is determined by position modulo a per-sample period, which
attention can read off from context), so the loss demonstrably falls.

    python examples/longcontext_train.py --simulate 8 --steps 60
    python examples/longcontext_train.py --impl ulysses --local-impl flash
"""

import argparse
import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--simulate", type=int, default=0,
                   help="run on this many virtual CPU host devices (the "
                        "seq-shard count); 0 = the real backend topology")
    p.add_argument("--impl", choices=["ring", "ulysses"], default="ring")
    p.add_argument("--local-impl", choices=["oracle", "flash"],
                   default="oracle",
                   help="Ulysses local attention backend (flash = fused "
                        "Pallas kernel)")
    p.add_argument("--local-backward", choices=["xla", "pallas"],
                   default="xla",
                   help="flash VJP implementation (pallas = fused "
                        "two-kernel backward)")
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--seq-per-device", type=int, default=64)
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--d-ff", type=int, default=128)
    p.add_argument("--n-heads", type=int, default=8)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--vocab", type=int, default=32)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args()


def main():
    args = parse_args()
    if args.simulate:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.simulate}"
        ).strip()
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpu_syncbn import runtime
    from tpu_syncbn.models import transformer as tfm
    from tpu_syncbn.parallel import collectives

    if args.impl != "ulysses" and (args.local_impl == "flash"
                                   or args.local_backward != "xla"):
        raise SystemExit(
            "--local-impl/--local-backward apply to --impl ulysses only "
            "(the library API rejects the combination too)"
        )

    runtime.initialize()
    n = args.simulate or runtime.global_device_count()
    mesh = Mesh(np.array(jax.devices()[:n]), ("seq",))
    L = args.seq_per_device * n  # global sequence length

    if args.n_heads % n:
        raise SystemExit(f"--n-heads {args.n_heads} must divide by {n} "
                         "(Ulysses shards heads; ring is fine either way "
                         "but keep configs comparable)")

    params = tfm.init_transformer_lm(
        jax.random.PRNGKey(args.seed), vocab=args.vocab,
        d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers, d_ff=args.d_ff, max_len=L,
    )
    opt = optax.adam(args.lr)
    opt_state = opt.init(params)

    # periodic sequences: token[t] = (t * stride + phase) % vocab with a
    # per-sample (stride, phase) — the continuation is predictable from
    # any context window, so a causal LM can learn it
    rng = np.random.RandomState(args.seed + 1)

    def sample_batch():
        stride = rng.randint(1, 7, size=(args.batch, 1))
        phase = rng.randint(0, args.vocab, size=(args.batch, 1))
        t = np.arange(L + 1)[None, :]
        toks = (t * stride + phase) % args.vocab
        return toks.astype(np.int32)

    total = args.batch * L  # global token count per step (loss mean)

    def step_body(p, opt_state, inputs, labels):
        """Runs per-shard: inputs/labels are this device's sequence
        chunk. The loss is the GLOBAL token mean (psum of local sums),
        so gradients agree with the unsharded program."""

        def loss_fn(p_in):
            logits = tfm.transformer_lm(
                p_in, inputs, n_heads=args.n_heads,
                attn_impl=args.impl, axis_name="seq",
                **({"local_impl": "flash",
                    "local_backward": args.local_backward}
                   if args.impl == "ulysses"
                   and args.local_impl == "flash" else {}),
            )
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels
            )
            return collectives.psum(jnp.sum(ce), "seq") / total

        # varying-cast OUTSIDE the VJP (trainer.py's round-1 lesson):
        # grads stay local and the explicit psum below is the ONE
        # cross-shard aggregation
        p_vary = collectives.pcast_varying(p, "seq")
        loss, grads = jax.value_and_grad(loss_fn)(p_vary)
        grads = collectives.psum(grads, "seq")
        updates, opt_state = opt.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), opt_state, loss

    # flash under shard_map: the interpret lowering rejects the VMA
    # checker around pallas bodies (CPU mesh only; TPU keeps it on)
    from tpu_syncbn.ops._pallas_common import interpret as _interpret

    check_vma = not (args.local_impl == "flash" and _interpret())
    from tpu_syncbn.compat import shard_map as compat_shard_map

    step = jax.jit(compat_shard_map(
        step_body, mesh=mesh,
        in_specs=(P(), P(), P(None, "seq"), P(None, "seq")),
        out_specs=(P(), P(), P()),
        check_vma=check_vma,
    ))

    shard = NamedSharding(mesh, P(None, "seq"))
    first = last = None
    for it in range(args.steps):
        toks = sample_batch()
        inputs = jax.device_put(jnp.asarray(toks[:, :L]), shard)
        labels = jax.device_put(jnp.asarray(toks[:, 1:]), shard)
        params, opt_state, loss = step(params, opt_state, inputs, labels)
        loss = float(loss)
        first = loss if first is None else first
        last = loss
        if it % 10 == 0 or it == args.steps - 1:
            runtime.master_print(f"step {it:4d}  loss {loss:.4f}")

    runtime.master_print(
        f"done: {args.impl}"
        + (f"+{args.local_impl}" if args.impl == "ulysses" else "")
        + f" over {n} seq shards, global L={L}: "
        f"loss {first:.3f} -> {last:.3f}"
    )
    if not last < first:
        raise SystemExit("loss did not decrease — training is broken")


if __name__ == "__main__":
    main()
