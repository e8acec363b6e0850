"""The lower-precision controls of the looped language-model family.

    python3 chipbench/controls_lm.py --workload <name> --seed <n> [--out <file>]

The family's ``TOLERANCES`` have to tell the configuration's arithmetic
from the nearest lower one. This runs the cell's own set-up (model,
trainer and first batch from ``--seed``, ``correct.program_outputs``),
then compares the program's outputs with ``reference_lm`` as it is and
with it lowered, one arithmetic at a time, each through
``correct.verdict`` against the committed table:

* ``fp8_products``: every product's operands (the seven matrices of a
  layer, q, k and v, the head) rounded to ``float8_e4m3fn``;
* ``bf16_softmax``: the attention's scores, softmax and probabilities
  in bfloat16;
* ``bf16_loss``: the logits and the log-softmax of the cross-entropy in
  bfloat16 (both by ``lax.reduce_precision`` after every operation, the
  sums taken in float32 and then rounded).

``as_configured`` has to come out ``correct`` and every control not.
The last line of standard output is the result (``--out`` writes it to a
file too): per variant the errors, the verdict and the limits that
failed. Beside them three readings that are no limits, all on the first
layer's q, k and v: ``xla_attention``, what XLA's attention of
``models.looped_lm`` (which rounds the probabilities to the compute
type) reads where the configured kernel is compared; ``flash_float32``,
the kernel on the same values held in float32 against the reference in
float32, so neither side stores anything in bfloat16 and what is left
is the precision of the kernel's own products; and ``flash_backward``,
the kernel's backward (an XLA scan over key blocks) against the gradient
of the reference's attention, which ``correct`` never compares.

On the CPU (``JAX_PLATFORMS=cpu``) it runs the ``rehearsal`` sizes, as
``run.py`` does.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import correct, reference_lm, run  # noqa: E402


def _fp8(x):
    import jax.numpy as jnp

    return x.astype(jnp.float8_e4m3fn).astype(x.dtype)


def fp8_products() -> dict:
    plain = {name: getattr(reference_lm, name)
             for name in ("dot", "attention", "head_logits")}
    return {
        "dot": lambda x, w, dtype: plain["dot"](
            _fp8(x.astype(dtype)), _fp8(w.astype(dtype)), dtype),
        "attention": lambda q, k, v: plain["attention"](
            _fp8(q), _fp8(k), _fp8(v)),
        "head_logits": lambda params, z, dtype: plain["head_logits"](
            {"head": _fp8(params["head"].astype(dtype))},
            _fp8(z.astype(dtype)), dtype),
    }


def _bf16(x):
    """``x`` rounded to bfloat16's eight bits of exponent and seven of
    mantissa and kept in float32. ``astype`` will not do: XLA on the TPU
    computes a bfloat16 elementwise chain in float32 and may drop the
    roundings between its operations (``xla_allow_excess_precision``),
    which ``reduce_precision`` forbids."""
    import jax.numpy as jnp
    from jax import lax

    return lax.reduce_precision(x.astype(jnp.float32), 8, 7)


def bf16_softmax() -> dict:
    import jax.numpy as jnp
    from jax import lax

    def attention(q, k, v):
        s, d = q.shape[1], q.shape[-1]
        keys = jnp.arange(s)
        blocks = []
        for start in range(0, s, reference_lm.QUERY_BLOCK):
            qb = q[:, start:start + reference_lm.QUERY_BLOCK]
            scores = _bf16(jnp.einsum("bqhd,bkhd->bhqk", qb, k,
                                      precision=lax.Precision.HIGHEST,
                                      preferred_element_type=jnp.float32)
                           / d ** 0.5)
            rows = start + jnp.arange(qb.shape[1])
            scores = jnp.where(rows[:, None] >= keys[None, :], scores,
                               -jnp.inf)
            top = jnp.max(scores, axis=-1, keepdims=True)
            e = _bf16(jnp.exp(_bf16(scores - top)))
            probs = _bf16(e / _bf16(jnp.sum(e, axis=-1, keepdims=True)))
            blocks.append(jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                                     precision=lax.Precision.HIGHEST,
                                     preferred_element_type=jnp.float32))
        return jnp.concatenate(blocks, axis=1).astype(v.dtype)

    return {"attention": attention}


def bf16_loss() -> dict:
    import jax.numpy as jnp

    def cross_entropy(params, z, targets, dtype):
        zf, tf = z.reshape(-1, z.shape[-1]), targets.reshape(-1)
        out = []
        for start in range(0, zf.shape[0], reference_lm.HEAD_BLOCK):
            stop = start + reference_lm.HEAD_BLOCK
            logits = _bf16(reference_lm.head_logits(
                params, zf[start:stop], dtype))
            shifted = _bf16(logits - jnp.max(logits, axis=-1, keepdims=True))
            total = _bf16(jnp.sum(_bf16(jnp.exp(shifted)), axis=-1,
                                  keepdims=True))
            logp = _bf16(shifted - _bf16(jnp.log(total)))
            picked = jnp.take_along_axis(logp, tf[start:stop, None], axis=-1)
            out.append(-picked[:, 0])
        return jnp.concatenate(out).reshape(targets.shape)

    return {"cross_entropy": cross_entropy}


CONTROLS = {"fp8_products": fp8_products, "bf16_softmax": bf16_softmax,
            "bf16_loss": bf16_loss}


@contextlib.contextmanager
def lowered(replacements: dict):
    """``reference_lm`` with some of its functions replaced: its own
    code finds them by name."""
    plain = {name: getattr(reference_lm, name) for name in replacements}
    for name, fn in replacements.items():
        setattr(reference_lm, name, fn)
    try:
        yield
    finally:
        for name, fn in plain.items():
            setattr(reference_lm, name, fn)


def attention_readings(got: dict) -> dict:
    """The three readings that are no limits (module docstring), on the
    first layer's q, k and v as the program made them."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import rel_l2
    from tpu_syncbn.models.looped_lm import causal_attention

    q, k, v = got["q"], got["k"], got["v"]

    def compare():
        # the reference's side in float32 from the same stored values, so
        # that its gradients are not summed block by block in bfloat16
        wide = [x.astype(jnp.float32) for x in (q, k, v)]
        want, pull = jax.vjp(reference_lm.attention, *wide)
        out, pull_flash = jax.vjp(
            lambda *qkv: causal_attention(*qkv, "flash"), q, k, v)
        grads = zip(pull_flash(out), pull(out.astype(jnp.float32)))
        return {
            "xla_attention": rel_l2(causal_attention(q, k, v, "xla"),
                                    want.astype(q.dtype)),
            "flash_float32": rel_l2(causal_attention(*wide, "flash"), want),
            "flash_backward": {name: rel_l2(a, b)
                               for name, (a, b) in zip("qkv", grads)},
        }

    return jax.tree_util.tree_map(float, jax.jit(compare)())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_SYNCBN_LOG_STREAM", "stderr")
    import jax
    import numpy as np

    from tpu_syncbn import parallel, runtime
    from tpu_syncbn.runtime import probe

    wl = run.load_json("workloads", args.workload + ".json")
    cfg = run.load_json("configs", wl["config"] + ".json")
    backend = probe.ensure_backend(wl["chips"])
    if backend.platform == "cpu":
        wl, cfg = run.rehearsal(wl), run.rehearsal(cfg)
    runtime.initialize()
    mesh = runtime.data_parallel_mesh(wl["chips"])
    family = importlib.import_module("chipbench.families." + cfg["family"])
    inputs = importlib.import_module("chipbench.inputs." + wl["input"]["mode"])
    model_seed, input_seed = np.random.SeedSequence(args.seed).spawn(2)
    key = jax.random.key(int(model_seed.generate_state(1)[0] >> 1))
    dp = parallel.DataParallel(
        family.build_model(cfg, key),
        family.optimizer(cfg, wl["per_chip_batch"] * wl["chips"]),
        family.loss_fn, mesh=mesh)
    batches, close_input = inputs.make(family, cfg, wl, dp, input_seed)
    try:
        batch = next(batches)
    finally:
        close_input()

    got = jax.block_until_ready(correct.program_outputs(dp, family, batch))
    params = correct.pure(dp.params)
    refs = {}
    for name, make in {"as_configured": dict, **CONTROLS}.items():
        with lowered(make()):
            ref = jax.jit(family.reference_fn(cfg))(params, batch, got)
        refs[name] = jax.tree_util.tree_map(float, ref)
    readings = attention_readings(got)
    del got
    before = correct.moving_state(dp, family)
    loss = float(dp.train_step(batch).loss)  # donates the state: last
    after = correct.moving_state(dp, family)

    variants = {}
    for name, ref in refs.items():
        errors = {**ref["errors"],
                  "loss": abs(loss - ref["loss"]) / abs(ref["loss"])}
        checked = correct.verdict(errors, [loss], before, after, family)
        variants[name] = {k: checked[k] for k in
                          ("correct", "out_of_tolerance", "errors")}
    result = {
        "workload": args.workload, "seed": args.seed,
        "platform": backend.platform, "first_loss": loss,
        "tolerances": checked["tolerances"], "variants": variants,
        "readings": readings,
        "ok": variants["as_configured"]["correct"] and not any(
            variants[name]["correct"] for name in CONTROLS),
    }
    line = json.dumps(result)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
