"""The lower-precision controls of the mixture-of-experts family.

    python3 chipbench/controls_moe_lm.py --workload <name> --seed <n> [--out <file>]

As ``controls_lm.py`` for the looped family: the family's ``TOLERANCES``
have to tell the configuration's arithmetic from the nearest lower one.
This runs the cell's own set-up (model, trainer and first batch from
``--seed``, ``correct.program_outputs``), then compares the program's
outputs with ``reference_moe_lm`` as it is and with it lowered, one
arithmetic at a time, each through ``correct.verdict`` against the
committed table:

* ``fp8_products``: every product's operands (the matrices of the
  attention, of the experts and of the shared expert, q, k and v, the
  head) rounded to ``float8_e4m3fn``; the router is left as it is;
* ``bf16_softmax``: the attention's scores, softmax and probabilities
  in bfloat16;
* ``bf16_router``: the router's product, its sigmoid and so its
  selection and weights in bfloat16;
* ``bf16_loss``: the logits and the log-softmax of the cross-entropy in
  bfloat16.

``as_configured`` has to come out ``correct`` and every control not. The
last line of standard output is the result (``--out`` writes it to a file
too): per variant the errors, the verdict and the limits that failed.

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

from chipbench import controls_lm, correct, reference_moe_lm, run  # noqa: E402


def fp8_products() -> dict:
    plain = {name: getattr(reference_moe_lm, name)
             for name in ("dot", "attention", "head_logits")}
    fp8 = controls_lm._fp8
    return {
        "dot": lambda x, w, dtype, out=None: plain["dot"](
            fp8(x.astype(dtype)), fp8(w.astype(dtype)), dtype, out),
        "attention": lambda q, k, v: plain["attention"](
            fp8(q), fp8(k), fp8(v)),
        "head_logits": lambda params, z, dtype: plain["head_logits"](
            {"head": fp8(params["head"].astype(dtype))},
            fp8(z.astype(dtype)), dtype),
    }


def bf16_router() -> dict:
    import jax
    import jax.numpy as jnp

    bf16 = controls_lm._bf16

    def router_scores(x, w):
        logits = jnp.dot(bf16(x), bf16(w),
                         precision=reference_moe_lm.HIGHEST)
        return bf16(jax.nn.sigmoid(bf16(logits)))

    return {"router_scores": router_scores}


# the attention's and the loss's controls are the looped family's: they
# replace a function of the same name and signature, and read only what
# the two references share (QUERY_BLOCK, HEAD_BLOCK, params["head"])
CONTROLS = {"fp8_products": fp8_products,
            "bf16_softmax": controls_lm.bf16_softmax,
            "bf16_router": bf16_router,
            "bf16_loss": controls_lm.bf16_loss}


@contextlib.contextmanager
def lowered(replacements: dict):
    """``reference_moe_lm`` with some of its functions replaced: its own
    code finds them by name."""
    plain = {name: getattr(reference_moe_lm, name) for name in replacements}
    for name, fn in replacements.items():
        setattr(reference_moe_lm, name, fn)
    try:
        yield
    finally:
        for name, fn in plain.items():
            setattr(reference_moe_lm, name, fn)


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
