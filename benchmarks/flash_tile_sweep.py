"""Where a grid step of the flash-attention forward kernel goes.

``ops/pallas_attention.py``'s forward walks (query tile, key tile)
pairs, one a grid step. This probe times the kernel alone on the chip at
one call's shape (default: the benchmark cell ``ouro-l8-train-b2x2048``'s
``(B, L, H, D) = (2, 2048, 16, 128)``, bf16, causal, inputs from a fixed
key) for every ``block_q`` x ``block_k`` of ``--blocks``. Device time a
call is the median over ``--calls`` calls of the kernel's operation in a
``jax.profiler`` capture (one capture for all points; each point is a
jitted program of its own, named after its blocks, and an operation
belongs to the program execution whose interval holds it); the report
says which point the kernel takes when it is given none (``chosen``). Beside each
time stand the grid steps a call and the scores computed, and the fit
``time = a x steps + b x scores``: ``a`` is the fixed cost of a grid
step, ``b`` the cost of a score.

``--against FILE`` loads another version of the kernel's module from its
file (the parent commit's, from ``git archive``) and compares the two on
the same q, k, v, at equal tiles and at each side's own: largest and
mean absolute difference of the outputs, how many differ, and each
side's distance to a float32 softmax at the highest matmul precision. ``--layer-probe`` traces loss and gradients of
a two-layer, two-pass ``LoopedDecoderLM`` at Ouro-2.6B's widths and
prints each of the kernel's operations as the compiler wrote it (operand
and result layouts, memory spaces) with its device time: the forward
pass's call beside the one ``jax.checkpoint`` runs again.

Run on the chip (on the CPU it times the interpreter, which tells
nothing: the script refuses unless ``--allow-cpu``, a rehearsal of the
control flow at a tiny ``--shape``):

    python benchmarks/flash_tile_sweep.py --layer-probe \
        [--against .scratch/parent/tpu_syncbn/ops/pallas_attention.py]

The last line of standard output is the report; ``--out`` writes it to a
file too.
"""

import argparse
import glob
import importlib.util
import json
import os
import statistics
import sys
import tempfile

from _common import log, setup

KERNEL_OP = "custom-call"  # the HLO text of a Mosaic kernel's operation


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--blocks", type=int, nargs="+",
                   default=[128, 256, 512, 1024])
    p.add_argument("--shape", type=int, nargs=4, default=[2, 2048, 16, 128],
                   metavar=("B", "L", "H", "D"))
    p.add_argument("--calls", type=int, default=20)
    p.add_argument("--module", default=None,
                   help="time this file's version of pallas_attention.py "
                        "and not the tree's")
    p.add_argument("--against", default=None,
                   help="file of another version of pallas_attention.py")
    p.add_argument("--layer-probe", action="store_true")
    p.add_argument("--backward", action="store_true",
                   help="time the two backward kernels and the XLA scan "
                        "instead of the forward, and compare their "
                        "gradients with a float32 reference")
    p.add_argument("--dv", type=int, default=None,
                   help="head width of v where it is not D")
    p.add_argument("--reference-heads", type=int, default=2,
                   help="--backward: heads the float32 reference computes")
    p.add_argument("--allow-cpu", action="store_true")
    p.add_argument("--out", default=None)
    return p.parse_args()


def load_module(path: str):
    name = "pallas_attention_" + "".join(c if c.isalnum() else "_"
                                         for c in path)
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def walk(pa, length: int, block_q: int, block_k: int) -> int:
    """Tile pairs of one batch-head's causal walk."""
    n_q, n_k = -(-length // block_q), -(-length // block_k)
    return len(pa._live_tiles(pa.CAUSAL, n_q, n_k, block_q, block_k)[0])


def capture(run):
    """``run()`` under a profiler capture without the host and Python
    tracers (``chipbench/tracer.py`` says why); the capture's planes as
    ``chipbench.xplane.read`` gives them."""
    import jax

    from chipbench import trace_reduce, xplane

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    with tempfile.TemporaryDirectory(prefix="flash-sweep-") as d:
        jax.profiler.start_trace(d, profiler_options=options)
        try:
            run()
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            return []
        with open(files[0], "rb") as f:
            return xplane.read(
                f.read(),
                planes=lambda n: n.startswith(trace_reduce.DEVICE_PLANE_PREFIX),
                lines=lambda n: n in (trace_reduce.OPS_LINE,
                                      trace_reduce.MODULES_LINE))


def by_program(planes) -> dict:
    """``{program name: [[(operation's HLO text, scope path, ns), ...]
    of one execution, ...]}`` of device 0."""
    from chipbench import trace_reduce

    out: dict = {}
    for plane in planes[:1]:
        meta = plane["metadata"]
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        ops = sorted(lines.get(trace_reduce.OPS_LINE, []), key=lambda e: e[1])
        for i, start, dur in sorted(lines.get(trace_reduce.MODULES_LINE, []),
                                    key=lambda e: e[1]):
            name = meta.get(i, ("", None))[0].split("(")[0]
            inside = [(*meta.get(j, ("", None)), d) for j, s, d in ops
                      if start <= s < start + dur]
            out.setdefault(name, []).append(inside)
    return out


def named_ms(executions: list, name: str) -> float:
    """Median ms, over a program's executions, of the operations whose
    HLO text holds ``name``."""
    return statistics.median(
        sum(d for n, _, d in ex if name in n) / 1e6 for ex in executions)


def kernel_ms(executions: list) -> tuple:
    """(median ms of the kernel's operation, median ms of all the
    program's operations) over a program's executions."""
    return named_ms(executions, KERNEL_OP), named_ms(executions, "")


def sweep(pa, q, k, v, points: list, calls: int) -> list:
    import jax

    b, length, h, _ = q.shape
    programs, rows = {}, []
    for bq, bk in points:
        tiles = walk(pa, length, bq, bk)
        row = {"block_q": bq, "block_k": bk, "steps": b * h * tiles,
               "scores": b * h * tiles * bq * bk}
        name = f"flash_q{bq}_k{bk}"

        def call(q, k, v, bq=bq, bk=bk):
            return pa.flash_attention(q, k, v, causal=True,
                                      block_q=bq, block_k=bk)

        call.__name__ = name
        fn = jax.jit(call)
        try:
            fn(q, k, v).block_until_ready()
        except Exception as e:  # the compiler refusing a tile is a finding
            row["error"] = " ".join(f"{type(e).__name__}: {e}".split())[:300]
            log(f"[sweep] {name}: {row['error']}")
        else:
            programs[f"jit_{name}"] = (fn, row)
        rows.append(row)

    def run():
        for fn, _ in programs.values():
            for _ in range(calls):
                out = fn(q, k, v)
            out.block_until_ready()

    executions = by_program(capture(run))
    for name, (_, row) in programs.items():
        if name in executions:
            row["kernel_ms"], row["program_ms"] = kernel_ms(executions[name])
            row["calls"] = len(executions[name])
            log(f"[sweep] {name}: {row}")
    return rows


def fit(rows: list) -> dict:
    """Least squares of ``kernel_ms = a x steps + b x scores`` over the
    timed points."""
    import numpy as np

    timed = [r for r in rows if "kernel_ms" in r]
    if len(timed) < 3:
        return {}
    x = np.array([[r["steps"], r["scores"]] for r in timed], float)
    y = np.array([r["kernel_ms"] for r in timed], float)
    (a, b), *_ = np.linalg.lstsq(x, y, rcond=None)
    resid = x @ np.array([a, b]) - y
    return {"a_us_per_step": a * 1e3, "b_ps_per_score": b * 1e9,
            "largest_residual_ms": float(np.abs(resid).max()),
            "points": len(timed)}


def dense(q, k, v):
    """Causal softmax attention in float32 at the highest matmul
    precision, on the values as stored."""
    import jax
    import jax.numpy as jnp

    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision="highest") * q.shape[-1] ** -0.5
    n = q.shape[1]
    s = jnp.where(jnp.arange(n)[:, None] >= jnp.arange(n)[None, :],
                  s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                      precision="highest")


def compare(pa, other, q, k, v, same: int = 128) -> dict:
    """This tree's kernel against ``other``'s on the same inputs, twice:
    both at ``same`` x ``same`` tiles, where the two walk the same pairs
    in the same order and only the arithmetic inside a tile can differ,
    and each with the tiles it takes when given none. Beside them each
    side's distance to a float32 softmax at the highest matmul
    precision."""
    import jax
    import jax.numpy as jnp

    def run(module, **blocks):
        return jax.jit(lambda q, k, v: module.flash_attention(
            q, k, v, causal=True, **blocks))(q, k, v).astype(jnp.float32)

    def distance(a, b) -> dict:
        diff = jnp.abs(a - b)
        return {"largest": float(diff.max()), "mean": float(diff.mean()),
                "differing": int((diff > 0).sum())}

    o_ref = jax.jit(dense)(q, k, v)
    o_new, o_old = run(pa), run(other)
    return {
        "outputs": int(o_ref.size),
        "largest_output": float(jnp.abs(o_ref).max()),
        "same_tiles": distance(run(pa, block_q=same, block_k=same),
                               run(other, block_q=same, block_k=same)),
        "own_tiles": distance(o_new, o_old),
        "new_vs_float32": distance(o_new, o_ref),
        "old_vs_float32": distance(o_old, o_ref),
    }


def sweep_backward(pa, q, k, v, points: list, calls: int) -> list:
    """The backward alone (residuals from one forward call, dO from a
    fixed key) at every point: device time a call of the dK/dV kernel,
    of the dQ kernel and of the whole program (delta and padding too),
    each kernel's grid steps and scores; and one row for the XLA scan."""
    import jax
    import jax.numpy as jnp

    b, length, h, d = q.shape
    scale = d ** -0.5
    to2d = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, length,
                                                     x.shape[-1])
    q2, k2, v2 = to2d(q), to2d(k), to2d(v)
    o2, lse = jax.jit(lambda q, k, v: pa._flash_fwd_2d(
        q, k, v, rule=pa.CAUSAL, scale=scale, block_q=None, block_k=None))(
            q2, k2, v2)
    do2 = jax.random.normal(jax.random.PRNGKey(7), o2.shape,
                            jnp.float32).astype(o2.dtype)
    args = (q2, k2, v2, o2, lse, do2)
    programs, rows = {}, []

    def add(name, fn, row):
        fn.__name__ = name
        jitted = jax.jit(fn)
        try:
            jax.block_until_ready(jitted(*args))
        except Exception as e:  # the compiler refusing a tile is a finding
            row["error"] = " ".join(f"{type(e).__name__}: {e}".split())[:300]
            log(f"[sweep] {name}: {row['error']}")
        else:
            programs[f"jit_{name}"] = (jitted, row)
        rows.append(row)

    for bq, bk in points:
        n_q, n_k = -(-length // bq), -(-length // bk)
        walks = {"dkv": len(pa._live_tiles(pa.CAUSAL, n_q, n_k, bq, bk,
                                           by_key=True)[0]),
                 "dq": len(pa._live_tiles(pa.CAUSAL, n_q, n_k, bq, bk)[0])}
        add(f"flash_bwd_q{bq}_k{bk}",
            lambda q, k, v, o, lse, do, bq=bq, bk=bk:
                pa._flash_bwd_2d_pallas(
                    (q, k, v, o, lse), do, rule=pa.CAUSAL, scale=scale,
                    block_q=bq, block_k=bk),
            {"block_q": bq, "block_k": bk,
             **{f"{kernel}_steps": b * h * n for kernel, n in walks.items()},
             **{f"{kernel}_scores": b * h * n * bq * bk
                for kernel, n in walks.items()}})
    add("flash_bwd_scan",
        lambda q, k, v, o, lse, do: pa._flash_bwd_2d(
            (q, k, v, o, lse), do, rule=pa.CAUSAL, scale=scale,
            block_k=pa.backward_scan_block(length)),
        {"scan_block": pa.backward_scan_block(length)})

    def run():
        for fn, _ in programs.values():
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)

    executions = by_program(capture(run))
    for name, (_, row) in programs.items():
        if name in executions:
            ex = executions[name]
            row["program_ms"] = named_ms(ex, "")
            if "block_q" in row:
                row["dkv_ms"] = named_ms(ex, "flash_bwd_dkv")
                row["dq_ms"] = named_ms(ex, "flash_bwd_dq")
            row["calls"] = len(ex)
            log(f"[sweep] {name}: {row}")
    return rows


def fit_backward(rows: list) -> dict:
    """``fit`` for each backward kernel."""
    return {kernel: fit([
        {"steps": r[f"{kernel}_steps"], "scores": r[f"{kernel}_scores"],
         "kernel_ms": r[f"{kernel}_ms"]}
        for r in rows if f"{kernel}_ms" in r]) for kernel in ("dkv", "dq")}


def gradients(modules: dict, q, k, v, heads: int) -> dict:
    """dq, dk, dv of ``sum(flash_attention(q, k, v) * w)`` on the first
    ``heads`` heads, for each ``{label: (module, backward)}``, as the
    relative L2 distance to the gradients of a float32 softmax at the
    highest matmul precision on the same (stored) values."""
    import jax
    import jax.numpy as jnp

    q, k, v = (x[:, :, :heads] for x in (q, k, v))
    w = jax.random.normal(jax.random.PRNGKey(11), v.shape, jnp.float32)

    def grads(attend):
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32) * w),
            argnums=(0, 1, 2)))(q, k, v)

    rel = lambda a, b: float(
        jnp.linalg.norm((a.astype(jnp.float32) - b).ravel())
        / jnp.linalg.norm(b.ravel()))
    want = grads(dense)
    out = {"heads": heads}
    for label, (module, backward) in modules.items():
        try:
            got = grads(lambda q, k, v: module.flash_attention(
                q, k, v, causal=True, backward=backward))
        except Exception as e:  # the parent refuses unequal widths
            out[label] = " ".join(f"{type(e).__name__}: {e}".split())[:200]
            continue
        out[label] = {name: rel(a, b)
                      for name, a, b in zip(("dq", "dk", "dv"), got, want)}
    return out


def layer_probe(calls: int = 3) -> list:
    """The kernel's operations in loss + gradients of a small looped
    decoder at Ouro-2.6B's widths, as the compiler wrote them."""
    import jax
    import jax.numpy as jnp
    from flax import nnx

    from tpu_syncbn.models.looped_lm import LoopedDecoderLM

    model = LoopedDecoderLM(
        vocab_size=6144, hidden_size=2048, num_heads=16, head_dim=128,
        intermediate_size=5632, num_layers=2, loops=2, rope_theta=1e6,
        exit_beta=0.1, dtype=jnp.bfloat16, attn_impl="flash",
        rngs=nnx.Rngs(0))
    graphdef, params = nnx.split(model, nnx.Param)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 2048), 0, 6144)

    def layer_probe_step(p, tokens, targets):
        return nnx.merge(graphdef, p).loss(tokens, targets)[0]

    fn = jax.jit(jax.value_and_grad(layer_probe_step))
    jax.block_until_ready(fn(params, tokens, tokens))

    def run():
        for _ in range(calls):
            out = fn(params, tokens, tokens)
        jax.block_until_ready(out)

    executions = by_program(capture(run)).get("jit_layer_probe_step", [])
    seen: dict = {}
    for ex in executions:
        for name, path, dur in ex:
            if KERNEL_OP in name and "pallas_call" in (path or ""):
                seen.setdefault((name, path), []).append(dur / 1e6)
    return [{"op": name.split(", custom_call_target")[0], "path": path,
             "ms_a_call": statistics.median(ms), "calls": len(ms)}
            for (name, path), ms in seen.items()]


def main():
    args = parse_args()
    setup(None)

    import jax
    import jax.numpy as jnp

    from tpu_syncbn.ops import pallas_attention as pa

    if args.module:
        pa = load_module(args.module)
    if jax.default_backend() != "tpu" and not args.allow_cpu:
        print(json.dumps({"metric": "flash_tile_sweep",
                          "skipped": "needs a TPU; --allow-cpu rehearses",
                          "backend": jax.default_backend()}))
        sys.exit(0)

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    widths = [args.shape[3], args.shape[3], args.dv or args.shape[3]]
    q, k, v = (jax.random.normal(key, (*args.shape[:3], width), jnp.float32)
               .astype(jnp.bfloat16) for key, width in zip(keys, widths))
    grid = [(bq, bk) for bq in args.blocks for bk in args.blocks]
    device = jax.devices()[0]
    report = {"metric": "flash_tile_sweep", "shape": args.shape,
              "dv": widths[2],
              "device": {"platform": device.platform,
                         "kind": device.device_kind}}
    _, length, _, d = args.shape
    if args.backward:
        report["chosen"] = pa.backward_blocks(length, d, q.dtype.itemsize,
                                              widths[2])
        rows = sweep_backward(pa, q, k, v, grid, args.calls)
        report["rows"] = rows
        report["fit"] = fit_backward(rows)
        modules = {"kernels": (pa, "pallas"), "scan": (pa, "xla")}
        if args.against:
            other = load_module(args.against)
            modules.update({"against_kernels": (other, "pallas"),
                            "against_scan": (other, "xla")})
        report["gradients"] = gradients(modules, q, k, v,
                                        args.reference_heads)
        finish(report, args.out)
        return
    # the tiles the kernel takes when given none: timed under their own
    # names (a program that differs from a point's in its name alone is
    # the same executable to the compile cache, and to the trace)
    chosen = []
    if hasattr(pa, "forward_blocks"):  # the parent's module has none
        chosen = [pa.forward_blocks(length, d, q.dtype.itemsize)]
        report["chosen"] = list(chosen[0])
    rows = sweep(pa, q, k, v, grid + [c for c in chosen if c not in grid],
                 args.calls)
    report["rows"] = rows
    report["fit"] = fit([r for r in rows
                         if (r["block_q"], r["block_k"]) in grid])
    if args.against:
        report["against"] = compare(pa, load_module(args.against), q, k, v)
    if args.layer_probe:
        report["layer_probe"] = layer_probe()
    finish(report, args.out)


def finish(report: dict, out) -> None:
    text = json.dumps(report)
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            f.write(text + "\n")
    print(text)


if __name__ == "__main__":
    main()
