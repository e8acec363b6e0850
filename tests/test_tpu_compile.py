"""Compile — not merely lower — the Pallas kernels for a TPU v5e that is
described, not attached.

``test_tpu_lowering.py`` stops at the Mosaic lowering, which enforces
the block-shape rules. What it cannot see is what the TPU *compiler*
refuses: a kernel that wants more scoped VMEM than a core has, a slice
that does not sit on the tiling, a program that does not fit the chip.
libtpu is installed here and compiles for a topology given by name
(``/opt/skills/guides/on-chip-measurement/SKILL.md`` §2.3), so these
cases ask it at the widths the ResNet-50 main path and the flash
attention callers really use. A pass is not a chip run: nothing
executes, no number comes out.

All of it stays in this one process: libtpu takes a lock file, and a
second process describing a topology at the same time aborts.
"""

import collections
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tpu_syncbn.ops import pallas_attention as pa
from tpu_syncbn.ops import pallas_bn


@pytest.fixture(scope="module")
def v5e():
    """One described v5e chip, with the persistent compile cache off
    around the module: an entry written for a described chip cannot be
    read back without one, and the next run would warn about it."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu / topology unknown to this build
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """Route pallas_calls to the TPU compiler, not the interpreter
    (``interpret()`` asks ``jax.default_backend()``, which is the CPU
    here whatever the program is compiled for)."""
    monkeypatch.setattr(pa, "_interpret", lambda: False)
    monkeypatch.setattr(pallas_bn, "_interpret", lambda: False)


def _compile_fwd_and_grad(loss, *args):
    """One program that holds the forward kernels and the backward
    ones: ``value_and_grad`` keeps the forward's result."""
    compiled = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1, 2))
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _flash_calls(compiled) -> collections.Counter:
    """The compiled program's ``tpu_custom_call`` instructions of the
    attention kernels, by kernel: an instruction carries its kernel's
    ``name`` and a number (``flash_fwd_q512_k512.47``). A loop's body is
    counted once."""
    return collections.Counter(re.findall(
        r'^\s*(?:ROOT )?%?(flash_[\w-]+?)(?:\.\d+)? = [^\n]*custom-call\('
        r'[^\n]*custom_call_target="tpu_custom_call"', compiled.as_text(),
        re.M))


def _bn_loss(x, w, b):
    y, mean, var, count = pallas_bn.fused_batch_norm(
        x, w, b, eps=1e-5, axis_name=None
    )
    # stats feed the no-grad running-buffer update only; the VJP
    # rejects differentiation through them by design
    return y.astype(jnp.float32).sum() + sum(
        jax.lax.stop_gradient(s).sum() for s in (mean, var, count)
    )


def _bn_args(shape, dtype, chip):
    c = shape[-1]
    return (
        jax.ShapeDtypeStruct(shape, dtype, sharding=chip),
        jax.ShapeDtypeStruct((c,), jnp.float32, sharding=chip),
        jax.ShapeDtypeStruct((c,), jnp.float32, sharding=chip),
    )


# ResNet-50 at per-chip batch 64, 224²: the stem BN, a stage-1 block's
# widest BN, the last stage's; and rows that are no multiple of a block
BN_SHAPES = [
    (64, 112, 112, 64),
    (64, 56, 56, 256),
    (64, 7, 7, 2048),
    (2, 100, 100, 256),
]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("shape", BN_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_fused_batch_norm_compiles_for_v5e(v5e, mosaic, shape, dtype):
    _compile_fwd_and_grad(_bn_loss, *_bn_args(shape, dtype, v5e))


@pytest.mark.parametrize("c", [16384, 32768])
def test_fused_batch_norm_very_wide_channels(v5e, mosaic, c):
    """Wider than any BN the models have. At C = 16384 f32 a 64-row
    block's two double-buffered streams are 16 MiB, the whole scoped
    VMEM, and the compiler still takes it; at C = 32768 it refuses 64
    rows (16.25 MiB against the 16 MiB limit), so ``_block_m`` has to
    follow its budget below 64 — and the compiler, not a chip run, is
    who says the result fits."""
    _compile_fwd_and_grad(_bn_loss, *_bn_args((512, c), jnp.float32, v5e))


# (seq, heads, head_dim): the chip_smoke shape, a long sequence, and the
# narrow head the 128-lane layout has to pad
FLASH_SHAPES = [(2048, 16, 128), (8192, 16, 128), (4096, 16, 64)]


@pytest.mark.parametrize("backward", ["xla", "pallas"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("shape", FLASH_SHAPES,
                         ids=lambda s: "S{}h{}d{}".format(*s))
def test_flash_attention_compiles_for_v5e(v5e, mosaic, shape, causal, backward):
    s, h, d = shape
    q = jax.ShapeDtypeStruct((1, s, h, d), jnp.bfloat16, sharding=v5e)

    def loss(q, k, v):
        return pa.flash_attention(
            q, k, v, causal=causal, backward=backward
        ).astype(jnp.float32).sum()

    _compile_fwd_and_grad(loss, q, q, q)


# (batch, seq, heads, head_dim): the benchmark cell's call, and a length
# that is no multiple of a block; both take the tiles the kernel chooses
# from the shape, the largest it can return, so that Mosaic's VMEM limit
# and tiling rules judge them
FLASH_CALLS = [(2, 2048, 16, 128), (1, 2000, 16, 128)]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("call", FLASH_CALLS,
                         ids=lambda c: "B{}S{}h{}d{}".format(*c))
def test_flash_attention_chosen_blocks_compile_for_v5e(v5e, mosaic, call,
                                                       dtype):
    b, s, h, d = call
    assert pa.forward_blocks(s, d, jnp.dtype(dtype).itemsize) == (512, 512)
    q = jax.ShapeDtypeStruct((b, s, h, d), dtype, sharding=v5e)

    def loss(q, k, v):
        return pa.flash_attention(
            q, k, v, causal=True).astype(jnp.float32).sum()

    _compile_fwd_and_grad(loss, q, q, q)


def test_looped_decoder_compiles_for_v5e(v5e, mosaic, monkeypatch):
    """The looped decoder at Ouro-2.6B's published widths (hidden 2048,
    16 heads of 128, SwiGLU 5632), 2 sequences of 2,048 tokens, through
    the flash kernel with per-layer recomputation: loss and gradients in
    one program. Cut where the compile time is: 2 layers, 2 passes and an
    eighth of the vocabulary; the benchmark's cell runs 8, 4 and all.

    Its checkpoint keeps none of the kernel's residuals, so the names
    the kernel's forward rule gives them lower to nothing: the compiled
    step has the temporaries and the bytes accessed it has without them,
    to the byte."""
    from flax import nnx

    from tpu_syncbn.models.looped_lm import LoopedDecoderLM

    abstract = nnx.eval_shape(lambda: LoopedDecoderLM(
        vocab_size=6144, hidden_size=2048, num_heads=16, head_dim=128,
        intermediate_size=5632, num_layers=2, loops=2, rope_theta=1e6,
        exit_beta=0.1, dtype=jnp.bfloat16, attn_impl="flash",
        rngs=nnx.Rngs(0)))
    graphdef, params = nnx.split(abstract, nnx.Param)
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e), t)
    tokens = jax.ShapeDtypeStruct((2, 2048), jnp.int32, sharding=v5e)

    def loss(p, tokens, targets):
        return nnx.merge(graphdef, p).loss(tokens, targets)[0]

    def compiled():
        return jax.jit(jax.value_and_grad(loss)).lower(
            on_chip(params), tokens, tokens).compile()

    # the forward kernel and its recomputation, and the two backward
    # kernels at the tiles the cell's 2,048 tokens choose
    ours = compiled()
    assert _flash_calls(ours) == {
        "flash_fwd_q512_k512": 2, "flash_bwd_dkv_q512_k512": 1,
        "flash_bwd_dq_q512_k512": 1}
    monkeypatch.setattr(pa, "_named_residuals", lambda o, lse, _: (o, lse))
    unnamed = compiled()
    assert (ours.memory_analysis().temp_size_in_bytes
            == unnamed.memory_analysis().temp_size_in_bytes)
    assert (ours.cost_analysis()["bytes accessed"]
            == unnamed.cost_analysis()["bytes accessed"])


@pytest.mark.parametrize("backward", ["xla", "pallas"])
def test_flash_attention_latent_widths_compile_for_v5e(v5e, mosaic, backward):
    """Latent attention's call in the benchmark's cell
    (``joyai-l5-train-b1x8192``): q and k 192 wide, which is no multiple
    of the 128 lanes, v and the output 128, 32 heads, 8,192 tokens; the
    tiles the shape chooses, the backward as an XLA scan and as the two
    kernels the cell runs (dk 192 wide, dv 128)."""
    assert pa.forward_blocks(8192, 192, 2, 128) == (512, 512)
    qk = jax.ShapeDtypeStruct((1, 8192, 32, 192), jnp.bfloat16, sharding=v5e)
    v = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16, sharding=v5e)

    def loss(q, k, v):
        return pa.flash_attention(
            q, k, v, causal=True, backward=backward
        ).astype(jnp.float32).sum()

    _compile_fwd_and_grad(loss, qk, qk, v)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("call", [(2, 2048, 16, 128, 128),
                                  (1, 2000, 16, 128, 128),
                                  (1, 8192, 32, 192, 128)],
                         ids=lambda c: "B{}S{}h{}d{}v{}".format(*c))
def test_backward_kernels_chosen_blocks_compile_for_v5e(v5e, mosaic, call,
                                                        dtype):
    """The two backward kernels at the tiles they choose from the shape,
    the largest they can return, so that Mosaic's VMEM limit (the
    kernels scope ``_BWD_VMEM_SCOPED_BYTES``) and tiling rules judge
    them: the two cells' calls and a ragged length, in both types."""
    b, s, h, d, dv = call
    qk = jax.ShapeDtypeStruct((b, s, h, d), dtype, sharding=v5e)
    v = jax.ShapeDtypeStruct((b, s, h, dv), dtype, sharding=v5e)

    def loss(q, k, v):
        return pa.flash_attention(
            q, k, v, causal=True, backward="pallas"
        ).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qk, qk, v).compile()
    chosen = pa.backward_blocks(s, d, jnp.dtype(dtype).itemsize, dv)
    for kernel, (bq, bk) in chosen.items():
        assert f"flash_bwd_{kernel}_q{bq}_k{bk}" in compiled.as_text()


def test_latent_moe_decoder_compiles_for_v5e(v5e, mosaic, monkeypatch):
    """The latent-attention mixture-of-experts decoder at
    JoyAI-LLM-Flash's published widths (hidden 2048, 32 heads of 192 /
    128, ranks 1536 and 512, experts 768 wide, a 256-way router with 16
    experts held, 8 a token), one sequence of 4,096 tokens, through the
    flash kernel and the grouped products with per-layer recomputation:
    loss and gradients in one program. Cut where the compile time is:
    one dense and one expert layer, the prediction module, a sixteenth of
    the slice of the vocabulary; the benchmark's cell runs 1 + 4, 8,192
    tokens and 16,160 ids.

    The layer's checkpoint keeps the kernel's output and log-sum-exp, so
    the program holds the forward kernel once a layer application, not
    twice, and what it holds for that is three outputs of 33.5 MB and
    three (BH, L) rows of 0.5 MB: were the log-sum-exp kept as the
    kernel writes it, (BH, T, 1) padded to 128 lanes, it would be 67 MB
    a call and the step 316 MB larger and not 118."""
    from flax import nnx

    from tpu_syncbn.models import moe_lm

    abstract = nnx.eval_shape(lambda: moe_lm.LatentMoEDecoderLM(
        vocab_size=1024, hidden_size=2048, num_heads=32, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_dim=128,
        dense_layers=1, dense_intermediate=7168, moe_layers=1, n_experts=256,
        experts_held=16, experts_per_token=8, moe_intermediate=768,
        shared_intermediate=768, routed_scale=2.5, mtp=True,
        rope_theta=32e6, dtype=jnp.bfloat16, attn_impl="flash",
        rngs=nnx.Rngs(0)))
    graphdef, params, rest = nnx.split(abstract, nnx.Param, ...)
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e), t)
    tokens = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=v5e)

    def loss(p, r, tokens, targets, targets2):
        # copy=True: the router state moves on variables of this trace
        return nnx.merge(graphdef, p, r, copy=True).loss(
            tokens, targets, targets2)[0]

    def compiled():
        return jax.jit(jax.value_and_grad(loss)).lower(
            on_chip(params), on_chip(rest), tokens, tokens, tokens).compile()

    # the attention kernel of three layer applications and their backward
    # kernels; the grouped products are the compiler's own kernels
    ours = compiled()
    assert _flash_calls(ours) == {
        "flash_fwd_q512_k512": 3, "flash_bwd_dkv_q512_k512": 3,
        "flash_bwd_dq_q512_k512": 3}
    assert "ragged-dot" in ours.as_text()
    # against the program with nothing named and nothing kept
    monkeypatch.setattr(moe_lm, "_saved", lambda: ())
    monkeypatch.setattr(pa, "_named_residuals", lambda o, lse, _: (o, lse))
    plain = compiled()
    assert _flash_calls(plain)["flash_fwd_q512_k512"] == 6
    grown = (ours.memory_analysis().temp_size_in_bytes
             - plain.memory_analysis().temp_size_in_bytes)
    assert 90e6 < grown < 130e6, grown


@pytest.mark.parametrize("call", [(1, 8192, 32, 4, 128, (4096, 4)),
                                  (1, 600, 8, 2, 128, (300, 4)),
                                  (2, 1024, 8, 2, 128, (512, 12)),
                                  (1, 2048, 8, 2, 128, None)],
                         ids=lambda c: "B{}S{}h{}kv{}d{}m{}".format(*c))
def test_grouped_block_masked_kernels_compile_for_v5e(v5e, mosaic, call):
    """The three kernels with fewer k/v heads than q heads, under the
    block-diffusion mask (and, the last case, causal), at the tiles the
    shape chooses: the benchmark cell's call (``sdar-l6-train-b1x4096``:
    8,192 positions, 32 q heads over 4 k/v heads of 128, blocks of 4:
    80 tile pairs a q head in the forward and dQ, 640 a k/v head in
    dK/dV), a ragged length whose halves meet inside a tile, and a block
    that is no power of two (integer division in the kernel)."""
    b, s, h, kv, d, mask = call
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=v5e)
    k = jax.ShapeDtypeStruct((b, s, kv, d), jnp.bfloat16, sharding=v5e)
    how = dict(causal=True) if mask is None else dict(
        block_diffusion_mask=mask)

    def loss(q, k, v):
        return pa.flash_attention(
            q, k, v, backward="pallas", **how).astype(jnp.float32).sum()

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        q, k, k).compile().as_text()
    assert text.count("tpu_custom_call") >= 3
    if s == 8192:
        for name in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
            assert f"{name}_q512_k512" in text


def test_block_diffusion_decoder_compiles_for_v5e(v5e, mosaic):
    """The block-diffusion decoder at SDAR-30B-A3B's published widths
    (hidden 2048, 32 q heads over 4 k/v heads of 128, experts 768 wide, a
    128-way router with 16 experts held, 8 a token), one sequence of
    2,048 tokens with its noisy copy, through the three kernels and the
    grouped products with per-layer recomputation: loss and gradients in
    one program. Cut where the compile time is: 2 layers and a sixteenth
    of the slice of the vocabulary; the benchmark's cell runs 6, 4,096
    tokens and 18,992 ids."""
    from flax import nnx

    from tpu_syncbn.models.block_diffusion_lm import BlockDiffusionMoELM

    abstract = nnx.eval_shape(lambda: BlockDiffusionMoELM(
        vocab_size=1187, hidden_size=2048, num_heads=32, num_kv_heads=4,
        head_dim=128, num_layers=2, block_length=4, n_experts=128,
        experts_held=16, experts_per_token=8, moe_intermediate=768,
        rope_theta=1e6, embed_std=1.0, dtype=jnp.bfloat16,
        attn_impl="flash", rngs=nnx.Rngs(0)))
    graphdef, params, rest = nnx.split(abstract, nnx.Param, ...)
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e), t)
    tokens = jax.ShapeDtypeStruct((1, 2048), jnp.int32, sharding=v5e)
    weights = jax.ShapeDtypeStruct((1, 2048), jnp.float32, sharding=v5e)

    def loss(p, r, x0, xt, w):
        # copy=True: the loads are counted on variables of this trace
        return nnx.merge(graphdef, p, r, copy=True).loss(x0, xt, w)[0]

    compiled = jax.jit(jax.value_and_grad(loss)).lower(
        on_chip(params), on_chip(rest), tokens, tokens, weights).compile()
    # a layer's forward kernel call (no second one: the layer's
    # checkpoint keeps its output and log-sum-exp) and its two backward
    # kernels, once in each loop's body; the grouped products are the
    # compiler's own
    assert _flash_calls(compiled) == {
        "flash_fwd_q512_k512": 1, "flash_bwd_dkv_q512_k512": 1,
        "flash_bwd_dq_q512_k512": 1}
    assert "ragged-dot" in compiled.as_text()


def _copies_and_pads(text: str) -> list[tuple[str, str]]:
    """(dimensions, operation) of the compiled program's entry
    computation's ``copy`` and ``pad`` instructions."""
    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", text, re.S | re.M).group(1)
    return re.findall(r"= \w+\[([\d,]*)\]\S* (copy|pad)\(", entry)


@pytest.mark.parametrize("n, strips", [(2, 4), (8, 1)])
def test_bottlenecks_on_strips_keep_space_to_batch_away(v5e, n, strips):
    """Two bottleneck blocks of RetinaNet's ``layer1`` at 800x1344
    (``retinanet-train-b2``: 2 x 200 x 336 x 256 bf16), loss and
    gradients in one program, the way ``ResNet.features`` runs a stage.
    On whole images the batch of 2 makes the compiler convert every
    convolution space-to-batch (the TPU's layout tiles batch x channels
    by 8 x 128): 10 ``copy`` and 4 ``pad`` in the entry computation,
    74.2 KB accessed a position. On 4 row strips an image the batch is 8:
    1 ``copy``, no ``pad``, 35.8 KB: the guard that the conversion stays
    away, without a chip. At 8 images the rule leaves the stage alone
    (there the plain program is the 14-fusion one, 23.1 KB a position,
    and every folded variant read worse)."""
    from flax import nnx

    from tpu_syncbn.models import resnet
    from tpu_syncbn.nn import BatchNorm2d

    shape = (n, 200, 336, 256)
    assert resnet.strip_count(n, shape[1]) == strips
    abstract = nnx.eval_shape(lambda: nnx.List([
        resnet.Bottleneck(256, 64, 1, BatchNorm2d, nnx.Rngs(0),
                          dtype=jnp.bfloat16) for _ in range(2)]))
    graphdef, params, rest = nnx.split(abstract, nnx.Param, ...)
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e), t)

    def loss(p, r, x):
        stage = nnx.merge(graphdef, p, r, copy=True)
        return resnet._run_stage(stage, x).astype(jnp.float32).mean()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 2))).lower(
        on_chip(params), on_chip(rest),
        jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=v5e)).compile()
    text = compiled.as_text()
    assert ("halo" in text) == (strips > 1)
    ops = _copies_and_pads(text)
    assert sum(op == "copy" for _, op in ops) <= 2, ops
    assert not [d for d, op in ops if op == "pad" and d.count(",") >= 3], ops
    kb_a_position = (compiled.cost_analysis()["bytes accessed"]
                     / (n * 200 * 336) / 1e3)
    assert kb_a_position < 45, kb_a_position


def test_stem_on_strips_keeps_space_to_batch_away(v5e):
    """RetinaNet's stem at 2 images of 800x1344 (``retinanet-train-b2``):
    the 7x7/2 convolution, BN, ReLU and the 3x3/2 max-pool in bf16, loss
    and the parameters' gradients in one program, on whole images and
    on the 4 row strips an image ``ResNet.features`` folds it onto.
    Whole images: 11 ``copy`` and 1 ``pad`` in the entry computation, 10
    of them of the 64-channel maps or of the input laid rows-first
    (space-to-batch), 6.06 GB accessed. On strips: 9 ``copy``, the
    input's layout, the halo's rows of 3 channels and the kernel, none
    of them space-to-batch, no ``pad``, 2.72 GB."""
    from flax import nnx

    from tpu_syncbn.models import resnet

    abstract = nnx.eval_shape(lambda: resnet.ResNet(
        resnet.Bottleneck, (1, 1, 1, 1), dtype=jnp.bfloat16,
        rngs=nnx.Rngs(0)))
    graphdef, params, rest = nnx.split(abstract, nnx.Param, ...)
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=v5e), t)
    x = jax.ShapeDtypeStruct((2, 800, 1344, 3), jnp.bfloat16, sharding=v5e)
    assert resnet.strip_count(2, 800, 4) == 4

    def compiled(stem):
        def loss(p, r, x):
            return stem(nnx.merge(graphdef, p, r, copy=True), x).astype(
                jnp.float32).mean()

        return jax.jit(jax.value_and_grad(loss)).lower(
            on_chip(params), on_chip(rest), x).compile()

    whole = compiled(lambda m, x: m._stem(x, 1))
    strips = compiled(lambda m, x: resnet._on_strips(m._stem, x, 4))
    assert "halo" in strips.as_text()
    # a copy or pad of a 64-channel map (not the 7x7 kernel), or of the
    # input laid rows-first, as the space-to-batch conversion lays it
    converted = lambda ops: [
        d for d, _ in ops
        if (d.endswith(",64") and not d.startswith("7,7,"))
        or d.startswith("800,")]
    whole_ops = _copies_and_pads(whole.as_text())
    ops = _copies_and_pads(strips.as_text())
    assert len(converted(whole_ops)) >= 8, whole_ops
    assert not converted(ops), ops
    assert not [op for _, op in ops if op == "pad"], ops
    gb = [c.cost_analysis()["bytes accessed"] / 1e9 for c in (whole, strips)]
    assert gb[0] > 5.5 and gb[1] < 3.0, gb
