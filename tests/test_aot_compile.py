"""The main path's Pallas kernels compile for the real chip — without one.

libtpu's compiler is installed wherever JAX's TPU wheel is, and compiles
for a chip that is described, not attached (on-chip-measurement guide,
§2.3). Interpret-mode tests cannot see what Mosaic refuses (unaligned
slices, too much VMEM, an unsupported op); these cases can, for a second
or two each and no chip time. Nothing runs, so they say nothing about
results or speed — ``chip_smoke.py`` does that on the chip.

``interpret_mode`` is patched to ``False`` here, in the test: the kernels
pick interpret mode from the backend, and this process's backend is the
CPU. The persistent compile cache is off around the compiles (an entry
written for a described chip cannot be read back without one).
"""

import functools
import os
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from deeplearning_tpu.core.registry import MODELS
from deeplearning_tpu.models.classification import swin
from deeplearning_tpu.models.language import glm_moe_lite, granite, mellum
from deeplearning_tpu.ops.pallas import choice_sum
from deeplearning_tpu.ops.pallas import flash_attention as flash
from deeplearning_tpu.ops.pallas import global_attention as global_attn
from deeplearning_tpu.ops.pallas import nms as pallas_nms
from deeplearning_tpu.ops.pallas import ssd_scan
from deeplearning_tpu.ops.pallas import window_attention as window
from deeplearning_tpu.parallel import moe

TOPOLOGY = "v5e:2x2"


@pytest.fixture(scope="module")
def chip():
    """Sharding on one described v5e chip; skips where libtpu cannot
    describe the topology (not installed, or its lock file is held by
    another process)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name=TOPOLOGY)
    except Exception as exc:  # noqa: BLE001 - any refusal means skip
        pytest.skip(f"cannot describe a {TOPOLOGY} topology here: {exc}")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_mode(monkeypatch):
    for module in (choice_sum, flash, global_attn, pallas_nms, ssd_scan,
                   window):
        monkeypatch.setattr(module, "interpret_mode", lambda: False)


def _window_case(windows, heads, n_mask, dtype, grad=False):
    """Fused window attention at a Swin stage's shape, forward or forward +
    the fused backward (``dqkv`` and the bias gradient)."""
    def forward(qkv, bias, mask=None):
        return window.window_attention(qkv, bias, mask, heads=heads)

    def backward(qkv, bias, mask=None):
        return jax.grad(lambda a, b: jnp.sum(
            forward(a, b, mask).astype(jnp.float32)), (0, 1))(qkv, bias)

    return (backward if grad else forward,
            [((windows, 49, 3 * heads * 32), dtype),
             ((heads, 49, 49), jnp.float32)]
            + ([((n_mask, 49, 49), jnp.float32)] if n_mask else []))


def _global_case(batch, tokens, heads, d, dtype, grad=False):
    """Fused global attention at a ViT's shape, forward or forward + the
    fused backward (``dqkv``)."""
    def forward(qkv):
        return global_attn.global_attention(qkv, heads=heads)

    def backward(qkv):
        return jax.grad(lambda a: jnp.sum(forward(a).astype(jnp.float32)))(
            qkv)

    return (backward if grad else forward,
            [((batch, tokens, 3 * heads * d), dtype)])


def _flash_grad(q, k, v):
    return jax.grad(lambda *qkv: jnp.sum(
        flash.flash_attention(*qkv).astype(jnp.float32)), (0, 1, 2))(q, k, v)


def _causal_grad(q, k, v):
    """The decoder's attention core (MLA at its published head width), fused,
    forward + backward."""
    return jax.grad(lambda *qkv: jnp.sum(flash.causal_attention(
        *qkv, 256 ** -0.5, "fused").astype(jnp.float32)), (0, 1, 2))(q, k, v)


def _gqa_grad(window):
    """Mellum2's attention core (32 query heads reading 4 key/value heads of
    128), fused, forward + backward; with a window, a sliding layer's."""
    def grad(q, k, v):
        return jax.grad(lambda *qkv: jnp.sum(flash.causal_attention(
            *qkv, 128 ** -0.5, "fused", window).astype(jnp.float32)),
            (0, 1, 2))(q, k, v)
    return grad


_GQA_QKV = [((1, 32, 8192, 128), jnp.bfloat16)] \
    + [((1, 4, 8192, 128), jnp.bfloat16)] * 2


def _grouped_grad(rows, experts, sizes):
    """The routed experts' grouped product by the Pallas route, forward +
    both gradients."""
    return jax.grad(lambda x, w: jnp.sum(moe.grouped_matmul(
        x, w, sizes, "megablox").astype(jnp.float32)), (0, 1))(rows, experts)


def _scan(x, dt, a, b, c):
    """Granite 4.0-H's chunked scan (chunk 256), the forward kernel."""
    return ssd_scan.ssd_scan(x, dt, a, b, c, 256)


def _scan_grad(*args):
    """The same, forward + the backward kernel: the gradients of x, Δ, A, B
    and C."""
    return jax.grad(lambda *t: jnp.sum(_scan(*t).astype(jnp.float32)),
                    range(5))(*args)


# one row of 8,192 tokens, 64 heads of 64, one group, a state of 128
_SCAN = [((1, 8192, 64, 64), jnp.bfloat16), ((1, 8192, 64), jnp.float32),
         ((64,), jnp.float32)] + [((1, 8192, 1, 128), jnp.bfloat16)] * 2


_NMS = functools.partial(pallas_nms.nms_pallas, iou_threshold=0.5,
                         max_out=100)
_VIT_QKV = [((8, 12, 197, 64), jnp.bfloat16)] * 3
_LONG_QKV = [((2, 12, 1024, 64), jnp.bfloat16)] * 3

CASES = {
    # Swin-T stage 1 at batch 32: 56x56 tokens, 3 heads of 32
    "window_swin_t_s1_bf16_masked": _window_case(2048, 3, 64, jnp.bfloat16),
    "window_swin_t_s1_bf16": _window_case(2048, 3, 0, jnp.bfloat16),
    "window_swin_t_s1_f32": _window_case(2048, 3, 0, jnp.float32),
    # Swin-B stage 3 at batch 32: 14x14 tokens, 16 heads of 32
    "window_swin_b_s3_bf16_masked": _window_case(128, 16, 4, jnp.bfloat16),
    # Swin-T at batch 128, the benchmark's cell, forward + the fused backward:
    # the first stage (3 heads: an odd one), the third (12 heads) and the
    # last (24 heads, no mask)
    "window_swin_t_s0_b128_grad_masked": _window_case(
        8192, 3, 64, jnp.bfloat16, grad=True),
    "window_swin_t_s2_b128_grad_masked": _window_case(
        512, 12, 4, jnp.bfloat16, grad=True),
    "window_swin_t_s3_b128_grad": _window_case(
        128, 24, 0, jnp.bfloat16, grad=True),
    # Swin-L's last stage, the widest rows a program holds (48 heads,
    # C = 1,536), and the 56-px configs' 16 windows of head width 32 in
    # float32 (8 sublanes a tile)
    "window_swin_l_s3_b128_grad": _window_case(
        128, 48, 0, jnp.bfloat16, grad=True),
    "window_swin_mini_s0_f32_grad_masked": _window_case(
        512, 2, 16, jnp.float32, grad=True),
    # ViT-B/16 at batch 128, the benchmark's cell (197 tokens, 12 heads of
    # 64), forward + the fused backward; the same served at batch 8 in
    # float32; ViT-L/16's 16 heads; 50 tokens (patch 32) at a ragged batch;
    # heads of 32 at the widest covered sequence
    "global_vit_b16_b128_grad": _global_case(
        128, 197, 12, 64, jnp.bfloat16, grad=True),
    "global_vit_b16_b8_f32": _global_case(8, 197, 12, 64, jnp.float32),
    "global_vit_l16_b64_grad": _global_case(
        64, 197, 16, 64, jnp.bfloat16, grad=True),
    "global_vit_b32_b30_grad": _global_case(
        30, 50, 12, 64, jnp.bfloat16, grad=True),
    "global_d32_n256_grad": _global_case(
        16, 256, 16, 32, jnp.bfloat16, grad=True),
    "nms_pallas_n1024": (_NMS, [((1024, 4), jnp.float32),
                                ((1024,), jnp.float32)]),
    "nms_pallas_n4096": (_NMS, [((4096, 4), jnp.float32),
                                ((4096,), jnp.float32)]),
    # ViT-B/16's attention shape: 197 tokens, 12 heads of 64
    "flash_fwd_n197": (flash.flash_attention, _VIT_QKV),
    "flash_grad_n197": (_flash_grad, _VIT_QKV),
    # 1,024 tokens: whole 128-wide blocks, no padded keys
    "flash_fwd_n1024": (flash.flash_attention, _LONG_QKV),
    "flash_grad_n1024": (_flash_grad, _LONG_QKV),
    # GLM-4.7-Flash's attention core: 4,096 tokens, causal, 20 heads of 256
    # (one sequence of the cell's four), blocks of 512
    "flash_causal_n4096_d256_grad": (
        _causal_grad, [((1, 20, 4096, 256), jnp.bfloat16)] * 3),
    # Mellum2's: 8,192 tokens (one sequence of the cell's two), a sliding
    # layer's window of 1,024 and a full layer
    "flash_sliding_gqa_n8192_d128_grad": (_gqa_grad(1024), _GQA_QKV),
    "flash_full_gqa_n8192_d128_grad": (_gqa_grad(None), _GQA_QKV),
    # its routed experts: 16 held, 2,304 -> gate and up of 896 (tiles of 768
    # deep and 896 wide) and 896 -> 2,304, a quarter of the cell's row buffer
    "grouped_megablox_16x2304x1792_grad": (
        _grouped_grad, [((16384, 2304), jnp.bfloat16),
                        ((16, 2304, 1792), jnp.bfloat16), ((16,), jnp.int32)]),
    "grouped_megablox_16x896x2304_grad": (
        _grouped_grad, [((16384, 896), jnp.bfloat16),
                        ((16, 896, 2304), jnp.bfloat16), ((16,), jnp.int32)]),
    # GLM-4.7-Flash's routed experts: 8 held, 2,048 -> gate and up of 1,536,
    # half the cell's row buffer
    "grouped_megablox_8x2048x3072_grad": (
        _grouped_grad, [((8192, 2048), jnp.bfloat16),
                        ((8, 2048, 3072), jnp.bfloat16), ((8,), jnp.int32)]),
    # the token side of both cells' row buffers (tokens x top_k slots, width,
    # buffer rows): Mellum2's 16,384 x 8 over 65,536 rows of 2,304, GLM's
    # 16,384 x 4 over 16,384 of 2,048; a packing call and the gather kernel
    "choice_sum_16384x8_2304_65536": (
        choice_sum.choice_sum, [((65536, 2304), jnp.bfloat16),
                                ((16384, 8), jnp.int32), ((16384, 8), bool),
                                ((16384, 8), jnp.float32)]),
    "choice_sum_16384x4_2048_16384": (
        choice_sum.choice_sum, [((16384, 2048), jnp.bfloat16),
                                ((16384, 4), jnp.int32), ((16384, 4), bool),
                                ((16384, 4), jnp.float32)]),
    # Granite 4.0-H Micro's Mamba-2 scan at the cell's row, value and
    # gradient
    "ssd_scan_granite4_n8192": (_scan, _SCAN),
    "ssd_scan_granite4_n8192_grad": (_scan_grad, _SCAN),
}
# cases whose compiled program holds an exact number of Mosaic calls: the
# decoder cores' value and gradient, the forward kernel and the one backward
# kernel (``dq`` summed beside ``dk`` and ``dv``)
MOSAIC_CALLS = {
    "flash_causal_n4096_d256_grad": 2,
    "flash_sliding_gqa_n8192_d128_grad": 2,
    "flash_full_gqa_n8192_d128_grad": 2,
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, chip, compiled_mode):
    fn, shapes = CASES[name]
    specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
             for shape, dtype in shapes]
    # conftest pins matmul precision to "highest" for the CPU suite; the
    # chip runs the kernels at the default, and Mosaic refuses an fp32
    # contraction of bf16 operands
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(fn).lower(*specs).compile()
    calls = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    assert calls, f"{name}: the compiled program holds no Mosaic kernel"
    if name in MOSAIC_CALLS:
        assert calls == MOSAIC_CALLS[name], (name, calls)


def test_compact_expert_layer_compiles_for_v5e(chip, monkeypatch):
    """GLM-4.7-Flash's expert layer as the benchmark's cell runs it (16,384
    tokens of width 2,048, 8 of 64 experts held, 8 x 2,048 x 3,072 and 8 x
    1,536 x 2,048 expert kernels, a row buffer of 16,384 rows), forward +
    backward: the grouped products are Mosaic kernels, 2 forward and 4
    backward where the rows fit the buffer in one pass (the forward's dead
    copies inside ``jax.vjp`` are gone), and 2, then 2 again + 4 in the loops
    over several passes."""
    # this process's backend is the CPU: the route a TPU would take
    monkeypatch.setattr(
        moe, "grouped_route", lambda rows, initializing=False:
        "ragged_dot" if initializing else "megablox")
    layer = moe.HeldExpertsMlp(shared_experts=0)
    x = jax.ShapeDtypeStruct((4, 4096, 2048), jnp.bfloat16, sharding=chip)
    assert moe.buffer_capacity(4 * 4096 * layer.top_k, 8, 64) == 16384
    shapes = jax.eval_shape(lambda: layer.init(
        jax.random.key(0), jnp.zeros((1, 128, 2048), jnp.bfloat16)))["params"]
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=chip), shapes)

    def loss(p, x):
        return jnp.mean(layer.apply({"params": p}, x).astype(jnp.float32) ** 2)

    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            params, x).compile()
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 6 + 8


# the cells' expert layers: (constructor keywords, input (rows, tokens, width))
EXPERT_LAYERS = {
    "mellum2": (dict(num_experts=64, held=16, top_k=8, hidden=896,
                     shared_experts=0, route=moe.softmax_route), (2, 8192, 2304)),
    "glm47_flash": (dict(shared_experts=0), (4, 4096, 2048)),
}
_MOSAIC_CALL = re.compile(
    r'custom_call_target="tpu_custom_call".*op_name="([^"]*)"')
# a gather of the token side: the forward's under ``moe_combine``, the
# backward's under ``moe_dispatch`` (the rows side's are the other way round)
_TOKEN_GATHER = re.compile(
    r'= \w+\[(\d+),(\d+)\].*op_name="[^"]*'
    r'jit\(_pass_(?:fwd\)/moe_combine|bwd\)/moe_dispatch)/gather"')


@pytest.mark.parametrize("name", sorted(EXPERT_LAYERS))
def test_the_expert_layers_token_side_is_the_kernel(name, chip, compiled_mode,
                                                    monkeypatch):
    """Each cell's expert layer, value and gradient, compiled for the
    described v5e with both kernels' routes a TPU takes: the token side is
    a packing call and the gather kernel in each direction of each branch (8
    Mosaic calls beside megablox's 14), each under ``moe_combine`` (forward)
    or ``moe_dispatch`` (backward), where ``moe_dispatch_ms*`` counts it, and
    no gather of a row a token, ``(T, D)``, stands on the token side (the
    lax path has ``top_k`` of them a direction: 8 in Mellum2's, 4 in GLM's)."""
    keywords, (rows, tokens, width) = EXPERT_LAYERS[name]
    monkeypatch.setattr(
        moe, "grouped_route", lambda rows, initializing=False:
        "ragged_dot" if initializing else "megablox")
    layer = moe.HeldExpertsMlp(**keywords)
    x = jax.ShapeDtypeStruct((rows, tokens, width), jnp.bfloat16,
                             sharding=chip)
    shapes = jax.eval_shape(lambda: layer.init(
        jax.random.key(0), jnp.zeros((1, 128, width), jnp.bfloat16)))["params"]
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=chip), shapes)

    def loss(p, x):
        return jnp.mean(layer.apply({"params": p}, x).astype(jnp.float32) ** 2)

    with jax.default_matmul_precision("default"):
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            params, x).compile().as_text()
    calls = [m.group(1) for m in map(_MOSAIC_CALL.search, text.splitlines())
             if m]
    token_side = [c for c in calls if "/choice_sum" in c]
    assert len(calls) == 14 + 8 and len(token_side) == 8, calls
    assert all(re.search(r"/moe_(combine|dispatch)/", c) for c in token_side)
    gathers = [m.groups() for m in map(_TOKEN_GATHER.search,
                                       text.splitlines()) if m]
    assert (str(rows * tokens), str(width)) not in gathers, gathers


# (registry entry of the cell, un-remat'd block, what its constructor takes
# after the config, (rows, tokens), Mosaic calls of value and gradient under a
# plain ``nn.remat``): GLM-4.7-Flash's dense block holds the attention core's
# forward, forward again and the one backward kernel; Mellum2's sliding block
# those three, 16 grouped products of its expert layer and 8 calls of its
# token side (a packing and a gather in each direction of each branch);
# Granite 4.0-H's attention block, heads of 64, the core's three. One row of
# 1,024 tokens (2,048 for Mellum2's window of 1,024) holds the same calls,
# under either wrapper, as the cells' (4, 4,096), (2, 8,192) and (1, 8,192),
# and the Mellum2 block compiles in three fifths of the time
DECODER_BLOCKS = {
    "glm47_flash_dense": ("glm47_flash_ep8", glm_moe_lite.DecoderBlock, True,
                          (1, 1024), 3),
    "mellum2_sliding": ("mellum2_ep4", mellum.MellumBlock, mellum.SLIDING,
                        (1, 2048), 27),
    "granite4_attention": ("granite4_h_micro_vocab8", granite.GraniteBlock,
                           granite.ATTENTION, (1, 1024), 3),
}


@pytest.mark.parametrize("name", sorted(DECODER_BLOCKS))
def test_a_decoder_block_keeps_its_attention_core(name, chip, compiled_mode,
                                                  monkeypatch):
    """One block of each language family, value and gradient, compiled for
    the described v5e: under ``decoder.remat_block``
    the program holds exactly one Mosaic call fewer than under a plain
    ``nn.remat`` of the same block: the attention core's forward kernel is
    not run a second time in the backward pass, and nothing else changes."""
    entry, block_cls, kind, (rows, tokens), plain_calls = DECODER_BLOCKS[name]
    monkeypatch.setattr(
        moe, "grouped_route", lambda rows, initializing=False:
        "ragged_dot" if initializing else "megablox")
    cfg = MODELS.build(entry).cfg
    x = jax.ShapeDtypeStruct((rows, tokens, cfg.hidden_size), jnp.bfloat16,
                             sharding=chip)

    def mosaic_calls(wrap):
        block = wrap(block_cls)(cfg, kind, jnp.bfloat16)
        shapes = jax.eval_shape(lambda: block.init(
            jax.random.key(0),
            jnp.zeros((1, 128, cfg.hidden_size), jnp.bfloat16)))["params"]
        params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), shapes)

        def loss(p, x):
            return jnp.mean(
                block.apply({"params": p}, x).astype(jnp.float32) ** 2)
        with jax.default_matmul_precision("default"):
            compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
                params, x).compile()
        return compiled.as_text().count(
            'custom_call_target="tpu_custom_call"')

    from deeplearning_tpu.models.language.decoder import remat_block
    assert mosaic_calls(nn.remat) == plain_calls
    assert mosaic_calls(remat_block) == plain_calls - 1


# (token grid, C, heads) of Swin-T's four stages; a stage's second block is
# the shifted one (the last stage's grid is one window: no shift)
SWIN_T_STAGES = [(56, 96, 3), (28, 192, 6), (14, 384, 12), (7, 768, 24)]
_KERNEL_BOUNDARY = re.compile(
    r'op_name="[^"]*(?:window_attention_fwd/pallas_call|jit\(_backward\)'
    r'|attn/qkv/dot_general)"')


@pytest.mark.parametrize("stage", range(4))
def test_no_layout_copy_at_the_window_kernels(stage, chip, compiled_mode):
    """A shifted ``SwinBlock``'s gradient at the benchmark's batch, compiled
    for the described v5e: the kernels take the token-major rows XLA keeps,
    so no ``copy`` stands between the qkv matmul and the forward kernel,
    after the forward kernel, or after the backward kernel's ``dqkv`` (a
    row-major operand had one at each, 2.1 GB a Swin-T step: PR 31)."""
    res, c, heads = SWIN_T_STAGES[stage]
    block = swin.SwinBlock(c, (res, res), heads, 7, 3, dtype=jnp.bfloat16,
                           name=f"stage{stage}_block1")
    x = jax.ShapeDtypeStruct((128, res * res, c), jnp.bfloat16, sharding=chip)
    # ``init`` takes the lax path whatever the backend; shapes alone
    shapes = jax.eval_shape(lambda: block.init(
        jax.random.key(0), jnp.zeros((1, res * res, c), jnp.bfloat16)))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=chip), shapes)

    def loss(p, x):
        return jnp.mean(block.apply(p, x).astype(jnp.float32) ** 2)

    with jax.default_matmul_precision("default"):
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            params, x).compile().as_text()
    assert text.count("tpu_custom_call") >= 2, "no fused kernels in the block"
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= \S+ copy\(", line)
              and _KERNEL_BOUNDARY.search(line)]
    assert not copies, copies


# a float32 array whose last two dimensions are a chunk's (256, 256): the lax
# path's decays and their gradients
_CHUNK_SQUARE = re.compile(r"f32\[[\d,]*256,256\]")


def test_a_granite_mamba_block_scans_in_the_kernels(chip, compiled_mode):
    """A Granite 4.0-H Mamba block at the cell's row (8,192 tokens of
    2,048), value and gradient, compiled for the described v5e: the scan is
    two Mosaic calls, the forward and the backward kernel, both under the
    scope ``ssd_scan`` that ``ssd_scan_roofline`` reads, and no float32
    (..., 256, 256) array of a chunk's decays stands in the program (the lax
    path's holds dozens)."""
    cfg = MODELS.build("granite4_h_micro_vocab8").cfg
    block = granite.GraniteBlock(cfg, granite.MAMBA, jnp.bfloat16)
    x = jax.ShapeDtypeStruct((1, 8192, cfg.hidden_size), jnp.bfloat16,
                             sharding=chip)
    shapes = jax.eval_shape(lambda: block.init(
        jax.random.key(0),
        jnp.zeros((1, 256, cfg.hidden_size), jnp.bfloat16)))["params"]
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=chip), shapes)

    def loss(p, x):
        return jnp.mean(block.apply({"params": p}, x).astype(jnp.float32) ** 2)

    with jax.default_matmul_precision("default"):
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            params, x).compile().as_text()
    calls = [m.group(1) for m in map(_MOSAIC_CALL.search, text.splitlines())
             if m]
    assert [c.rsplit("/", 2)[-2] for c in calls] == [
        "ssd_scan_fwd", "ssd_scan_bwd"], calls
    assert all("/ssd_scan/" in c for c in calls), calls
    assert not _CHUNK_SQUARE.search(text)
