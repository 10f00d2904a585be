"""The three flash kernels (under the causal mask, held to the text they
lowered to before the mask became a description, and under the
block-diffusion mask), the two gated-delta-rule kernels, the two
Mamba-2 scan kernels, the two selective-scan kernels, the two kernels of
the Mamba-1 gate, the two of latent attention's assembly and the two
short-convolution kernels compiled at
the benchmark's shapes for a v5e that is described and not attached (rehearsal 3 of the
on-chip-measurement guide; the recipe of
``perfbench/tests/test_chip_compile.py``).

Nothing runs, so nothing here is a measurement: Mosaic either takes the
kernels or refuses them (a slice off the tiling, more VMEM than a kernel
may use — what refused ``ops/fused_stem.py`` in PR 21), and the compiled
program names them as the per-layer metrics read them.  The topology is
described inside a fixture, never at import, and everything compiles in
this process (a child could not load libtpu beside it).
"""

import os
import re

import pytest


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # A program compiled for a described chip cannot be read back from
    # the persistent cache without the chip: keep these out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# [B, T, H, D] of gpt67_t8192 (B*H = 32), gpt67_t2048 (B*H = 128) and
# glm47flash_t8192 (B*H = 20 at head dim 256, 1024^2 auto blocks: twice
# the tiles and accumulators in VMEM).
@pytest.mark.parametrize("segments", [False, True],
                         ids=["causal", "segment_ids"])
@pytest.mark.parametrize("shape", [(1, 8192, 32, 128), (4, 2048, 32, 128),
                                   (1, 8192, 20, 256)],
                         ids=["t8192", "t2048", "t8192_d256"])
def test_kernels_compile_for_the_v5e(one_chip, shape, segments):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.flash_attention import flash_attention
    from horovod_tpu.telemetry import scopes

    b, t, _, _ = shape
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    seg = (jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=one_chip)
           if segments else None)

    def fwd_and_grads(q, k, v, do, seg):
        out, pull = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, True, None, None, None,
                                            False, seg), q, k, v)
        return (out,) + pull(do)

    text = jax.jit(fwd_and_grads).lower(x, x, x, x, seg).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for name in (scopes.FLASH_FWD, scopes.FLASH_BWD_DQ,
                 scopes.FLASH_BWD_DKV):
        assert f"%{name}." in text or f"%{name} " in text, name


def _mosaic_kernels(lowered_text):
    """The Mosaic modules of a lowered program's kernels as text without
    locations (a location names a file and a function, which a refactor
    moves and no compiler reads)."""
    import base64
    import json

    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    kernels = []
    for found in re.finditer(r'backend_config = "((?:[^"\\]|\\.)*)"',
                             lowered_text):
        config = json.loads(found.group(1).replace("\\22", '"').replace(
            "\\5C", "\\"))
        ctx = mlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        with ctx:
            kernels.append(ir.Module.parse(base64.b64decode(
                config["custom_call_config"]["body"])).operation.get_asm(
                    enable_debug_info=False))
    return kernels


# sha256 (16 digits) of the forward, dQ and dK+dV kernels under the causal
# mask as the parent of PR 46 lowered them (jax 0.9.0): the mask became a
# description there, and the causal instance had to stay the same kernels.
CAUSAL_KERNELS = {
    ("t8192", False): ("b9667c54a7895eec", "efa32740fe56ab06",
                       "ecee4ace4c58b667"),
    ("t8192", True): ("2528025464324c2a", "3c0d38feccbaffe1",
                      "9a83e91cb83f2f5a"),
    ("t2048", False): ("f20a3ed4e8843ec8", "8aeccb61deaf8141",
                       "16b2b80e904d0482"),
    ("t2048", True): ("bcfb36482363a47e", "d171228f3812379a",
                      "4890400eabe69a85"),
    ("t8192_d256", False): ("3144adc4c799daf7", "3f8275678f606b2b",
                            "07c87bd97c8d216f"),
    ("t8192_d256", True): ("cdb3cb7f6874971c", "c31146ee2ffc8fc9",
                           "2c1de0364af07a56")}
SHAPES = {"t8192": (1, 8192, 32, 128), "t2048": (4, 2048, 32, 128),
          "t8192_d256": (1, 8192, 20, 256)}


@pytest.mark.parametrize("name,segments", list(CAUSAL_KERNELS),
                         ids=[f"{n}-{'segment_ids' if s else 'causal'}"
                              for n, s in CAUSAL_KERNELS])
def test_the_causal_kernels_lower_as_they_did(one_chip, name, segments):
    """``causal=True`` through the mask's description gives, operation for
    operation, the kernels that branched on a bool."""
    import hashlib

    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.flash_attention import flash_attention

    shape = SHAPES[name]
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    seg = (jax.ShapeDtypeStruct(shape[:2], jnp.int32, sharding=one_chip)
           if segments else None)

    def fwd_and_grads(q, k, v, do, seg):
        out, pull = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, True, None, None, None,
                                            False, seg), q, k, v)
        return (out,) + pull(do)

    kernels = _mosaic_kernels(
        jax.jit(fwd_and_grads).lower(x, x, x, x, seg).as_text())
    assert tuple(hashlib.sha256(k.encode()).hexdigest()[:16]
                 for k in kernels) == CAUSAL_KERNELS[name, segments]


# [B, 2 L, H, D] of sdar30b_bd8k (8192 clean tokens beside their noised
# copy, blocks of 4, 1024-blocks), and a diffusion block as large as a
# sub-tile.
@pytest.mark.parametrize("shape,length,block",
                         [((1, 16384, 32, 128), 8192, 4),
                          ((1, 1024, 2, 128), 512, 256)],
                         ids=["sdar30b_bd8k", "block256"])
def test_kernels_compile_under_the_block_diffusion_mask(one_chip, shape,
                                                        length, block):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.flash_attention import (BlockDiffusion,
                                                 flash_attention)
    from horovod_tpu.telemetry import scopes

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    mask = BlockDiffusion(length, block)

    def fwd_and_grads(q, k, v, do):
        out, pull = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, mask, None, None, None,
                                            False), q, k, v)
        return (out,) + pull(do)

    text = jax.jit(fwd_and_grads).lower(x, x, x, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for name in (scopes.FLASH_FWD, scopes.FLASH_BWD_DQ,
                 scopes.FLASH_BWD_DKV):
        assert f"%{name}." in text or f"%{name} " in text, name


# One layer's recurrence of olmohybrid_t16k: B*H = 30 heads of key width
# 96 and value width 192 over 16384 tokens, bf16 with float32 gates.
def test_gated_delta_rule_kernels_compile_for_the_v5e(one_chip):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import gated_delta_rule as op
    from horovod_tpu.telemetry import scopes

    bh, t, dk, dv = 30, 16384, 96, 192
    packs = op.tiles(t)
    assert packs == op.TILE_PACKS

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    qk, vo = shape(bh, t, dk), shape(bh, t, dv)
    gates = shape(bh, t // op.ROWS, op.ROWS, dtype=jnp.float32)

    # The calls themselves, told to compile: the public function reads
    # the executing mesh, and this process's is the CPU.
    def fwd_and_grads(q, k, v, g, beta, do):
        plain = op._fwd_call(q, k, v, g, beta, packs=packs,
                             save_states=False, interpret=False)
        o, states = op._fwd_call(q, k, v, g, beta, packs=packs,
                                 save_states=True, interpret=False)
        return plain, o, op._bwd_call(q, k, v, g, beta, do, states,
                                      packs=packs, interpret=False)

    text = jax.jit(fwd_and_grads).lower(
        qk, qk, vo, gates, gates, vo).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for name, calls in ((scopes.GDN_SCAN_FWD, 2), (scopes.GDN_SCAN_BWD, 1)):
        assert len(re.findall(rf"%{name}[.\d]* = ", text)) == calls, name


# One layer's recurrence: of nemotron3s_t8192 (8 groups of 16 heads of 64
# channels, a state of 128, 8192 tokens), and of a group four times as
# wide, which VMEM holds a chunk a grid step; bf16 with float32 gates.
@pytest.mark.parametrize("t,g,r,p,chunks", [
    (8192, 8, 16, 64, 2), (1024, 1, 32, 128, 1)],
    ids=["nemotron3s_t8192", "wide_group"])
def test_mamba2_scan_kernels_compile_for_the_v5e(one_chip, t, g, r, p,
                                                 chunks):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import mamba2_scan as op
    from horovod_tpu.telemetry import scopes

    bsz, n, chunk = 1, 128, 128
    assert op.tiles(t, chunk, r, p, n) == chunks
    assert op.takes(jnp.zeros((bsz, t, 8)), chunk, r, p, n)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    x, bc = shape(bsz, t, g * r * p), shape(bsz, t, g * n)
    gates = shape(bsz, g, r, t, dtype=jnp.float32)
    skip = shape(g, 1, r * p, dtype=jnp.float32)
    dy = shape(bsz, t, g * r * p, dtype=jnp.float32)

    # The calls themselves, told to compile: the public function reads
    # the executing mesh, and this process's is the CPU.
    def fwd_and_grads(x, b_in, c_in, delta, log_a, d, dy):
        operands = (x, b_in, c_in, delta, log_a, d)
        plain = op._fwd_call(*operands, chunk=chunk, save_states=False,
                             interpret=False)
        y, states = op._fwd_call(*operands, chunk=chunk, save_states=True,
                                 interpret=False)
        return plain, y, op._bwd_call(*operands, y, dy, states, chunk=chunk,
                                      interpret=False)

    text = jax.jit(fwd_and_grads).lower(
        x, bc, bc, gates, gates, skip, dy).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for name, calls in ((scopes.SSM_SCAN_FWD, 2), (scopes.SSM_SCAN_BWD, 1)):
        assert len(re.findall(rf"%{name}[.\d]* = ", text)) == calls, name


# jamba2_t16k's selective scan: 5120 channels (five slabs of 1024) with a
# state of 16 over 16384 tokens in tiles of 256, ``x`` in bfloat16.
def test_selective_scan_kernels_compile_for_the_v5e(one_chip):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import selective_scan as op
    from horovod_tpu.telemetry import scopes

    bsz, t, channels, n = 1, 16384, 5120, 16
    tile = op.tiles(t, channels, n)
    assert tile == 256 and op.vmem_bytes(tile, n) <= op.VMEM_LIMIT
    assert op.takes(jnp.zeros((bsz, t, 8)), channels, n)
    rows = channels // op.LANES

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    x = shape(bsz, t, rows, op.LANES, dtype=jnp.bfloat16)
    wide = shape(bsz, t, rows, op.LANES)
    a, d = shape(n, rows, op.LANES), shape(rows, op.LANES)
    scalars = shape(bsz, t * n)

    # The calls themselves, told to compile: the public function reads
    # the executing mesh, and this process's is the CPU.
    def fwd_and_grads(x, dt, a, d, b_in, c_in, dy):
        operands = (x, dt, a, d, b_in, c_in)
        plain = op._fwd_call(*operands, tile=tile, save_states=False,
                             interpret=False)
        y, states = op._fwd_call(*operands, tile=tile, save_states=True,
                                 interpret=False)
        return plain, y, op._bwd_call(*operands, dy, states, tile=tile,
                                      interpret=False)

    text = jax.jit(fwd_and_grads).lower(
        x, wide, a, d, scalars, scalars, wide).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    for name, calls in ((scopes.MAMBA_SCAN_FWD, 2),
                        (scopes.MAMBA_SCAN_BWD, 1)):
        assert len(re.findall(rf"%{name}[.\d]* = ", text)) == calls, name


# jamba2_t16k's gate: ``y`` float32 as the scan leaves it (16384 tokens of
# 40 rows of 128 lanes), ``z`` and ``d out`` bfloat16 [16384, 5120], in
# tiles of 256 tokens; the strided sublane loads and stores that make the
# layout move have to be ones Mosaic takes.
def test_mamba_gate_kernels_compile_for_the_v5e(one_chip):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import mamba_gate as op
    from horovod_tpu.telemetry import scopes

    t, channels = 16384, 5120
    tile = op.tiles(t, channels)
    assert tile == 256 and op.vmem_bytes(tile, channels) <= op.VMEM_LIMIT
    assert op.takes(jnp.zeros((1, t, 8), jnp.bfloat16), channels)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    y = shape(1, t * channels // op.LANES, op.LANES, dtype=jnp.float32)
    z = shape(1, t, channels)

    # The calls themselves, told to compile: the public function reads
    # the executing mesh, and this process's is the CPU.
    def fwd_and_grads(y, z, dout):
        return (op._fwd_call(y, z, tile=tile, interpret=False),
                op._bwd_call(y, z, dout, tile=tile, interpret=False))

    text = jax.jit(fwd_and_grads).lower(y, z, z).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for name in (scopes.MAMBA_GATE_FWD, scopes.MAMBA_GATE_BWD):
        assert len(re.findall(rf"%{name}[.\d]* = ", text)) == 1, name


# glm47flash_t8192's assembly: 20 heads of 256 with 64 rotary over 8192
# tokens in tiles of 128, and the least widths the kernels take; a head of
# ``up`` is 448 lanes wide, so the slices at odd heads' lane offsets have to
# be ones Mosaic takes.
@pytest.mark.parametrize("t,heads,hd,rope,tile", [(8192, 20, 256, 64, 128),
                                                  (2048, 4, 128, 64, 128)],
                         ids=["glm47flash", "least"])
def test_mla_assemble_kernels_compile_for_the_v5e(one_chip, t, heads, hd,
                                                  rope, tile):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import mla_assemble as op
    from horovod_tpu.telemetry import scopes

    assert op.tiles(t, heads, hd, rope) == tile
    assert op.vmem_bytes(tile, heads, hd, rope) <= op.VMEM_LIMIT
    assert op.takes(jnp.zeros((1, t, 8), jnp.bfloat16), heads, hd, rope)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    table = shape(t, rope // 2, dtype=jnp.float32)
    folded = shape(heads, t, hd)

    # The calls themselves, told to compile: the public function reads
    # the executing mesh, and this process's is the CPU.
    def fwd_and_grads(q_proj, up, k_r, cos, sin, dq, dk, dv):
        return (op._fwd_call(q_proj, up, k_r, cos, sin, heads=heads,
                             tile=tile, interpret=False),
                op._bwd_call(dq, dk, dv, cos, sin, heads=heads, tile=tile,
                             interpret=False))

    text = jax.jit(fwd_and_grads).lower(
        shape(1, t, heads * hd), shape(1, t, heads * (2 * hd - rope)),
        shape(1, t, rope), table, table, folded, folded,
        folded).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for name in (scopes.MLA_ASSEMBLE_FWD, scopes.MLA_ASSEMBLE_BWD):
        assert len(re.findall(rf"%{name}[.\d]* = ", text)) == 1, name


# sdar30b_bd8k's and keyevl2_t16k's assembly: 32 heads over 4 key-value
# heads of 128 over 16384 positions in tiles of 512, each key-value head
# written once (the sparse route's kernels read the group in place) and once
# a query head (the flash kernels want equal counts), and the least widths.
@pytest.mark.parametrize("t,heads,kv_heads,hd,copies", [
    (16384, 32, 4, 128, 1), (16384, 32, 4, 128, 8), (2048, 2, 1, 256, 2)],
    ids=["keyevl2", "sdar30b", "least"])
def test_qk_assemble_kernels_compile_for_the_v5e(one_chip, t, heads,
                                                 kv_heads, hd, copies):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import qk_assemble as op
    from horovod_tpu.ops.selective_scan import VMEM_LIMIT
    from horovod_tpu.telemetry import scopes

    tile = op.tiles(t, heads, kv_heads, hd)
    assert tile == op.TILE == 512
    assert op.vmem_bytes(tile, heads, kv_heads, hd) <= VMEM_LIMIT
    assert op.takes(jnp.zeros((1, t, 8), jnp.bfloat16), heads, kv_heads, hd)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    table = shape(t, hd // 2, dtype=jnp.float32)
    scale = shape(1, hd, dtype=jnp.float32)
    q_proj, kv_proj = shape(1, t, heads * hd), shape(1, t, kv_heads * hd)
    dq, dkv = shape(heads, t, hd), shape(kv_heads * copies, t, hd)

    # The calls themselves, told to compile: the public function reads
    # the executing mesh, and this process's is the CPU.
    def fwd_and_grads(q_proj, k_proj, v_proj, q_scale, k_scale, cos, sin,
                      dq, dk, dv):
        return (op._fwd_call(q_proj, k_proj, v_proj, q_scale, k_scale, cos,
                             sin, heads=heads, copies=copies, eps=1e-6,
                             tile=tile, interpret=False),
                op._bwd_call(dq, dk, dv, q_proj, k_proj, q_scale, k_scale,
                             cos, sin, eps=1e-6, tile=tile,
                             interpret=False))

    text = jax.jit(fwd_and_grads).lower(
        q_proj, kv_proj, kv_proj, scale, scale, table, table, dq, dkv,
        dkv).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for name in (scopes.QK_ASSEMBLE_FWD, scopes.QK_ASSEMBLE_BWD):
        assert len(re.findall(rf"%{name}[.\d]* = ", text)) == 1, name


# The short convolutions of one mixer layer: olmohybrid_t16k's q (or k: 30
# heads of 96 with the L2 norm, written head-major) and v (30 heads of 192),
# nemotron3s_t8192's xBC with its bias (three token-major outputs),
# jamba2_t16k's xs with its bias (one output of the whole width).
@pytest.mark.parametrize("t,channels,kw,tile", [
    (16384, 2880, dict(head_dim=96, norm_scale=96 ** -0.5), 512),
    (16384, 5760, dict(head_dim=192), 512),
    (8192, 10240, dict(widths=(8192, 1024, 1024)), 256),
    (16384, 5120, dict(widths=(5120,)), None)],
    ids=["olmohybrid_q", "olmohybrid_v", "nemotron3s_xBC", "jamba2_xs"])
def test_short_conv_kernels_compile_for_the_v5e(one_chip, t, channels, kw,
                                                tile):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import short_conv as op
    from horovod_tpu.telemetry import scopes

    head_dim, widths = kw.get("head_dim"), kw.get("widths")
    if tile is None:        # whatever the kernels choose at this width
        tile = op.tiles(t, channels, 4, head_dim, widths)
        assert tile in (256, 512)
    assert op.tiles(t, channels, 4, head_dim, widths) == tile
    plan = op._Plan(widths or (channels,), head_dim, kw.get("norm_scale"),
                    1e-6, widths is not None)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    x, w = shape(1, t, channels), shape(4, channels, dtype=jnp.float32)
    bias = shape(1, channels, dtype=jnp.float32) if plan.bias else None
    dys = ((shape(channels // head_dim, t, head_dim),) if head_dim
           else tuple(shape(1, t, width) for width in widths))

    # The calls themselves, told to compile: the public function reads
    # the executing mesh, and this process's is the CPU.
    def fwd_and_grads(x, w, bias, dys):
        return (op._fwd_call(x, w, bias, plan=plan, interpret=False),
                op._bwd_call(x, w, bias, dys, plan=plan, interpret=False))

    text = jax.jit(fwd_and_grads).lower(x, w, bias, dys).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for name in (scopes.SHORT_CONV_FWD, scopes.SHORT_CONV_BWD):
        assert len(re.findall(rf"%{name}[.\d]* = ", text)) == 1, name


# The gated norm of one mixer layer: nemotron3s_t8192's (``y`` float32 in
# 8 groups of 1024, gate first) and olmohybrid_t16k's (``o`` head-major in
# 30 heads of 192, norm first).
@pytest.mark.parametrize("t,width,group,gate_first,head_major,tile", [
    (8192, 8192, 1024, True, False, 128),
    (16384, 5760, 192, False, True, 256)],
    ids=["nemotron3s", "olmohybrid"])
def test_gated_norm_kernels_compile_for_the_v5e(one_chip, t, width, group,
                                                gate_first, head_major,
                                                tile):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import gated_norm as op
    from horovod_tpu.telemetry import scopes

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    x = (shape(width // group, t, group) if head_major
         else shape(1, t, width, dtype=jnp.float32))
    z, scale = shape(1, t, width), shape(1, width, dtype=jnp.float32)
    assert op.tiles(t, width, group, head_major, x.dtype.itemsize,
                    2) == tile
    plan = op._Plan(width, group, gate_first, head_major, 1e-5)

    # The calls themselves, told to compile: the public function reads
    # the executing mesh, and this process's is the CPU.
    def fwd_and_grads(x, z, scale, dout):
        return (op._fwd_call(x, z, scale, plan=plan, interpret=False),
                op._bwd_call(x, z, scale, dout, plan=plan, interpret=False))

    text = jax.jit(fwd_and_grads).lower(x, z, scale, z).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for name in (scopes.GATED_NORM_FWD, scopes.GATED_NORM_BWD):
        assert len(re.findall(rf"%{name}[.\d]* = ", text)) == 1, name


# One layer's learned sparse attention of keyevl2_t16k: 32 query heads
# over 4 key-value heads of 128, 16 indexer heads of 64 over one key head,
# the 2048 best of up to 16384 keys a query; as it stands and under
# ``jax.checkpoint`` (the cell's ``remat`` ``full``), which runs every
# forward kernel again but the indexer's loss's: nothing the backward pass
# reads comes out of ``dsa_probs``.
@pytest.mark.parametrize("recomputed", [False, True],
                         ids=["plain", "checkpoint"])
def test_sparse_attention_kernels_compile_for_the_v5e(one_chip, recomputed):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import sparse_attention as op
    from horovod_tpu.telemetry import scopes

    t = 16384
    assert op.attention_block(t) == 512

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    operands = (shape(1, t, 32, 128), shape(1, t, 4, 128),
                shape(1, t, 4, 128), shape(1, t, 16, 64), shape(1, t, 64),
                shape(1, t, 16))

    # The route told to take the kernels and to compile them: left to
    # itself it reads the executing mesh, and this process's is the CPU.
    def total(*operands):
        o, kl = op.dsa_attention(*operands, topk=2048, index_scale=2.0 ** -5,
                                 kernels=True, interpret=False)
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(kl)

    if recomputed:
        total = jax.checkpoint(total)
    text = jax.jit(jax.value_and_grad(total, argnums=tuple(range(6)))).lower(
        *operands).compile().as_text()
    again = 2 if recomputed else 1
    calls = {scopes.DSA_INDEX_FWD: again, scopes.DSA_SELECT_KERNEL: again,
             scopes.DSA_FWD: again, scopes.DSA_PROBS: 1,
             scopes.DSA_INDEX_BWD: 1, scopes.DSA_BWD_DQ: 1,
             scopes.DSA_BWD_DKV: 1}
    assert (text.count('custom_call_target="tpu_custom_call"')
            == sum(calls.values()))
    for name, times in calls.items():
        assert len(re.findall(rf"%{name}[.\d]* = ", text)) == times, name
    # The only [T, T] float32 arrays are the scores and their gradient:
    # no instruction reads or writes one but the kernels (and the pick of
    # dQ's second result).
    touching = re.findall(rf"%([\w.\-]+) = [^\n]*f32\[1,{t},{t}\]", text)
    assert {name.rsplit(".", 1)[0] for name in touching} == {
        scopes.DSA_INDEX_FWD, scopes.DSA_SELECT_KERNEL, scopes.DSA_PROBS,
        scopes.DSA_BWD_DQ, scopes.DSA_INDEX_BWD, "pallas_call"}, touching
    picks = [line for line in text.splitlines()
             if re.match(r"\s*%pallas_call[.\d]* = ", line)
             and f"f32[1,{t},{t}]" in line]
    assert all(" get-tuple-element(" in line for line in picks), picks
