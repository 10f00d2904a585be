"""The names the compiled SPMD step carries (docs/timeline.md, "The
compiled step in XProf/Perfetto"): module name, scopes of the one
vocabulary (``horovod_tpu/telemetry/scopes.py``) under forward, backward
and recomputation, and no executed instruction without one.  Read from
``compiled.as_text()`` of tiny steps on 4 of the 8 virtual CPU devices.
"""

import itertools
import pathlib
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from horovod_tpu.telemetry import scopes
from perfbench import scope_reduce

OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
# Compiler-made plumbing is not held to a scope; nor is a fusion that
# holds nothing else.
PLUMBING = {"parameter", "constant", "broadcast", "copy", "bitcast",
            "tuple", "get-tuple-element", "iota"}
HELD = {"dot", "convolution", "fusion", "custom-call"}


def _op_names(text):
    return set(OP_NAME.findall(text))


def _under(names, scope, *marks, without=()):
    """Some op_name holds ``scope`` as a whole component, every mark, and
    none of ``without``."""
    part = re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:$|[/)])")
    return any(part.search(n) and all(m in n for m in marks)
               and not any(w in n for w in without) for n in names)


def _unplaced(text):
    """The executed dots, convolutions, fusions, custom calls and
    collectives of the step that the benchmark's rules
    (``perfbench/scope_reduce.py``) cannot give a phase and a scope: the
    program-side twin of ``unattributed_ms_per_step``."""
    hlo = scope_reduce.parse_hlo(text)
    instructions, computations, _ = hlo
    fused = {c for i in instructions.values() if i.opcode == "fusion"
             for c in i.calls}
    out = []
    for computation, names in computations.items():
        if computation in fused:
            continue
        for name in names:
            i = instructions[name]
            if not (i.opcode in HELD
                    or scope_reduce.trace_reduce.COLLECTIVE.match(i.opcode)):
                continue
            inside = {instructions[n].opcode
                      for c in i.calls for n in computations.get(c, ())}
            if i.opcode == "fusion" and inside <= PLUMBING:
                continue
            phase, scope, _, _ = scope_reduce.classify(name, hlo)
            if phase == "unattributed" or not scope:
                out.append((name, i.opcode, i.op_name))
    return out


MOE = dict(positions="rope", qk_norm=True, tie_embeddings=False,
           mlp="swiglu", n_experts=4, experts_per_token=2, d_expert=64,
           router_aux_coef=0.01, router_z_coef=0.001)


def _lm_step_text(attention, remat, shard_optimizer, seq_axis=None,
                  **fields):
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.topology import build_mesh

    cfg = tfm.TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, max_seq=128,
        dtype=jnp.bfloat16, **{"n_heads": 2, "d_ff": 128, **fields})
    axes = ("data", seq_axis) if seq_axis else ("data",)
    mesh = build_mesh(axes=axes, shape=(2, 2) if seq_axis else None,
                      devices=jax.devices()[:4])
    optimizer = optax.sgd(0.01, momentum=0.9)
    step, _, _ = tfm.make_train_step(
        cfg, optimizer, mesh, attention=attention, remat=remat,
        seq_axis=seq_axis, shard_optimizer=shard_optimizer)
    params = tfm.init_abstract(cfg)
    opt_state = jax.eval_shape(
        step.init if shard_optimizer else optimizer.init, params)
    tokens = jax.ShapeDtypeStruct((8, 128), jnp.int32)
    batch = (tokens, tokens)
    if cfg.diffusion_block:
        batch = (tokens, jax.ShapeDtypeStruct((8, 128), bool),
                 jax.ShapeDtypeStruct((8, 128 // cfg.diffusion_block),
                                      jnp.float32))
    return step.lower(params, opt_state, *batch).compile().as_text()


ROUTE = {"local": scopes.ATTN_LOCAL, "flash": scopes.ATTN_FLASH,
         "ring": scopes.ATTN_RING, "ulysses": scopes.ATTN_ULYSSES}


def _check_lm(text, attention, remat, shard_optimizer,
              recurrence_recomputes=False):
    assert text.startswith(f"HloModule jit_{scopes.LM_TRAIN_STEP},")
    names = _op_names(text)
    assert all(n.startswith(f"jit({scopes.LM_TRAIN_STEP})")
               for n in names if n.startswith("jit("))
    model = [scopes.EMBED, scopes.ATTN_QKV, scopes.ATTN_OUT,
             ROUTE[attention], scopes.MLP, scopes.HEAD, scopes.LOSS]
    for scope in model:
        assert _under(names, scope, "jvp(", without=("transpose(",)), scope
        assert _under(names, scope, "transpose("), scope
    for i in range(2):
        assert _under(names, scopes.LAYER % i, "jvp("), i
    if remat == "full":
        # Mixer and MLP are recomputed apart (transformer._remat_wrap):
        # the out projection's result is the MLP half's saved input, so
        # nothing recomputes attn/out.
        for scope in (scopes.ATTN_QKV, ROUTE[attention], scopes.MLP):
            assert _under(names, scope, "rematted_computation"), scope
    elif not recurrence_recomputes:
        # (A linear layer's recurrence recomputes its blocks under every
        # policy: models/linear_attention.py.)
        assert not any("rematted_computation" in n for n in names)
    step = ([scopes.GRAD_REDUCE_SCATTER, scopes.OPTIMIZER,
             scopes.PARAM_ALL_GATHER] if shard_optimizer
            else [scopes.GRAD_MEAN, scopes.OPTIMIZER])
    for scope in step + [scopes.LOSS_MEAN]:
        assert _under(names, scope), scope
    assert not _under(names, scopes.STEP_GUARD)
    if attention == "flash":
        for kernel in (scopes.FLASH_FWD, scopes.FLASH_BWD_DQ,
                       scopes.FLASH_BWD_DKV):
            assert _under(names, kernel, scopes.ATTN_FLASH), kernel
    assert _unplaced(text) == []


@pytest.mark.parametrize(
    "attention,remat,shard_optimizer",
    list(itertools.product(("local", "flash"), ("none", "full"),
                           (False, True))))
def test_lm_step_carries_the_vocabulary(hvd, attention, remat,
                                        shard_optimizer):
    _check_lm(_lm_step_text(attention, remat, shard_optimizer), attention,
              remat, shard_optimizer)


MOE_PARTS = (scopes.MOE_ROUTER, scopes.MOE_DISPATCH, scopes.MOE_EXPERTS,
             scopes.MOE_COMBINE)


@pytest.mark.parametrize(
    "attention,remat,shard_optimizer",
    [("local", "none", False), ("flash", "none", False),
     ("local", "full", False), ("local", "none", True)])
def test_moe_step_carries_the_vocabulary_and_its_parts_under_mlp(
        hvd, attention, remat, shard_optimizer):
    """The expert layer's four parts open *under* ``mlp``, forward and
    backward, so the benchmark's fixed vocabulary still places every
    executed op (and answers ``mlp``), while ``perfbench/moe_reduce.py``
    reads the part one level down."""
    from perfbench import moe_reduce

    text = _lm_step_text(attention, remat, shard_optimizer, **MOE)
    _check_lm(text, attention, remat, shard_optimizer)
    names = _op_names(text)
    for part in MOE_PARTS:
        inside = f"{scopes.MLP}/{part}"
        assert _under(names, part, inside, "jvp("), part
        assert _under(names, part, inside, "transpose("), part
        assert not any(part in n and inside not in n for n in names), part
    hlo = scope_reduce.parse_hlo(text)
    parts = {moe_reduce.part_of(moe_reduce.op_name_of(name, hlo))
             for name, i in hlo.instructions.items()
             if i.opcode in HELD}
    assert set(MOE_PARTS) <= parts


GDN_PARTS = {scopes.GDN_PROJ: scopes.ATTN_QKV, scopes.GDN_CONV: scopes.ATTN_QKV,
             scopes.GDN_GATE_NORM: scopes.ATTN_OUT,
             scopes.GDN_OUT: scopes.ATTN_OUT}
HYBRID = dict(positions="none", qk_norm=True, tie_embeddings=False,
              mlp="swiglu", linear_key_heads=2, linear_value_heads=2,
              linear_key_head_dim=16, linear_value_head_dim=32,
              linear_conv_kernel=4, linear_allow_neg_eigval=True,
              layer_types=("linear_attention", "full_attention"))


@pytest.mark.parametrize("attention,remat",
                         [("local", "none"), ("flash", "full")])
def test_hybrid_step_carries_the_vocabulary_and_the_mixers_parts(
        hvd, attention, remat):
    """The linear layer's projections and convolution open *under*
    ``attn/qkv``, its gated norm and out projection under ``attn/out``,
    forward, backward and recomputed, so the benchmark's fixed vocabulary
    still places every executed op; the recurrence is a route of its own
    that the vocabulary answers with ``layer``; ``perfbench/gdn_reduce.py``
    reads all five by name."""
    from perfbench import gdn_reduce, moe_reduce

    text = _lm_step_text(attention, remat, False, **HYBRID)
    _check_lm(text, attention, remat, False, recurrence_recomputes=True)
    names = _op_names(text)
    marks = ("jvp(", "transpose(") + (
        ("rematted_computation",) if remat == "full" else ())
    for part, parent in GDN_PARTS.items():
        inside = f"{parent}/{part}"
        for mark in marks:
            if (part, mark) != (scopes.GDN_OUT, "rematted_computation"):
                assert _under(names, part, inside, mark), (part, mark)
        assert not any(part in n and inside not in n for n in names), part
    for mark in marks:
        assert _under(names, scopes.ATTN_GDN_SCAN, mark), mark
    # Only layer 0 is linear: no part of the mixer under layer_1, no
    # attention route under layer_0.
    assert not any("gdn_" in n and "layer_1" in n for n in names)
    assert not any(ROUTE[attention] in n and "layer_0" in n for n in names)
    scan = (f"jit(x)/transpose(jvp({scopes.LAYER % 0}))/"
            f"{scopes.ATTN_GDN_SCAN}/while/body/dot_general")
    assert scope_reduce.scope_of(scan) == "layer"
    assert scope_reduce.phase_of(scan) == "bwd"
    hlo = scope_reduce.parse_hlo(text)
    parts = {gdn_reduce.part_of(moe_reduce.op_name_of(name, hlo))
             for name, i in hlo.instructions.items() if i.opcode in HELD}
    assert set(gdn_reduce.PARTS) <= parts


SSM_PARTS = {scopes.SSM_PROJ: scopes.ATTN_QKV, scopes.SSM_CONV: scopes.ATTN_QKV,
             scopes.SSM_GATE_NORM: scopes.ATTN_OUT,
             scopes.SSM_OUT: scopes.ATTN_OUT}
LATENT_PARTS = MOE_PARTS + (scopes.MOE_LATENT, scopes.MOE_SHARED)
NEMOTRON = dict(positions="none", tie_embeddings=False, n_kv_heads=1,
                mlp="relu2", n_experts=8, experts_per_token=3, d_expert=64,
                d_latent=32, d_shared=64, routed_scale=5.0, experts_held=2,
                experts_held_from=2, ssm_heads=2, ssm_head_dim=32,
                ssm_state=16, ssm_groups=1, ssm_conv_kernel=4, ssm_chunk=32,
                layer_types=("mamba2", "mlp"),
                mtp_layer_types=("attention", "mlp"), mtp_loss_coef=0.1)


@pytest.mark.parametrize("attention,remat",
                         [("local", "none"), ("flash", "full")])
def test_nemotron_step_carries_the_vocabulary_and_every_part(
        hvd, attention, remat):
    """Layers of one part each and the prediction module: the Mamba-2
    mixer's parts open under ``attn/qkv`` and ``attn/out`` and its
    recurrence as a route the vocabulary answers with ``layer``; the
    latent expert layer's six parts under ``mlp``; the module under
    ``mtp``, holding model scopes of its own; every executed op has a
    phase and a scope, and ``perfbench/ssm_reduce.py`` reads each part by
    name."""
    from perfbench import moe_reduce, ssm_reduce

    text = _lm_step_text(attention, remat, False, **NEMOTRON)
    _check_lm(text, attention, remat, False)
    names = _op_names(text)
    marks = ("jvp(", "transpose(") + (
        ("rematted_computation",) if remat == "full" else ())
    for part, parent in SSM_PARTS.items():
        inside = f"{parent}/{part}"
        for mark in marks:
            if (part, mark) != (scopes.SSM_OUT, "rematted_computation"):
                assert _under(names, part, inside, mark), (part, mark)
        assert not _under(names, part, without=(inside,)), part
    for mark in marks:
        assert _under(names, scopes.ATTN_SSM_SCAN, mark), mark
    for part in LATENT_PARTS:
        inside = f"{scopes.MLP}/{part}"
        for mark in marks:
            assert _under(names, part, inside, mark), (part, mark)
        assert not _under(names, part, without=(inside,)), part
    # Layer 0 is a mixer alone and layer 1 a feed-forward part alone; the
    # only attention is the prediction module's.
    assert not any("ssm_" in n and "layer_1" in n for n in names)
    assert not any(f"layer_0)/{scopes.MLP}" in n or
                   f"layer_0/{scopes.MLP}" in n for n in names
                   if scopes.MTP not in n)
    assert all(scopes.MTP in n for n in names if ROUTE[attention] in n)
    for scope in (scopes.EMBED, scopes.ATTN_QKV, scopes.ATTN_OUT,
                  scopes.MLP, scopes.HEAD, scopes.LOSS):
        assert _under(names, scope, scopes.MTP, "jvp("), scope
        assert _under(names, scope, scopes.MTP, "transpose("), scope
    inside_mtp = (f"jit(x)/transpose(jvp({scopes.MTP}))/{scopes.LAYER % 1}/"
                  f"{scopes.MLP}/{scopes.MOE_SHARED}/dot_general")
    assert scope_reduce.scope_of(inside_mtp) == scopes.MLP
    assert ssm_reduce.parts_of(inside_mtp) == [scopes.MOE_SHARED, scopes.MTP]
    scan = (f"jit(x)/transpose(jvp({scopes.LAYER % 0}))/"
            f"{scopes.ATTN_SSM_SCAN}/while/body/mul")
    assert scope_reduce.scope_of(scan) == "layer"
    assert scope_reduce.phase_of(scan) == "bwd"
    hlo = scope_reduce.parse_hlo(text)
    parts = set()
    for name, i in hlo.instructions.items():
        if i.opcode in HELD:
            parts.update(ssm_reduce.parts_of(moe_reduce.op_name_of(name,
                                                                   hlo)))
    assert set(ssm_reduce.PARTS) | {ssm_reduce.MTP} <= parts


MAMBA1_PARTS = {scopes.MAMBA_PROJ: scopes.ATTN_QKV,
                scopes.MAMBA_CONV: scopes.ATTN_QKV,
                scopes.MAMBA_DT_BC: scopes.ATTN_QKV,
                scopes.MAMBA_GATE: scopes.ATTN_OUT,
                scopes.MAMBA_OUT: scopes.ATTN_OUT}
JAMBA = dict(positions="none", tie_embeddings=True, n_kv_heads=1,
             mlp="swiglu", mamba_inner=128, mamba_state=4, mamba_dt_rank=8,
             mamba_conv_kernel=4, layer_types=("mamba", "full_attention"))


@pytest.mark.parametrize("attention,remat",
                         [("local", "none"), ("flash", "full")])
def test_jamba_step_carries_the_vocabulary_and_the_mixers_parts(
        hvd, attention, remat):
    """A Mamba-1 layer and a multi-query attention layer, each with the
    dense MLP: the mixer's five parts open under ``attn/qkv`` and
    ``attn/out`` and its scan as a route the vocabulary answers with
    ``layer``; every executed op has a phase and a scope, and
    ``perfbench/mamba1_reduce.py`` reads each part by name."""
    from perfbench import mamba1_reduce, moe_reduce

    text = _lm_step_text(attention, remat, False, **JAMBA)
    # (The ``jax.numpy`` scan recomputes its blocks under every policy.)
    _check_lm(text, attention, remat, False, recurrence_recomputes=True)
    names = _op_names(text)
    marks = ("jvp(", "transpose(") + (
        ("rematted_computation",) if remat == "full" else ())
    for part, parent in MAMBA1_PARTS.items():
        inside = f"{parent}/{part}"
        for mark in marks:
            if (part, mark) != (scopes.MAMBA_OUT, "rematted_computation"):
                assert _under(names, part, inside, mark), (part, mark)
        assert not _under(names, part, without=(inside,)), part
    for mark in marks:
        assert _under(names, scopes.ATTN_MAMBA_SCAN, mark), mark
    # Layer 0 holds the mixer and layer 1 the attention, each with an MLP.
    assert not any("mamba_" in n and "layer_1" in n for n in names)
    assert not any(ROUTE[attention] in n and "layer_0" in n for n in names)
    for i in range(2):
        assert _under(names, scopes.MLP, scopes.LAYER % i, "jvp("), i
    scan = (f"jit(x)/transpose(jvp({scopes.LAYER % 0}))/"
            f"{scopes.ATTN_MAMBA_SCAN}/while/body/mul")
    assert scope_reduce.scope_of(scan) == "layer"
    assert scope_reduce.phase_of(scan) == "bwd"
    hlo = scope_reduce.parse_hlo(text)
    parts = set()
    for name, i in hlo.instructions.items():
        if i.opcode in HELD:
            parts.add(mamba1_reduce.part_of(moe_reduce.op_name_of(name, hlo)))
    assert set(mamba1_reduce.PARTS) <= parts


MLA_PARTS = (scopes.MLA_Q, scopes.MLA_KV, scopes.MLA_ROPE)
SIGMOID_PARTS = MOE_PARTS + (scopes.MOE_SHARED,)
GLM = dict(positions="rope", tie_embeddings=False, head_width=64,
           q_latent_rank=32, kv_latent_rank=32, rope_dim=16, mlp="swiglu",
           n_experts=8, experts_per_token=2, d_expert=64, d_shared=64,
           routed_scale=1.8, experts_held=2, experts_held_from=2,
           dense_layers=1, mtp_layer_types=("full_attention",),
           mtp_loss_coef=0.1)


# Heads of 128 with 64 rotary, the least the assembly's kernels take: on
# the flash route the heads are put together by ``ops/mla_assemble.py``.
GLM_WIDE = dict(GLM, head_width=128, rope_dim=64)


@pytest.mark.parametrize("attention,remat,fields",
                         [("local", "none", GLM), ("flash", "full", GLM),
                          ("flash", "dots", GLM_WIDE)],
                         ids=("local-none", "flash-full", "flash-dots-wide"))
def test_glm_step_carries_the_vocabulary_and_every_part(hvd, attention,
                                                        remat, fields):
    """Latent attention's three parts open under ``attn/qkv``, a leading
    dense layer's MLP under ``mlp/mlp_dense``, the sigmoid-routed layer's
    five parts under ``mlp``, the module under ``mtp``; every executed op
    has a phase and a scope, and ``perfbench/mla_reduce.py`` reads each
    part by name.  With the heads assembled by the kernels (``GLM_WIDE``)
    all three parts still hold ops, and the kernels are ``mla_rope``'s."""
    from perfbench import mla_reduce, moe_reduce

    text = _lm_step_text(attention, remat, False, **fields)
    # (``dots`` recomputes what is no matmul's output.)
    _check_lm(text, attention, remat, False,
              recurrence_recomputes=remat == "dots")
    names = _op_names(text)
    if fields is GLM_WIDE:
        inside = f"{scopes.ATTN_QKV}/{scopes.MLA_ROPE}"
        assert _under(names, scopes.MLA_ASSEMBLE_FWD, inside, "jvp(")
        assert _under(names, scopes.MLA_ASSEMBLE_BWD, inside, "transpose(")
    for part in MLA_PARTS:
        inside = f"{scopes.ATTN_QKV}/{part}"
        for mark in ("jvp(", "transpose("):
            assert _under(names, part, inside, mark), (part, mark)
        assert not _under(names, part, without=(inside,)), part
    for part in SIGMOID_PARTS + (scopes.MLP_DENSE,):
        inside = f"{scopes.MLP}/{part}"
        for mark in ("jvp(", "transpose("):
            assert _under(names, part, inside, mark), (part, mark)
        assert not _under(names, part, without=(inside,)), part
    # Layer 0 alone is dense, and nothing of it is an expert layer's.
    main = [n for n in names if scopes.MTP not in n]
    assert all("layer_0" in n for n in main if scopes.MLP_DENSE in n)
    assert not any("layer_0" in n and "/moe_" in n for n in main)
    assert not any(scopes.MOE_LATENT in n for n in names)
    for scope in (scopes.EMBED, scopes.ATTN_QKV, scopes.ATTN_OUT,
                  ROUTE[attention], scopes.MLP, scopes.HEAD, scopes.LOSS):
        assert _under(names, scope, scopes.MTP, "jvp("), scope
        assert _under(names, scope, scopes.MTP, "transpose("), scope
    hlo = scope_reduce.parse_hlo(text)
    parts = set()
    for name, i in hlo.instructions.items():
        if i.opcode in HELD:
            parts.update(mla_reduce.parts_of(moe_reduce.op_name_of(name,
                                                                   hlo)))
    assert set(mla_reduce.PARTS) | {mla_reduce.MTP} <= parts
    assert _unplaced(text) == []


SDAR = dict(positions="rope", tie_embeddings=False, n_kv_heads=1,
            head_width=64, qk_norm_per_head=True, mlp="swiglu", n_experts=4,
            experts_per_token=2, d_expert=64, norm_topk_prob=True,
            experts_held=2, experts_held_from=1, diffusion_block=4,
            mask_token_id=255)


@pytest.mark.parametrize("attention,remat",
                         [("local", "none"), ("flash", "full")])
def test_sdar_step_carries_the_vocabulary_and_the_assembly(hvd, attention,
                                                           remat):
    """Under block diffusion the step's own assembly of the two streams
    opens under ``embed/diffusion_assemble`` and nowhere else, the flash
    kernels sit under their route as ever, every executed op has a phase
    and a scope, and ``perfbench/bd_reduce.py`` reads each part by name."""
    from perfbench import bd_reduce, moe_reduce

    text = _lm_step_text(attention, remat, False, **SDAR)
    _check_lm(text, attention, remat, False)
    names = _op_names(text)
    # (``embed`` is the outermost scope here, so jvp wraps it alone.)
    inside = f"({scopes.EMBED})/{scopes.DIFFUSION_ASSEMBLE}/"
    assert _under(names, scopes.DIFFUSION_ASSEMBLE, inside)
    assert not _under(names, scopes.DIFFUSION_ASSEMBLE, without=(inside,))
    assert not any(f"/{scopes.MTP}/" in n or "/dsa_" in n for n in names)
    hlo = scope_reduce.parse_hlo(text)
    parts = {bd_reduce.part_of(moe_reduce.op_name_of(name, hlo))
             for name, i in hlo.instructions.items() if i.opcode in HELD}
    assert set(bd_reduce.PARTS) - {bd_reduce.ASSEMBLE} <= parts


OURO = dict(positions="rope", tie_embeddings=False, mlp="swiglu",
            post_norm=True, loops=4, exit_entropy_coef=0.05)


@pytest.mark.parametrize("attention,remat",
                         [("flash", "full"), ("local", "none")])
def test_looped_step_carries_the_vocabulary_under_the_loop(hvd, attention,
                                                           remat):
    """A stack run four times: every model scope nests under its pass's
    (``loop_<t>``), so the fixed vocabulary still answers; the sandwich's
    second norms, the carried final norm, the gate and the mixture open
    their own components; and ``perfbench/loop_reduce.py`` books every
    executed op to one part, those the fixed rules leave without a scope
    to the loop's carry."""
    from perfbench import loop_reduce, moe_reduce

    text = _lm_step_text(attention, remat, False, **OURO)
    names = _op_names(text)
    loop = scopes.LOOP % 3
    for scope in (scopes.ATTN_QKV, ROUTE[attention], scopes.ATTN_OUT,
                  scopes.MLP, scopes.LOOP_NORM):
        assert _under(names, scope, loop, "jvp(", without=("transpose(",))
        assert _under(names, scope, loop, "transpose("), scope
    # The readouts stand outside the loop's scope, a block a pass.
    for scope in (scopes.HEAD, scopes.LOSS):
        assert _under(names, scope, "jvp(", without=("transpose(",)), scope
        assert _under(names, scope, "transpose("), scope
        assert not _under(names, scope, loop), scope
    for part, homes in ((scopes.POST_NORM, (scopes.ATTN_OUT, scopes.MLP)),
                        (scopes.EXIT_GATE, (scopes.HEAD,)),
                        (scopes.EXIT_MIX, (scopes.LOSS,))):
        # (``jvp(loss)/exit_mix``: an outermost scope closes a bracket.)
        held = [n for n in names if _under([n], part)]
        for home in homes:
            inside = re.compile(re.escape(home) + r"\)*/" + part)
            assert any(inside.search(n) for n in held), (home, part)
            held = [n for n in held if not inside.search(n)]
        assert not held, part
    if attention == "flash":
        for kernel in (scopes.FLASH_FWD, scopes.FLASH_BWD_DQ,
                       scopes.FLASH_BWD_DKV):
            assert _under(names, kernel, scopes.ATTN_FLASH, loop), kernel
    hlo = scope_reduce.parse_hlo(text)
    parts = {name: loop_reduce.part_of(name, hlo)
             for name, i in hlo.instructions.items() if i.opcode in HELD}
    # (On the CPU the flash kernels run in the interpreter: no custom call
    # carries their name, and their time would be the glue's.)
    assert set(loop_reduce.PARTS) - {"flash", "qk_glue", "exit",
                                     "carry"} <= set(parts.values())
    # Written out, the passes have no carry to copy or stack: what is
    # booked there is the sums of a shared leaf's gradient over the passes
    # (and the cast of a recomputed block's input).
    assert {moe_reduce.op_name_of(name, hlo).rsplit("/", 1)[1]
            for name, part in parts.items()
            if part == "carry"} <= {"add_any", "remat2"}
    # What the fixed rules cannot place is the loop's own: those sums and
    # the final norm after every pass, which stands under no model scope
    # (and the CPU's fusions booked by the cast of a recomputed block's
    # input, the last pass's norm inside one of them).
    assert {parts[name] for name, _, _ in _unplaced(text)
            if not moe_reduce.op_name_of(name, hlo).endswith("/remat2")
            } <= {"carry", "norm"}


ZAYA = dict(positions="rope", n_heads=4, n_kv_heads=2, head_width=8,
            d_ff=0, mlp="swiglu", n_experts=4, experts_per_token=1, d_expert=64,
            experts_held=2, experts_held_from=1, cca_taps=(2, 2),
            rotary_dims=4, router_width=16, residual_scaling=True)


@pytest.mark.parametrize("attention,remat",
                         [("local", "none"), ("flash", "full")])
def test_zaya_step_carries_the_vocabulary_and_its_parts(hvd, attention,
                                                        remat):
    """Compressed convolutional attention opens ``cca_mix`` and
    ``cca_norm_rope`` under ``attn/qkv`` and nowhere else, the MLP router
    ``router_state`` and ``router_mlp`` under ``mlp/moe_router``, the skip
    ``moe_skip`` under ``mlp``, the scaled merge ``res_scale`` under
    ``attn/out`` and under ``mlp``; every executed op has a phase and a
    scope, and ``perfbench/cca_reduce.py`` books each to one part."""
    from perfbench import cca_reduce

    text = _lm_step_text(attention, remat, False, **ZAYA)
    _check_lm(text, attention, remat, False)
    names = _op_names(text)
    for part, homes in (
            (scopes.CCA_MIX, (scopes.ATTN_QKV,)),
            (scopes.CCA_NORM_ROPE, (scopes.ATTN_QKV,)),
            (scopes.ROUTER_STATE, (scopes.MOE_ROUTER,)),
            (scopes.ROUTER_MLP, (scopes.MOE_ROUTER,)),
            (scopes.MOE_SKIP, (scopes.MLP,)),
            (scopes.RES_SCALE, (scopes.ATTN_OUT, scopes.MLP))):
        held = [n for n in names if _under([n], part)]
        assert held, part
        for home in homes:
            inside = re.compile(re.escape(home) + r"\)*/" + part)
            assert any(inside.search(n) for n in held), (home, part)
            held = [n for n in held if not inside.search(n)]
        assert not held, (part, held[:3])
        assert _under(names, part, "jvp(", without=("transpose(",)), part
        assert _under(names, part, "transpose("), part
    assert _under(names, scopes.MOE_ROUTER, scopes.MLP)
    assert not any("/dsa_" in n or "/mla_" in n or scopes.QK_HEAD_NORM_ROPE
                   in n for n in names)
    hlo = scope_reduce.parse_hlo(text)
    parts = {name: cca_reduce.part_of(name, hlo)
             for name, i in hlo.instructions.items() if i.opcode in HELD}
    # (On the CPU the flash kernels run in the interpreter: no custom call
    # carries their name.)
    assert set(cca_reduce.PARTS) - {"flash"} <= set(parts.values())


@pytest.mark.parametrize("attention", ("ring", "ulysses"))
def test_sequence_routes_open_their_own_scope(hvd, attention):
    text = _lm_step_text(attention, "none", False, seq_axis="seq")
    _check_lm(text, attention, "none", False)


def test_step_guard_scope_only_with_the_guard_on(hvd, monkeypatch):
    monkeypatch.setenv("HOROVOD_STEP_GUARD", "skip")
    names = _op_names(_lm_step_text("local", "none", False))
    assert _under(names, scopes.STEP_GUARD)
    assert _under(names, scopes.LOSS_MEAN)


def test_resnet_step_carries_flax_names_and_the_step_scopes(hvd):
    from horovod_tpu.benchmark import make_train_step
    from horovod_tpu.models import ResNet18
    from horovod_tpu.topology import build_mesh

    mesh = build_mesh(axes=("data",), devices=jax.devices()[:4])
    model = ResNet18(num_classes=4)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)),
                           train=False))
    params, batch_stats = variables["params"], variables["batch_stats"]
    optimizer = optax.sgd(0.05, momentum=0.9)
    step = make_train_step(model, optimizer, mesh)
    text = step.lower(
        params, batch_stats, jax.eval_shape(optimizer.init, params),
        jax.ShapeDtypeStruct((8, 32, 32, 3), jnp.float32),
        jax.ShapeDtypeStruct((8,), jnp.int32)).compile().as_text()
    assert text.startswith(f"HloModule jit_{scopes.TRAIN_STEP},")
    names = _op_names(text)
    for scope in (scopes.LOSS, scopes.GRAD_MEAN, scopes.OPTIMIZER,
                  scopes.LOSS_MEAN):
        assert _under(names, scope), scope
    assert any("jvp(ResNet)/" in n for n in names)
    assert any("transpose(jvp(ResNet))/" in n for n in names)
    assert _unplaced(text) == []


def test_pipelined_step_has_its_module_name(hvd):
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.topology import build_mesh

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_seq=16,
                                dtype=jnp.float32)
    mesh = build_mesh(axes=("data", "pipe"), shape=(2, 2),
                      devices=jax.devices()[:4])
    optimizer = optax.sgd(0.01)
    step, _ = tfm.make_train_step_pipelined(cfg, optimizer, mesh,
                                            n_microbatches=2)
    params = jax.eval_shape(
        lambda: tfm.split_pipeline_params(
            tfm.init_params(jax.random.PRNGKey(0), cfg), 2))
    tokens = jax.ShapeDtypeStruct((4, 16), jnp.int32)
    lowered = step.lower(params, jax.eval_shape(optimizer.init, params),
                         tokens, tokens)
    text = lowered.as_text()
    assert f"jit_{scopes.LM_PIPELINED_TRAIN_STEP}" in text
    names = set(re.findall(r'loc\("([^"]*)"', lowered.as_text(
        debug_info=True)))
    for scope in (scopes.EMBED, scopes.ATTN_QKV, scopes.MLP, scopes.HEAD,
                  scopes.LOSS, scopes.OPTIMIZER):
        assert _under(names, scope), scope


def test_every_named_scope_takes_its_name_from_the_vocabulary():
    root = pathlib.Path(scopes.__file__).resolve().parents[1]
    calls = [(path, line)
             for path in root.rglob("*.py") if path.name != "scopes.py"
             for line in path.read_text().splitlines()
             if "named_scope(" in line and not line.lstrip().startswith("#")]
    assert calls
    stray = [(str(p), l.strip()) for p, l in calls
             if not re.search(r"named_scope\(scopes\.[A-Z_]+[ )%]", l)]
    assert stray == []


def test_the_benchmark_reads_the_same_vocabulary():
    """``perfbench/scope_reduce.py`` keeps its own copy of the names (it
    also runs over programs from before them): hold the two together."""
    program = {v for k, v in vars(scopes).items()
               if k.isupper() and isinstance(v, str)}
    kernels = {scopes.FLASH_FWD, scopes.FLASH_BWD_DQ, scopes.FLASH_BWD_DKV}
    modules = {scopes.LM_TRAIN_STEP, scopes.LM_PIPELINED_TRAIN_STEP,
               scopes.TRAIN_STEP}
    # A group of their own: the expert layer's parts are sub-scopes of
    # ``mlp`` and its grouped-matmul kernels are named for
    # ``perfbench/moe_reduce.py`` and the ``moe_lm`` adapter; the fixed
    # vocabulary does not hold them and does not need to.
    moe_parts = set(MOE_PARTS)
    moe_kernels = {scopes.MOE_GMM, scopes.MOE_GMM_NT, scopes.MOE_TGMM,
                   scopes.MOE_ROW_TILES, scopes.MOE_ROWS_BACK,
                   scopes.MOE_CHOOSE}
    # Likewise the linear-attention layer's parts, read by
    # ``perfbench/gdn_reduce.py``: four sub-scopes of ``attn/qkv`` and
    # ``attn/out``, and the recurrence's route, which the fixed
    # vocabulary books as ``layer``.
    gdn_parts = set(GDN_PARTS) | {scopes.ATTN_GDN_SCAN}
    # The recurrence's two kernels run under that route and are booked by
    # it, not by their names.
    gdn_kernels = {scopes.GDN_SCAN_FWD, scopes.GDN_SCAN_BWD}
    # And the Mamba-2 mixer's parts with its route, the latent expert
    # layer's two dense parts and the prediction module's component, read
    # by ``perfbench/ssm_reduce.py``.
    ssm_parts = (set(SSM_PARTS) | {scopes.ATTN_SSM_SCAN, scopes.MOE_LATENT,
                                   scopes.MOE_SHARED, scopes.MTP})
    # Its recurrence's two kernels, likewise booked by the route.
    ssm_kernels = {scopes.SSM_SCAN_FWD, scopes.SSM_SCAN_BWD}
    # And latent attention's three parts under ``attn/qkv`` with the
    # leading dense layer's component under ``mlp``, read by
    # ``perfbench/mla_reduce.py``.
    mla_parts = set(MLA_PARTS) | {scopes.MLP_DENSE}
    # The short convolution's two kernels run under ``gdn_conv`` and
    # ``ssm_conv`` and are booked by those parts, not by their names.
    conv_kernels = {scopes.SHORT_CONV_FWD, scopes.SHORT_CONV_BWD}
    # Likewise the gated norm's two, under ``gdn_gate_norm`` and
    # ``ssm_gate_norm``.
    norm_kernels = {scopes.GATED_NORM_FWD, scopes.GATED_NORM_BWD}
    # Learned sparse attention's six parts (two under ``attn/qkv``, four
    # under ``attn/flash_attention``, its route), read by
    # ``perfbench/dsa_reduce.py``, and its seven kernels, booked by the
    # parts they run under and named in the ``dsa_moe_lm`` adapter.
    dsa_parts = {scopes.DSA_INDEX_PROJ, scopes.QK_HEAD_NORM_ROPE,
                 scopes.DSA_INDEX_SCORES, scopes.DSA_SELECT,
                 scopes.DSA_FLASH, scopes.DSA_INDEX_LOSS}
    dsa_kernels = {scopes.DSA_INDEX_FWD, scopes.DSA_INDEX_BWD,
                   scopes.DSA_SELECT_KERNEL, scopes.DSA_FWD,
                   scopes.DSA_BWD_DQ, scopes.DSA_BWD_DKV, scopes.DSA_PROBS}
    # And the Mamba-1 mixer's five parts with its route, read by
    # ``perfbench/mamba1_reduce.py``, and its scan's two kernels, booked
    # by the route, and its gate's two, booked by ``mamba_gate``.
    mamba1_parts = set(MAMBA1_PARTS) | {scopes.ATTN_MAMBA_SCAN}
    mamba1_kernels = {scopes.MAMBA_SCAN_FWD, scopes.MAMBA_SCAN_BWD,
                      scopes.MAMBA_GATE_FWD, scopes.MAMBA_GATE_BWD}
    # Latent attention's assembly: two kernels, booked by ``mla_rope``.
    mla_kernels = {scopes.MLA_ASSEMBLE_FWD, scopes.MLA_ASSEMBLE_BWD}
    # Plain attention's assembly: two kernels, booked by
    # ``qk_head_norm_rope``.
    qk_kernels = {scopes.QK_ASSEMBLE_FWD, scopes.QK_ASSEMBLE_BWD}
    # Block diffusion's assembly of the two streams, a sub-scope of
    # ``embed`` read by ``perfbench/bd_reduce.py``.
    bd_parts = {scopes.DIFFUSION_ASSEMBLE}
    # A looped stack's scopes (the loop's own, the carried norm, the
    # sandwich's second norm, the gate and the mixture), read by
    # ``perfbench/loop_reduce.py``.
    loop_parts = {scopes.LOOP, scopes.LOOP_NORM,
                  scopes.POST_NORM, scopes.EXIT_GATE, scopes.EXIT_MIX}
    # Compressed convolutional attention's two parts under ``attn/qkv``,
    # the MLP router's two under ``mlp/moe_router``, the skip's term and
    # the scaled merge, read by ``perfbench/cca_reduce.py``.
    cca_parts = {scopes.CCA_MIX, scopes.CCA_NORM_ROPE, scopes.ROUTER_STATE,
                 scopes.ROUTER_MLP, scopes.MOE_SKIP, scopes.RES_SCALE}
    from perfbench import cca_reduce
    assert cca_reduce._MIX.search(
        f"jit(x)/jvp({scopes.LAYER % 1})/{scopes.ATTN_QKV}/"
        f"{scopes.CCA_MIX}/dot_general")
    assert cca_reduce._MERGE.search(
        f"jit(x)/transpose(jvp({scopes.LAYER % 1}))/{scopes.MLP}/"
        f"{scopes.RES_SCALE}/mul")
    assert cca_reduce._ROUTER.search(
        f"jit(x)/jvp({scopes.LAYER % 0})/{scopes.MLP}/{scopes.MOE_ROUTER}/"
        f"{scopes.ROUTER_MLP}/erf")
    assert cca_reduce.MARK == scopes.CCA_MIX
    from perfbench import loop_reduce
    for inside, part in (
            (f"{scopes.LOOP % 1}/{scopes.LAYER % 1}/{scopes.ATTN_OUT}"
             f"/{scopes.POST_NORM}/mul", "norm"),
            (f"{scopes.LOOP % 2}/{scopes.LAYER % 0}/{scopes.MLP}/"
             f"{scopes.POST_NORM}/mul", "norm"),
            (f"{scopes.LOOP % 1}/{scopes.LOOP_NORM}/rsqrt", "norm"),
            (f"{scopes.LOOP % 1}/{scopes.HEAD}/{scopes.EXIT_GATE}"
             f"/reduce_sum", "exit"),
            (f"{scopes.LOSS}/{scopes.EXIT_MIX}/exp", "exit"),
            (f"{scopes.LOOP % 1}/{scopes.HEAD}/dot_general", "head"),
            (f"{scopes.LOOP % 3}/{scopes.LOSS}/reduce_max", "head"),
            (f"{scopes.LOOP % 1}/{scopes.LAYER % 1}/"
             f"{scopes.ATTN_QKV}/dot_general", "attn"),
            (f"{scopes.LOOP % 1}/{scopes.LAYER % 1}/{scopes.MLP}"
             f"/dot_general", "mlp"),
            (f"{scopes.LOOP % 1}/add_any", "carry"),
            (f"{scopes.EMBED}/gather", "other"),
            (f"{scopes.OPTIMIZER}/mul", "other")):
        for phase, wrap in (("fwd", "jvp(%s)"), ("bwd", "transpose(jvp(%s))")):
            head, _, rest = inside.partition("/")
            call = f"jit(x)/{wrap % head}/{rest}"
            assert loop_reduce.part_of_name(call) == part, call
            if part != "other":
                assert scope_reduce.phase_of(call) == phase, call
    # The sum of a shared leaf's partial gradients, under no scope.
    assert loop_reduce.part_of_name(
        "jit(x)/transpose(jvp(jvp()))/add_any") == "carry"
    assert loop_reduce.part_of_name("jit(x)/jvp()/add") == "other"
    from perfbench import bd_reduce
    assert bd_parts | {scopes.QK_HEAD_NORM_ROPE} | moe_parts == set(
        bd_reduce.PARTS)
    from perfbench import dsa_reduce
    assert dsa_parts == set(dsa_reduce.DSA_PARTS)
    assert set(scope_reduce.KERNEL_NAMES) == kernels
    assert (set(scope_reduce.MODEL_SCOPES + scope_reduce.GRAD_MEAN_SCOPES
                + scope_reduce.OPTIMIZER_SCOPES)
            == program - kernels - modules - {scopes.LAYER} - moe_parts
            - moe_kernels - gdn_parts - gdn_kernels - ssm_parts
            - ssm_kernels - mla_parts - conv_kernels - norm_kernels
            - dsa_parts - dsa_kernels - mamba1_parts - mamba1_kernels
            - mla_kernels - qk_kernels - bd_parts - loop_parts - cca_parts)
    from perfbench import mamba1_reduce
    assert ({p.rsplit("/", 1)[-1] for p in mamba1_parts}
            == set(mamba1_reduce.PARTS))
    for phase, name, kernel in (
            ("fwd", "jvp(%s)", scopes.MAMBA_SCAN_FWD),
            ("bwd", "transpose(jvp(%s))", scopes.MAMBA_SCAN_BWD)):
        call = (f"jit(x)/{name % (scopes.LAYER % 3)}/"
                f"{scopes.ATTN_MAMBA_SCAN}/{kernel}/pallas_call")
        assert mamba1_reduce.part_of(call) == mamba1_reduce.SCAN
        assert scope_reduce.phase_of(call) == phase
    # The gate's kernels are booked where the ``jax.numpy`` line was.
    for phase, name, kernel in (
            ("fwd", "jvp(%s)", scopes.MAMBA_GATE_FWD),
            ("bwd", "transpose(jvp(%s))", scopes.MAMBA_GATE_BWD)):
        call = (f"jit(x)/{name % (scopes.LAYER % 3)}/{scopes.ATTN_OUT}/"
                f"{scopes.MAMBA_GATE}/{kernel}/pallas_call")
        assert mamba1_reduce.part_of(call) == scopes.MAMBA_GATE
        assert scope_reduce.phase_of(call) == phase
        assert scope_reduce.scope_of(call) == scopes.ATTN_OUT
    from perfbench import mla_reduce
    assert (mla_parts | moe_parts | {scopes.MOE_SHARED}
            == set(mla_reduce.PARTS))
    # The assembly's kernels are booked where the rotation was.
    for phase, name, kernel in (
            ("fwd", "jvp(%s)", scopes.MLA_ASSEMBLE_FWD),
            ("bwd", "transpose(jvp(%s))", scopes.MLA_ASSEMBLE_BWD)):
        call = (f"jit(x)/{name % (scopes.LAYER % 3)}/{scopes.ATTN_QKV}/"
                f"{scopes.MLA_ROPE}/{kernel}/pallas_call")
        assert mla_reduce.parts_of(call) == [scopes.MLA_ROPE]
        assert scope_reduce.phase_of(call) == phase
        assert scope_reduce.scope_of(call) == scopes.ATTN_QKV
    # Plain attention's are booked where the per-head norm and the
    # rotation were, by both reducers that read that part.
    for phase, name, kernel in (
            ("fwd", "jvp(%s)", scopes.QK_ASSEMBLE_FWD),
            ("bwd", "transpose(jvp(%s))", scopes.QK_ASSEMBLE_BWD)):
        call = (f"jit(x)/{name % (scopes.LAYER % 3)}/{scopes.ATTN_QKV}/"
                f"{scopes.QK_HEAD_NORM_ROPE}/{kernel}/pallas_call")
        assert bd_reduce.part_of(call) == scopes.QK_HEAD_NORM_ROPE
        assert dsa_reduce.part_of(call) == scopes.QK_HEAD_NORM_ROPE
        assert scope_reduce.phase_of(call) == phase
        assert scope_reduce.scope_of(call) == scopes.ATTN_QKV
    from perfbench import gdn_reduce
    assert ({p.rsplit("/", 1)[-1] for p in gdn_parts}
            == set(gdn_reduce.PARTS))
    for phase, name, kernel in (
            ("fwd", "jvp(%s)", scopes.GDN_SCAN_FWD),
            ("bwd", "transpose(jvp(%s))", scopes.GDN_SCAN_BWD)):
        call = (f"jit(x)/{name % (scopes.LAYER % 1)}/{scopes.ATTN_GDN_SCAN}"
                f"/{kernel}/pallas_call")
        assert gdn_reduce.part_of(call) == "gdn_scan"
        assert scope_reduce.phase_of(call) == phase
    from perfbench import ssm_reduce
    for phase, name, kernel in (
            ("fwd", "jvp(%s)", scopes.SSM_SCAN_FWD),
            ("bwd", "transpose(jvp(%s))", scopes.SSM_SCAN_BWD)):
        call = (f"jit(x)/{name % (scopes.LAYER % 2)}/{scopes.ATTN_SSM_SCAN}"
                f"/{kernel}/pallas_call")
        assert ssm_reduce.parts_of(call) == ["ssm_scan"]
        assert scope_reduce.phase_of(call) == phase
    # The gated norm's kernels are booked where the ``jax.numpy`` lines
    # were: by the part they run under, in both mixers.
    for phase, name, kernel in (
            ("fwd", "jvp(%s)", scopes.GATED_NORM_FWD),
            ("bwd", "transpose(jvp(%s))", scopes.GATED_NORM_BWD)):
        call = (f"jit(x)/{name % (scopes.LAYER % 1)}/{scopes.ATTN_OUT}/%s/"
                f"{kernel}/pallas_call")
        assert gdn_reduce.part_of(
            call % scopes.GDN_GATE_NORM) == scopes.GDN_GATE_NORM
        assert ssm_reduce.parts_of(
            call % scopes.SSM_GATE_NORM) == [scopes.SSM_GATE_NORM]
        assert scope_reduce.phase_of(call) == phase
        assert scope_reduce.scope_of(call) == scopes.ATTN_OUT
    assert scope_reduce.scope_of(
        f"jit(x)/jvp({scopes.LAYER % 3})/{scopes.MLP}/dot_general"
    ) == scopes.MLP
    from perfbench import moe_reduce
    assert set(moe_reduce.SUB_SCOPES) == moe_parts
    inside = (f"jit(x)/transpose(jvp({scopes.LAYER % 3}))/{scopes.MLP}/"
              f"{scopes.MOE_EXPERTS}/dot_general")
    assert scope_reduce.scope_of(inside) == scopes.MLP
    assert scope_reduce.phase_of(inside) == "bwd"
    assert moe_reduce.part_of(inside) == scopes.MOE_EXPERTS
    assert moe_reduce.part_of(
        f"jit(x)/jvp({scopes.LAYER % 0})/{scopes.MLP}/add"
    ) == moe_reduce.OTHER
    assert moe_reduce.part_of(f"jit(x)/{scopes.HEAD}/dot_general") is None
