"""Ahead-of-time compiles for the TPU v5e of every Pallas kernel at the
widths of the shipped presets.

Interpret mode (what every other kernel test runs) accepts block shapes,
unaligned stores and VMEM footprints that the chip's compiler refuses.
The TPU compiler is installed in the sandbox and compiles for a chip that
is described, not attached — so each case here lowers one kernel with
``interpret=False`` and compiles it for one device of a ``v5e:2x2``
topology.  Nothing runs: a pass means "Mosaic and XLA accept this shape",
never a timing or a result.

The topology is described inside a module-scoped fixture (only the xdist
worker that is handed this file loads libtpu) and the compile happens in
the test's own process.  Serving ticks at full depth (35-45 s each) are
NOT here — they live in the builder's scratch script; the paged engine's
pool programs are, cut to two layers (ISSUE 30: the compiled text is what
says that no program copies the pool), and so is ONE whole train step, the
train cell's (ISSUE 48: its memory and its `loss` scope are the step's).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bpe_transformer_tpu.kernels.pallas.decode_attention import (
    decode_attention,
    paged_decode_attention,
)
from bpe_transformer_tpu.kernels.pallas.flash_attention import flash_attention
from bpe_transformer_tpu.kernels.pallas.grouped_matmul import grouped_matmul
from bpe_transformer_tpu.kernels.pallas.quant_matmul import quant_matmul
from bpe_transformer_tpu.kernels.pallas.ragged_attention import (
    ragged_paged_attention,
)
from bpe_transformer_tpu.kernels.pallas.runtime import (
    attention_path,
    flash_tiles,
)
from bpe_transformer_tpu.kernels.pallas.sample import (
    fused_head_sample,
    fused_verify_head,
)
from bpe_transformer_tpu.kernels.pallas.swiglu import swiglu_fused
from bpe_transformer_tpu.models.config import GPT2_MEDIUM, GPT2_SMALL_32K

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def four_chips():
    """The four described devices of a v5e:2x2; the persistent compile
    cache is off while this module runs (a described-device executable
    cannot be read back)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # no libtpu / lock held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(four_chips):
    """One described v5e device."""
    return SingleDeviceSharding(four_chips[0])


def _compile(fn, one_chip, *shapes):
    """Lower ``fn`` on ShapeDtypeStructs placed on the described chip and
    compile; returns the compiled text (must hold a Mosaic custom call)."""
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in shapes
    ]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


# ------------------------------------------------------------ training


@pytest.mark.parametrize(
    "tiles", [(256, 256), (512, 512), "picked"], ids=["256", "512", "picked"]
)
def test_flash_attention_fwd_bwd(one_chip, tiles):
    """Forward and the one-pass backward at gpt2-small-32k's attention
    shape, at fixed tiles and at the tiles `flash_tiles` picks for
    (1024, 64, bf16); d_head 64 reaches the kernels at its own width (no
    128-lane copy of q/k/v/dO in HBM) and the row statistics compact."""
    if tiles == "picked":
        tiles = flash_tiles(1024)
    block_q, block_k = tiles
    qkv = ((8, 12, 1024, 64), BF16)

    def loss(q, k, v):
        out = flash_attention(q, k, v, True, block_q, block_k, False)
        return jnp.sum(out.astype(F32))

    text = _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 2)), one_chip, qkv, qkv, qkv
    )
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
    assert len(calls) == 2  # forward, backward
    for call in calls:
        assert "bf16[96,1024,64]" in call and "bf16[96,1024,128]" not in call
        assert "f32[96,1,1024]" in call and "f32[96,1024,128]" not in call


@pytest.mark.parametrize("seq", [600, 1000])
def test_flash_attention_unaligned_sequence(one_chip, seq):
    """A sequence that no 128-lane tile divides (a raw prompt length in
    `decode.prefill`): "auto" sends it to XLA, and a forced "flash" runs
    256-wide tiles over the sequence padded to their multiple, so Mosaic
    never sees an unaligned S x S tile."""
    assert attention_path(seq, 64, "tpu") == "xla"
    block_q, block_k = flash_tiles(seq)
    assert (block_q, block_k) == (256, 256)
    qkv = ((4, 12, seq, 64), BF16)

    def loss(q, k, v):
        out = flash_attention(q, k, v, True, block_q, block_k, False)
        return jnp.sum(out.astype(F32))

    text = _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 2)), one_chip, qkv, qkv, qkv
    )
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
    padded = -(-seq // 256) * 256
    assert len(calls) == 2
    assert all(f"bf16[48,{padded},64]" in call for call in calls)


@pytest.mark.parametrize(
    "strategy,axes,path",
    [
        ("fsdp", {"data": 4}, "xla"),
        ("tp", {"model": 4}, "xla"),
        ("fsdp_tp", {"data": 2, "model": 2}, "xla"),
        ("dp", {"data": 4}, "flash"),
        ("pp", {"pp": 2, "data": 2}, "flash"),
    ],
)
def test_auto_attention_lowers_under_every_partitioning(
    four_chips, monkeypatch, strategy, axes, path
):
    """gpt2-small-32k's shape says "flash" on the TPU, but XLA's SPMD
    partitioner cannot split a Mosaic kernel: the GSPMD steps lower with
    materialized attention (`partitioned_config`), the explicit-dp and
    pipeline steps — per-device bodies under shard_map — with the kernels.  Lowered for the
    described 2x2 with the TPU's branch of the predicate (the CPU's always
    materializes); two layers, nothing compiled."""
    import dataclasses

    from bpe_transformer_tpu.models import init_params
    from bpe_transformer_tpu.optim.adamw import adamw_init
    from bpe_transformer_tpu.parallel import (
        make_dp_train_step,
        make_gspmd_train_step,
        make_mesh,
        make_pp_train_step,
        stack_pipeline_params,
    )
    from bpe_transformer_tpu.training.train_step import TrainHParams

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config = dataclasses.replace(GPT2_SMALL_32K, num_layers=2)
    assert config.attention_impl == "auto"
    mesh = make_mesh(axes, devices=four_chips)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), config))
    if strategy == "pp":
        params = jax.eval_shape(lambda p: stack_pipeline_params(p, 2), params)
    opt_state = jax.eval_shape(adamw_init, params)
    ids = jax.ShapeDtypeStruct((8, config.context_length), I32)
    if strategy == "dp":
        step = make_dp_train_step(config, TrainHParams(), mesh)
    elif strategy == "pp":
        step = make_pp_train_step(
            config, TrainHParams(), mesh, num_microbatches=2
        )
    else:
        step = make_gspmd_train_step(
            config, TrainHParams(), mesh, strategy, example_params=params
        )
    text = step.lower(params, opt_state, ids, ids).as_text()
    assert ("tpu_custom_call" in text) == (path == "flash")


def _loss_scope(text, opcode):
    """Names' scopes of the compiled text's ``opcode`` instructions that lie
    in the `loss` scope."""
    import re

    return re.findall(
        rf" {opcode}\([^\n]*op_name=\"([^\"]*\(loss\)[^\"]*)\"", text
    )


def test_train_step_at_the_train_cells_shape(one_chip, on_tpu):
    """``small.train``'s step as the cell compiles it (gpt2-small-32k whole,
    B=32 x S=1,024, flash attention, the layers as calls; ~35 s): the `loss`
    scope is ONE loop - the forward's, which makes the gradients too - of
    three products a chunk, nothing of it is left in the backward, and the
    step's temporaries stay within 0.3 GB of the 11.54 GB they were when a
    second loop rematerialised each chunk's logits (ISSUE 48)."""
    from bpe_transformer_tpu.models import init_params
    from bpe_transformer_tpu.optim.adamw import adamw_init
    from bpe_transformer_tpu.training.train_step import (
        TrainHParams,
        make_train_step,
    )

    config = GPT2_SMALL_32K
    params = _described(
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), config)), one_chip
    )
    opt_state = _described(jax.eval_shape(adamw_init, params), one_chip)
    ids = jax.ShapeDtypeStruct((32, config.context_length), I32, sharding=one_chip)
    compiled = (
        make_train_step(config, TrainHParams())
        .lower(params, opt_state, ids, ids)
        .compile()
    )
    text = compiled.as_text()
    assert "flash_attention_bwd" in text
    assert _loss_scope(text, "while") == ["jit(step)/jvp(loss)/while"]
    products = _loss_scope(text, "convolution")
    assert len(products) == 3 and not any("transpose(" in p for p in products)
    assert compiled.memory_analysis().temp_size_in_bytes <= 11.54e9 + 0.3e9


@pytest.mark.parametrize(
    "config", [GPT2_SMALL_32K, GPT2_MEDIUM], ids=["d_ff2048", "d_ff2731"]
)
def test_swiglu(one_chip, config):
    """Forward only: the kernel's backward is plain XLA recompute."""
    d, ff = config.d_model, config.d_ff
    _compile(
        lambda x, w1, w2, w3: swiglu_fused(x, w1, w2, w3, interpret=False),
        one_chip,
        ((512, d), BF16), ((ff, d), BF16), ((d, ff), BF16), ((ff, d), BF16),
    )


# ------------------------------------------------------------- serving


def _matmul_shapes(config):
    """Every (d_out, d_in) `ops.quant.quantize_params` produces for a dense
    config: q/k/v/o, FFN w1/w3 and w2, and the LM head."""
    d, ff, v = config.d_model, config.d_ff, config.vocab_size
    return [(d, d), (ff, d), (d, ff), (v, d)]


#: + the tinystories-12l head: vocab 10,000 has no 128-multiple divisor.
QUANT_SHAPES = sorted(
    set(_matmul_shapes(GPT2_SMALL_32K))
    | set(_matmul_shapes(GPT2_MEDIUM))
    | {(10_000, 512)}
)


@pytest.mark.parametrize("rows", [8, 512], ids=["tick", "prefill512"])
@pytest.mark.parametrize(
    "d_out,d_in", QUANT_SHAPES, ids=[f"{o}x{i}" for o, i in QUANT_SHAPES]
)
def test_quant_matmul(one_chip, d_out, d_in, rows):
    _compile(
        lambda x, q, s: quant_matmul(x, q, s, interpret=False), one_chip,
        ((rows, d_in), BF16), ((d_out, d_in), I8), ((d_out,), F32),
    )


@pytest.mark.parametrize(
    "heads,d_head", [(12, 64), (16, 64)], ids=["small", "medium"]
)
def test_decode_attention(one_chip, heads, d_head):
    cache = ((8, heads, 1024, d_head), BF16)
    _compile(
        lambda q, k, v, pos: decode_attention(q, k, v, pos, interpret=False),
        one_chip, ((8, heads, d_head), BF16), cache, cache, ((8,), I32),
    )


def _paged_attention_args(slots, heads, d_head, block_size, kv_dtype, num_blocks):
    """``(fn, shapes)`` of one `paged_decode_attention` call over a pool of
    ``num_blocks`` blocks and a table one context (1,024 keys) wide."""
    pool = ((num_blocks, block_size, heads * d_head), kv_dtype)
    shapes = [
        ((slots, heads, d_head), BF16), pool, pool,
        ((slots, 1024 // block_size), I32), ((slots,), I32),
    ]
    if kv_dtype == I8:
        shapes += [((num_blocks, heads), F32)] * 2

        def fn(q, k, v, tables, counts, ks, vs):
            return paged_decode_attention(
                q, k, v, tables, counts, k_scale=ks, v_scale=vs,
                interpret=False,
            )
    else:

        def fn(q, k, v, tables, counts):
            return paged_decode_attention(
                q, k, v, tables, counts, interpret=False
            )

    return fn, shapes


@pytest.mark.parametrize("block_size", [16, 32, 128])
@pytest.mark.parametrize("kv_dtype", [BF16, I8], ids=["bf16", "int8"])
def test_paged_decode_attention(one_chip, kv_dtype, block_size):
    fn, shapes = _paged_attention_args(8, 12, 64, block_size, kv_dtype, 515)
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("kv_dtype", [BF16, I8], ids=["bf16", "int8"])
@pytest.mark.parametrize(
    "slots,heads", [(128, 12), (64, 16)],
    ids=["small.serve.decode-heavy", "medium.serve.prefill-heavy"],
)
def test_paged_decode_attention_at_the_cells_shapes(
    one_chip, slots, heads, kv_dtype
):
    """The serve cells' own ticks: 128 slots x 64 blocks of 16 x 768 and 64
    x 64 of 16 x 1,024, over a pool that holds every slot's whole table."""
    fn, shapes = _paged_attention_args(
        slots, heads, 64, 16, kv_dtype, slots * 64 + 1
    )
    _compile(fn, one_chip, *shapes)


# ------------------------------------------- the paged pool, in place
#
# gpt2-small-32k's widths cut to 2 layers and 8 slots, over a pool of 2,049
# blocks of 16 (four times what the slots can hold, so that a pool array is
# larger than the tick's gathered chains): the engine's own programs with
# the pool donated, as `PagedEngine` jits them.  The pool's shape IS its
# layout on the device (`init_kv_pool`), so the compiled text must hold no
# copy or transpose of a pool-shaped array, on the way in, inside, or on the
# way out.

POOL_SLOTS, POOL_BLOCK, POOL_BLOCKS = 8, 16, 2049


def _described(tree, one_chip):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree,
    )


def _pool_program(
    name, config, one_chip, kv_dtype, layers_as_calls=True,
    slots=POOL_SLOTS, blocks=POOL_BLOCKS, bucket=256, prefill_chunk=256,
):
    """``(jitted program, its arguments described on the chip, the pool)``
    for one of the engine's pool programs, jitted as `PagedEngine` jits it
    on the TPU (``layers_as_calls=False``: without the compiler option),
    over ``slots`` slots and a pool of ``blocks`` blocks; a chunk is of
    ``bucket`` rows, the engine's ``prefill_chunk`` (which sizes a window
    group's rows) ``prefill_chunk``."""
    import functools

    from bpe_transformer_tpu.utils.compile_cache import layered_program_options

    options = layered_program_options("tpu") if layers_as_calls else None

    from bpe_transformer_tpu.models.decode import (
        RecurrentRows,
        cache_kind,
        init_paged_pool,
    )
    from bpe_transformer_tpu.models.transformer import init_params
    from bpe_transformer_tpu.serving.engine import prepare_serving_weights
    from bpe_transformer_tpu.serving.kvpool import paged_engine as pe

    bs = POOL_BLOCK
    nbs = config.context_length // bs
    if config.eva_block:  # summary blocks, then a window's: `EvaRows`
        from bpe_transformer_tpu.models.decode import eva_table_geometry

        nbs = eva_table_geometry(config, bs)[2]

    def weights():
        params = init_params(jax.random.PRNGKey(0), config)
        return prepare_serving_weights(params, config, None)[:2]

    params, lm_head = _described(jax.eval_shape(weights), one_chip)
    # A config with window layers keeps a window group beside the full one,
    # its rows and its size the kind's host half's (window + one chunk of
    # positions a row), as the engine asks them.
    window_cap = window_blocks = 0
    if config.has_window_layers:
        from bpe_transformer_tpu.serving.kvpool.host_cache import HOST_HALVES

        host = HOST_HALVES[cache_kind(config)](
            config, slots=slots, block_size=bs, prefill_chunk=prefill_chunk,
            prefix_cache=False, kv_dtype=False, fused_sampling=False,
        )
        window_cap = host.window_cap
        window_blocks = host.pool_keywords["num_window_blocks"]
    pool = _described(
        jax.eval_shape(
            lambda: init_paged_pool(
                config, blocks, bs, BF16, kv_dtype=kv_dtype, slots=slots,
                num_window_blocks=window_blocks,
            )
        ),
        one_chip,
    )
    # The routing counts a kind carries (None for the dense kind).
    moe = cache_kind(config).zero_counts(config)
    moe = moe if moe is None else _described(moe, one_chip)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    scalar = arr((), I32)
    layered = dict(donate_argnums=(2,), compiler_options=options)
    # The decode carry: a launch's tokens, positions and keys.
    tokens, positions, keys = (
        arr((slots,), I32), arr((slots,), I32), arr((slots, 2), jnp.uint32)
    )
    def tables(*lead):
        """The block tables as `PagedEngine.cache.table_rows` hands them over."""
        if not window_cap:
            return arr((*lead, nbs), I32)
        return {
            "full": arr((*lead, nbs), I32),
            "window": arr((*lead, window_cap), I32),
            "window_base": arr(lead, I32),
        }

    if name == "tick":
        fn = functools.partial(pe._tick_program, config=config, block_size=bs)
        args = (
            params, lm_head, pool, moe, tables(slots), tokens,
            positions, arr((slots,), jnp.bool_), keys, arr((slots,), F32),
            arr((slots,), I32), arr((slots,), F32),
        )
        return jax.jit(fn, **layered), args, pool
    if name == "chunk":
        fn = functools.partial(pe._chunk_program, config=config, block_size=bs)
        table_row = tables()
        if cache_kind(config) is RecurrentRows:
            # A chunk addresses its slot's recurrent state by the slot's id.
            table_row = {"blocks": table_row, "slot": scalar}
        args = (
            params, lm_head, pool, moe, table_row, arr((1, bucket), I32),
            scalar, scalar, arr((2,), jnp.uint32), arr((), F32), scalar,
            arr((), F32), (tokens, positions, keys), scalar,
            arr((), jnp.bool_),
        )
        return jax.jit(fn, **layered), args, pool
    if name == "verify":
        from bpe_transformer_tpu.serving.spec.engine import _spec_verify_program

        k = 2
        fn = functools.partial(_spec_verify_program, config=config, block_size=bs)
        args = (
            params, lm_head, pool, arr((slots, nbs), I32), arr((slots,), I32),
            arr((slots, k), I32), arr((slots, k, config.vocab_size), F32),
            arr((slots,), I32), arr((slots,), I32), arr((slots,), jnp.bool_),
            arr((slots, 2), jnp.uint32), arr((slots,), F32),
            arr((slots,), I32), arr((slots,), F32),
        )
        return jax.jit(fn, **layered), args, pool
    if name == "copy_block":
        return (
            jax.jit(pe._copy_block_program, donate_argnums=(0,)),
            (pool, scalar, scalar), pool,
        )
    rows = _described(
        jax.eval_shape(lambda p: pe._extract_block_program(p, 0), pool),
        one_chip,
    )
    return (
        jax.jit(pe._inject_block_program, donate_argnums=(0,)),
        (pool, rows, scalar), pool,
    )


def _shape_text(a):
    tag = {"bfloat16": "bf16", "int8": "s8", "float32": "f32"}[str(a.dtype)]
    return f"{tag}[{','.join(str(n) for n in a.shape)}]"


def _pool_copies(text, pool_shapes):
    """Lines of the compiled text that copy or transpose an array of one of
    ``pool_shapes`` (as operand or result: a layout change keeps the
    shape)."""
    import re

    moves = re.compile(r" = \S+ (copy|copy-start|transpose)\(")
    return [
        line.strip()[:200] for line in text.splitlines()
        if moves.search(line) and any(s in line for s in pool_shapes)
    ]


def _relayouts_around(text, kernel):
    """The ``copy`` and ``transpose`` instructions of a compiled program that
    sit around the Mosaic call named ``kernel``: those that make one of its
    operands or take one of its results (through bitcasts, reshapes and tuple
    elements, which move nothing), and those XLA made for an operation traced
    in the kernel's scope (its metadata says so)."""
    import re

    lines = [line.strip() for line in text.splitlines()]
    defs = {line.split(" = ", 1)[0]: line for line in lines if " = " in line}
    moves = re.compile(r" = \S+ (copy|transpose)\(")
    passes = re.compile(r" = \S+ (bitcast|reshape|get-tuple-element)\(")
    (call,) = [l for l in lines if l.startswith(f"%{kernel}") and " custom-call(" in l]
    operands = re.findall(r"%[\w.-]+", call.split(" custom-call(", 1)[1].split("), ", 1)[0])

    def made_by(name, depth=0):  # the instruction behind the no-ops
        line = defs.get(name, "")
        if passes.search(line) and depth < 4:
            return made_by(re.findall(r"%[\w.-]+", line.split("(", 1)[1])[0], depth + 1)
        return line

    results, around = {call.split(" = ", 1)[0]}, [made_by(name) for name in operands]
    for line in lines:  # users of the call's results, no-ops followed
        used = set(re.findall(r"%[\w.-]+", line.split(" = ", 1)[-1]))
        if " = " in line and used & results:
            if passes.search(line):
                results.add(line.split(" = ", 1)[0])
            else:
                around.append(line)
    around += [l for l in lines if f"/{kernel}/" in l]
    return sorted({line[:200] for line in around if moves.search(line)})


@pytest.mark.parametrize(
    "name,kv_dtype",
    [("tick", None), ("chunk", None), ("copy_block", None),
     ("inject_block", None), ("tick", "int8"), ("verify", None)],
    ids=["tick", "chunk256", "copy_block", "inject_block", "tick-int8",
         "verify2"],
)
def test_pool_programs_hold_no_pool_copy(one_chip, name, kv_dtype):
    import dataclasses

    config = dataclasses.replace(GPT2_SMALL_32K, num_layers=2)
    jitted, args, pool = _pool_program(name, config, one_chip, kv_dtype)
    compiled = jitted.lower(*args).compile()
    leaves = jax.tree_util.tree_leaves(pool)
    shapes = {_shape_text(a) for a in leaves if a.ndim == 3}
    assert shapes == {f"{'s8' if kv_dtype else 'bf16'}[2049,16,768]"}
    assert _pool_copies(compiled.as_text(), shapes) == []
    memory = compiled.memory_analysis()
    kv_bytes = sum(a.size * a.dtype.itemsize for a in leaves if a.ndim == 3)
    # The K/V arrays rest unpadded and are aliased whole (an int8 pool's
    # (blocks, heads) scale rows are padded to the lane width, so they
    # alias to more than they hold); all the temporaries of a program over
    # the activation-width pool are smaller than one of its arrays.
    assert memory.alias_size_in_bytes >= kv_bytes
    if kv_dtype is None:
        assert memory.alias_size_in_bytes == kv_bytes
        assert memory.temp_size_in_bytes < kv_bytes // (2 * config.num_layers)


def _cell_config(cell: str):
    """A serve cell's configuration at its published widths, cut to the
    fewest layers that hold one of each kind it has."""
    import dataclasses
    import json
    from pathlib import Path

    from bpe_transformer_tpu.models.config import ModelConfig

    if cell in ("small", "medium"):
        preset = GPT2_SMALL_32K if cell == "small" else GPT2_MEDIUM
        return dataclasses.replace(preset, num_layers=2)
    name, cut = {
        "cmdaplus": ("command-a-plus-05-2026", {}),  # one period as it is
        "longcat": ("LongCat-Flash-Omni", {"num_layers": 1}),
        "granite": ("granite-4.0-h-small", {
            "num_layers": 2, "attn_layer_period": 2, "attn_layer_offset": 1,
        }),
        "nemotron": ("NVIDIA-Nemotron-3-Nano-30B-A3B-BF16", {
            "num_layers": 3, "layer_pattern": "ME*",
        }),
        # The leading dense layer, a window layer and a full layer that routes.
        "mimo": ("MiMo-V2.5", {"num_layers": 3, "layer_pattern": "Awa"}),
        # The leading dense layer and a layer that routes, latent attention
        # in both.
        "sarvam": ("sarvam-105b", {"num_layers": 2, "layer_pattern": "Aa"}),
    }[cell]
    path = Path(__file__).resolve().parents[1] / f"chipbench/configs/{name}.json"
    file = json.loads(path.read_text())
    return ModelConfig(**{**{k: file[k] for k in file["architecture_keys"]}, **cut})


def _donated(lowered_text: str) -> int:
    """Arguments of the lowered module's ``main`` that the caller donates."""
    import re

    head = lowered_text[lowered_text.index("func.func public @main("):]
    head = head[: head.index(") -> ")]
    return len(re.findall(r"tf\.aliasing_output|jax\.buffer_donor", head))


@pytest.mark.parametrize("name", ["tick", "chunk"], ids=["tick", "chunk256"])
@pytest.mark.parametrize(
    "cell", ["small", "medium", "cmdaplus", "longcat", "granite", "nemotron"]
)
def test_the_carry_rides_through_both_programs_undonated(one_chip, on_tpu, cell, name):
    """The decode carry (ISSUE 37) lowered for the described v5e, at the
    cells' widths: the tick takes ``tokens``, ``positions`` and ``keys`` in
    the shapes and types it always took and hands them back, with no write
    of its own into them; a chunk takes all three, writes its slot's entry
    of each (three scatters under ``carry_write``, nothing else there but
    their selects) and hands them back; and neither program donates
    anything but the pool - the launch before still holds the arrays the
    host has yet to read."""
    config = _cell_config(cell)
    jitted, args, pool = _pool_program(name, config, one_chip, None, slots=8)
    carry = [((8,), I32), ((8,), I32), ((8, 2), jnp.uint32)]
    outs = jax.tree_util.tree_leaves(jax.eval_shape(jitted, *args))
    if name == "tick":
        assert [(a.shape, a.dtype) for a in (args[5], args[6], args[8])] == carry
        assert [(o.shape, o.dtype) for o in outs[:3]] == carry
    else:
        assert [(o.shape, o.dtype) for o in outs[1:4]] == carry
    traced = jitted.trace(*args)
    assert _donated(traced.lower().as_text()) == len(jax.tree_util.tree_leaves(pool))
    written = [
        str(eqn.primitive) for eqn in traced.jaxpr.eqns
        if "carry_write" in str(eqn.source_info.name_stack)
    ]
    assert written.count("scatter") == (3 if name == "chunk" else 0)
    assert (written != []) == (name == "chunk")


@pytest.fixture
def on_tpu(monkeypatch):
    """The program's own choices as it makes them on the chip (they ask
    `jax.default_backend()`, which says "cpu" beside a described device):
    the dense tick takes the paged-native kernel, compiled by Mosaic."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["act", "int8"])
def test_the_tick_reads_the_pool_in_place(one_chip, on_tpu, kv_dtype):
    """The tick at the small cell's 128 slots (two layers): the pool is
    aliased whole and never copied, nothing of gathered-rows shape exists -
    neither the table's blocks ``[8192,16,768]`` nor its rows
    ``[128,1024,768]`` at any width - the kernel is there once a layer, and
    the temporaries stay under 100 MB where the gathered K and V were 201
    MB each."""
    import dataclasses
    import re

    config = dataclasses.replace(GPT2_SMALL_32K, num_layers=2)
    jitted, args, pool = _pool_program(
        "tick", config, one_chip, kv_dtype, slots=128, blocks=128 * 64 + 1
    )
    compiled = jitted.lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= config.num_layers
    assert "paged_decode_attention" in text
    leaves = jax.tree_util.tree_leaves(pool)
    assert _pool_copies(text, {_shape_text(a) for a in leaves if a.ndim == 3}) == []
    gathered = re.compile(r"\[(8192,16|128,64,16|128,1024),768\]")
    assert [line[:160] for line in text.splitlines() if gathered.search(line)] == []
    memory = compiled.memory_analysis()
    kv_bytes = sum(a.size * a.dtype.itemsize for a in leaves if a.ndim == 3)
    assert memory.alias_size_in_bytes >= kv_bytes
    assert memory.temp_size_in_bytes < 100e6


def _sorts(text):
    """The compiled text's sort instructions, one line each."""
    import re

    return [
        line.strip()[:200] for line in text.splitlines()
        if re.search(r"(?<![\w.%-])sort\(", line)
    ]


def _branch_signatures(text):
    """The signature line of every computation that a ``conditional`` of
    the compiled text branches to: its parameters' and its result's
    types."""
    import re

    names = re.findall(
        r" conditional\(.*?branch_computations=\{([^}]*)\}", text
    )
    heads = {
        line.split(" ", 1)[0]: line
        for line in text.splitlines() if line.startswith("%")
    }
    return [
        heads[name.strip()] for group in names for name in group.split(",")
    ]


def test_a_sorting_sampler_would_be_caught(one_chip):
    """What `_sorts` is for: the sampler's filter as it was until PR 34
    compiles to stable sorts of the whole f32[128,32000] logits."""
    (arg,) = _described((jnp.zeros((128, 32000), F32),), one_chip)
    text = jax.jit(lambda x: jnp.sort(x, axis=-1)).lower(arg).compile().as_text()
    assert [line for line in _sorts(text) if "[128,32000]" in line] != []


@pytest.mark.parametrize(
    "name", ["tick", "chunk", "verify"], ids=["tick", "chunk256", "verify2"]
)
def test_dense_serving_programs_hold_no_sort(one_chip, on_tpu, name):
    """The sampler finds its two cut-offs by threshold search
    (`ops/sampling.py`): the tick, a chunk and the verify pass at the small
    cell's 128 slots and 32,000 columns (two layers) compile for the v5e
    to a text with no sort at all - two stable sorts of f32[128,32000]
    were 59% of the tick's device time (PERF.md, PR 34).  The searches'
    scopes are there, under a conditional each, and each search makes its
    uint32 keys inside its branch: what crosses into a branch is the
    float32 logits and per-row vectors, never a key for every logit -
    a search handed its keys from outside reads them from HBM on all 32
    passes, 0.72 against 0.13 ms a 128-row tick (PERF.md section 6, PR 34,
    call C)."""
    import dataclasses
    import re

    config = dataclasses.replace(GPT2_SMALL_32K, num_layers=2)
    jitted, args, _ = _pool_program(
        name, config, one_chip, None, slots=128, blocks=128 * 64 + 1
    )
    text = jitted.lower(*args).compile().as_text()
    assert _sorts(text) == []
    assert "sample/top_k" in text and "sample/top_p" in text
    branches = _branch_signatures(text)
    assert len(branches) == 4  # search or keep all, for either filter
    wide = [re.findall(r"(\w+)\[\d+,32000\]", line) for line in branches]
    assert sorted(map(tuple, wide)) == [(), (), ("f32",), ("f32",)]


def test_pool_programs_compile_their_layers_as_calls(one_chip, monkeypatch):
    """With one pool alive the chip has memory to spare, and XLA then
    writes every layer's code out: gpt2-medium's tick went 20 -> 78 MB and
    its 1,024-token chunk program 26 -> 126 MB, which a 192 MiB compile
    cache cannot keep, so every start compiled cold (PERF.md §6 PR 30).
    `layered_program_options` asks for the layers as calls; at two layers
    the executable is already a third smaller, and the gap grows with the
    depth.  The tick over the paged-native kernel (the TPU's choice, PR 32)
    is no larger than the tick over gathered rows: a Mosaic call a layer
    costs less text than the gather and the rows attention it replaces."""
    import dataclasses

    from jax.experimental import serialize_executable

    config = dataclasses.replace(GPT2_SMALL_32K, num_layers=2)
    size = {}
    for as_calls in (True, False):
        jitted, args, _ = _pool_program("tick", config, one_chip, None, as_calls)
        compiled = jitted.lower(*args).compile()
        size[as_calls] = len(serialize_executable.serialize(compiled)[0])
    assert size[True] < 0.8 * size[False], size
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jitted, args, _ = _pool_program("tick", config, one_chip, None)
    compiled = jitted.lower(*args).compile()
    assert "paged_decode_attention" in compiled.as_text()
    size["paged"] = len(serialize_executable.serialize(compiled)[0])
    assert size["paged"] <= 1.02 * size[True], size


def test_a_four_dimensional_pool_would_be_copied(one_chip):
    """Why the pool's rows are ``kv_heads * d_head`` wide and not ``(...,
    kv_heads, d_head)``: with 64 as the minor dimension of a
    four-dimensional array the chip rests it block-axis-minor, which no
    scatter or gather indexes, and the detector above sees the copies.  If
    this stops holding, the comment in `init_kv_pool` is out of date."""
    shape = (513, 16, 12, 64)

    def program(pool, ids, offsets, rows, tables):
        pool = pool.at[ids, offsets].set(rows)
        return pool, jnp.sum(pool[tables].astype(F32), axis=1)

    args = [
        jax.ShapeDtypeStruct(s, d, sharding=one_chip)
        for s, d in (
            (shape, BF16), ((8,), I32), ((8,), I32), ((8, 12, 64), BF16),
            ((8, 64), I32),
        )
    ]
    text = jax.jit(program, donate_argnums=(0,)).lower(*args).compile().as_text()
    assert len(_pool_copies(text, {"bf16[513,16,12,64]"})) >= 2


def _head_shapes(vocab, d, head_dtype):
    if head_dtype == I8:
        return [((vocab, d), I8), ((vocab,), F32)]
    return [((vocab, d), BF16)]


def _as_head(head_args):
    return (
        {"q": head_args[0], "scale": head_args[1]}
        if len(head_args) == 2 else head_args[0]
    )


#: One head width per (kernel, vocab): the int8 head differs from the bf16
#: one by a (block_v, 1) scale tile, and each of these compiles costs 2-7 s.
SAMPLE_HEADS = [
    pytest.param(10_000, 512, I8, id="v10000-int8"),
    pytest.param(32_000, 768, BF16, id="v32000-bf16"),
]
VERIFY_HEADS = [
    pytest.param(10_000, 256, BF16, id="v10000-bf16"),
    pytest.param(32_000, 1024, I8, id="v32000-int8"),
]


@pytest.mark.parametrize("vocab,d,head_dtype", SAMPLE_HEADS)
def test_fused_head_sample(one_chip, vocab, d, head_dtype):
    rows = 8
    head = _head_shapes(vocab, d, head_dtype)

    def fn(hidden, temps, top_ks, top_ps, gumbel, *head_args):
        return fused_head_sample(
            hidden, _as_head(head_args), temps, top_ks, top_ps, gumbel,
            interpret=False,
        )

    _compile(
        fn, one_chip, ((rows, d), BF16), ((rows,), F32), ((rows,), I32),
        ((rows,), F32), ((rows, vocab), F32), *head,
    )


@pytest.mark.parametrize("vocab,d,head_dtype", VERIFY_HEADS)
def test_fused_verify_head(one_chip, vocab, d, head_dtype):
    rows = 8 * 3  # slots * (K + 1)
    head = _head_shapes(vocab, d, head_dtype)

    def fn(hidden, temps, top_ks, top_ps, judge, q_probs, gumbel, *head_args):
        return fused_verify_head(
            hidden, _as_head(head_args), temps, top_ks, top_ps, judge,
            q_probs, gumbel, interpret=False,
        )

    _compile(
        fn, one_chip, ((rows, d), BF16), ((rows,), F32), ((rows,), I32),
        ((rows,), F32), ((rows,), I32), ((rows, vocab), F32),
        ((rows, vocab), F32), *head,
    )


# ----------------------------- command-a-plus-05-2026 (Cohere2-MoE) serving


@pytest.mark.parametrize(
    "rows", [256, 4096, 16384], ids=["tick_32_slots", "chunk_512", "chunk_2048"]
)
def test_grouped_matmul(one_chip, monkeypatch, rows):
    """The expert layer's grouped matmul at the published widths: 8
    assignments a token, 16 experts held, 4,096 x 4,096 matrices."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _compile(
        grouped_matmul, one_chip,
        ((rows, 4096), BF16), ((16, 4096, 4096), BF16), ((16,), I32),
    )


@pytest.mark.parametrize(
    "tokens,seqs,pages,window",
    [(32, 32, 1024, None), (32, 32, 384, 4096), (2048, 1, 1024, None),
     (2048, 1, 384, 4096), (512, 1, 384, 4096)],
    ids=["tick_full", "tick_window", "chunk_full", "chunk_window", "chunk512_window"],
)
def test_ragged_paged_attention(one_chip, monkeypatch, tokens, seqs, pages, window):
    """The grouped pools' attention: 128 query heads on 8 KV heads of 128,
    pages of 16 positions, a full group's row of 1,024 pages and a window
    group's of (4,096 + 2,048) / 16."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def fn(q, kv_pages, kv_lens, rows, cu_q_lens, num_seqs):
        return ragged_paged_attention(
            q, kv_pages, kv_lens, rows, cu_q_lens, num_seqs, window=window,
            one_query_per_seq=seqs > 1,
        )

    _compile(
        fn, one_chip, ((tokens, 128, 128), BF16), ((2049, 16, 16, 128), BF16),
        ((seqs,), I32), ((seqs, pages), I32), ((seqs + 1,), I32), ((1,), I32),
    )


# ------------------------------------- LongCat-Flash-Omni (latent attention)


def _mla_kernels(text) -> set:
    """The latent attention's Mosaic kernels in a compiled text, by the
    names their device events carry (the instruction's, less its number)."""
    import re

    return set(re.findall(
        r"%(mla_(?:paged|chunk)_attention[a-z_]*)\.\d+ = [^\n]*custom-call\(",
        text,
    ))


@pytest.mark.parametrize("slots", [64, 8], ids=["64_slots", "8_slots"])
def test_mla_paged_attention(one_chip, slots):
    """The tick's absorbed latent attention at the published widths: 64
    heads against latent rows of 576 values in a pool whose rows are padded
    to 640 lanes, blocks of 16, a table of 1,024 blocks.  (At 576 lanes the
    compiler refuses the block's slice: not a whole tile.)  Two kernels, the
    shared pass and the own pass, and both device events start with the
    name that `mla_paged_attention_roofline` reads."""
    from bpe_transformer_tpu.kernels.pallas.mla_attention import (
        mla_paged_attention,
    )

    def fn(q, pool, tables, counts):
        return mla_paged_attention(
            q, pool, tables, counts, rank=512, scale=192 ** -0.5,
            path="mla_paged", interpret=False,
        )

    text = _compile(
        fn, one_chip, ((slots, 64, 576), BF16), ((20481, 16, 640), BF16),
        ((slots, 1024), I32), ((slots,), I32),
    )
    assert _mla_kernels(text) == {"mla_paged_attention", "mla_paged_attention_shared"}


@pytest.mark.parametrize("queries", [256, 512, 1024, 2048])
def test_mla_chunk_attention(one_chip, queries):
    """A chunk's expanded latent attention at the published widths: 64
    heads of 128 + 64 / 128 over a slot's gathered chain of 16,384 rows as
    the pool pads them (640 lanes), every bucket the rule admits (a grid
    step is one head against the whole bucket, 2,048 rows in four tiles of
    512, the chain left in HBM and copied a block of 512 rows at a time: the
    step's buffers, its expanded block, its state and a tile's scores fit
    `MLA_SHARED_VMEM_BYTES`, or the compile would refuse); the device
    event's name is the one `mla_chunk_attention_roofline` reads, and not
    the tick's."""
    from bpe_transformer_tpu.kernels.pallas.mla_attention import (
        mla_chunk_attention,
        mla_chunk_path,
    )

    assert mla_chunk_path(queries, 128, 128, 512, backend="tpu") == "mla_chunk"

    def fn(q_nope, q_rope, rows, kv_b, positions, n_keys):
        return mla_chunk_attention(
            q_nope, q_rope, rows, kv_b, positions, n_keys, scale=192 ** -0.5,
            interpret=False,
        )

    text = _compile(
        fn, one_chip, ((64, queries, 128), BF16), ((64, queries, 64), BF16),
        ((16384, 640), BF16), ((64, 256, 512), BF16), ((queries,), I32),
        ((), I32),
    )
    assert _mla_kernels(text) == {"mla_chunk_attention"}


def test_mla_self_attention_takes_the_chunk_kernel_a_sequence_at_a_time(
    one_chip, on_tpu
):
    """The plain forward's latent sublayer over two sequences of 512 rows at
    the published widths: the kernel copies a sequence's rows out of HBM
    itself and so takes no batch axis (`mla.self_attention` maps over the
    sequences where the rule picks the kernel, and `vmap`s the loop)."""
    from bpe_transformer_tpu.models import mla

    config = _cell_config("longcat")
    assert mla.rows_attention_path(512, config) == "mla_chunk"
    h, attn, positions = _described(
        (
            jax.ShapeDtypeStruct((2, 512, config.d_model), BF16),
            jax.eval_shape(
                lambda: mla.init_mla_params(jax.random.PRNGKey(0), config, BF16)
            ),
            jax.ShapeDtypeStruct((512,), I32),
        ),
        one_chip,
    )
    text = jax.jit(
        lambda h, p, positions: mla.self_attention(h, p, positions, config)
    ).lower(h, attn, positions).compile().as_text()
    assert _mla_kernels(text) == {"mla_chunk_attention"}


@pytest.mark.parametrize(
    "rows", [768, 6144, 24576], ids=["tick_64_slots", "chunk_512", "chunk_2048"]
)
@pytest.mark.parametrize("d_out,d_in", [(2048, 6144), (6144, 2048)], ids=["up", "down"])
def test_grouped_matmul_at_longcat_widths(one_chip, monkeypatch, rows, d_out, d_in):
    """12 assignments a token, 16 experts of 2,048 held, hidden 6,144."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _compile(
        grouped_matmul, one_chip,
        ((rows, d_in), BF16), ((16, d_out, d_in), BF16), ((16,), I32),
    )


@pytest.mark.parametrize("name", ["tick", "chunk"], ids=["tick", "chunk256"])
def test_latent_pool_programs(one_chip, on_tpu, name):
    """The engine's two programs over a latent pool, one double layer at
    the published widths: the tick's two kernels or the chunk's one are
    there (256 rows: the smallest bucket that attends in the expanded
    form), the pool's two arrays are aliased whole and never copied."""
    import json
    from pathlib import Path

    from bpe_transformer_tpu.models.config import ModelConfig

    path = Path(__file__).resolve().parents[1] / "chipbench/configs/LongCat-Flash-Omni.json"
    file = json.loads(path.read_text())
    config = ModelConfig(**{
        **{k: file[k] for k in file["architecture_keys"]}, "num_layers": 1,
    })
    jitted, args, pool = _pool_program(name, config, one_chip, None, slots=8)
    compiled = jitted.lower(*args).compile()
    text = compiled.as_text()
    assert _mla_kernels(text) == (
        {"mla_paged_attention", "mla_paged_attention_shared"}
        if name == "tick" else {"mla_chunk_attention"}
    )
    assert "gmm" in text
    # The expert layer may sort assignments by expert (`models/moe.py`); the
    # sampler sorts nothing, so no sort has a vocabulary-wide operand.
    assert [line for line in _sorts(text) if f",{config.vocab_size}]" in line] == []
    leaves = jax.tree_util.tree_leaves(pool)
    assert {_shape_text(a) for a in leaves} == {"bf16[2049,16,640]"} and len(leaves) == 2
    assert _pool_copies(text, {"bf16[2049,16,640]"}) == []
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= sum(a.size * 2 for a in leaves)


# ------------------------------ granite-4.0-h-small (state-space layers)


def _compiled_ssm_state_update(one_chip, slots, heads, channels, n, groups=1):
    """``(compiled, the resting states' shape)``: `ssm_state_update`'s kernel
    for the described chip over ``slots`` tick rows and ``slots + 1`` states
    where they rest (`to_resting`'s shape), the states donated."""
    import functools

    from bpe_transformer_tpu.kernels.pallas.ssm import ssm_state_update, to_resting

    resting = jax.eval_shape(
        functools.partial(to_resting, groups=groups),
        jax.ShapeDtypeStruct((slots + 1, heads, channels, n), F32),
    ).shape
    by_row = (slots, n) if groups == 1 else (slots, groups, n)
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in (
            (resting, F32), ((slots,), I32), ((slots, heads, channels), BF16),
            ((slots, heads), F32), ((heads,), F32), (by_row, BF16), (by_row, BF16),
            ((heads,), F32),
        )
    ]
    fn = functools.partial(ssm_state_update, path="pallas")
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ssm_state_update" in text
    return compiled, resting


@pytest.mark.parametrize("slots", [96, 8], ids=["96_slots", "8_slots"])
def test_ssm_state_update(one_chip, on_tpu, slots):
    """The tick's one-step update at the published widths: 128 heads of 64
    channels over a state of 128 - resting two heads a lane row, ``(slots +
    1, 64, 128, 128)`` - float32 states a row a slot and one of trash,
    updated in place (aliased whole, no temporary of their size)."""
    compiled, resting = _compiled_ssm_state_update(one_chip, slots, 128, 64, 128)
    assert resting == (slots + 1, 64, 128, 128)
    memory = compiled.memory_analysis()
    states = (slots + 1) * 128 * 64 * 128 * 4
    assert memory.alias_size_in_bytes == states
    assert memory.temp_size_in_bytes < states // 16


@pytest.mark.parametrize(
    "rows", [960, 2560, 10240], ids=["tick_96_slots", "chunk_256", "chunk_1024"]
)
@pytest.mark.parametrize("d_out,d_in", [(768, 4096), (4096, 768)], ids=["up", "down"])
def test_grouped_matmul_at_granite_widths(one_chip, monkeypatch, rows, d_out, d_in):
    """10 assignments a token, 36 experts of 768 held, hidden 4,096: an
    output width and a contraction of 768 (`grouped_matmul._tiling`)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _compile(
        grouped_matmul, one_chip,
        ((rows, d_in), BF16), ((36, d_out, d_in), BF16), ((36,), I32),
    )


def test_paged_decode_attention_at_granite_widths(one_chip):
    """The attention layer's tick: 32 query heads over 8 KV heads of 128,
    96 slots, a table one context (4,096 keys) wide, scores times 1/128."""

    def fn(q, k, v, tables, counts):
        return paged_decode_attention(
            q, k, v, tables, counts, interpret=False, scale=0.0078125
        )

    pool = ((12289, 16, 8 * 128), BF16)
    _compile(
        fn, one_chip, ((96, 32, 128), BF16), pool, pool, ((96, 256), I32),
        ((96,), I32),
    )


@pytest.mark.parametrize("name", ["tick", "chunk"], ids=["tick", "chunk256"])
def test_recurrent_pool_programs(one_chip, on_tpu, name):
    """The engine's two programs over a recurrent pool, a Mamba-2 layer and
    an attention layer at the published widths: the kernels are there (the
    chunk scans and attends in XLA), and the pool - state rows, conv rows, K
    and V - is aliased whole and never copied."""
    import json
    from pathlib import Path

    from bpe_transformer_tpu.models.config import ModelConfig

    path = Path(__file__).resolve().parents[1] / "chipbench/configs/granite-4.0-h-small.json"
    file = json.loads(path.read_text())
    config = ModelConfig(**{
        **{k: file[k] for k in file["architecture_keys"]}, "num_layers": 2,
        "attn_layer_period": 2, "attn_layer_offset": 1,
    })
    jitted, args, pool = _pool_program(name, config, one_chip, None, slots=8)
    compiled = jitted.lower(*args).compile()
    text = compiled.as_text()
    # By the Mosaic calls themselves (an instruction is named after its
    # kernel): the text's header tables name functions of the whole trace.
    calls = " ".join(l.split("=")[0] for l in text.splitlines() if " custom-call(" in l)
    assert ("ssm_state_update" in calls) == (name == "tick")
    assert ("paged_decode_attention" in calls) == (name == "tick")
    assert "gmm" in calls
    if name == "tick":
        # What the kernel takes lies as the layers before it left it, and its
        # rows leave as the gate reads them: no copy, no transpose.
        assert _relayouts_around(text, "ssm_state_update") == []
    assert [line for line in _sorts(text) if f",{config.vocab_size}]" in line] == []
    leaves = jax.tree_util.tree_leaves(pool)
    shapes = {_shape_text(a) for a in leaves}
    assert shapes == {"f32[9,64,128,128]", "bf16[9,3,8448]", "bf16[2049,16,1024]"}
    assert _pool_copies(text, {"f32[9,64,128,128]", "bf16[2049,16,1024]"}) == []
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize for a in leaves)


# ------- NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 (a sublayer a layer, groups)


@pytest.mark.parametrize("slots", [192, 8], ids=["192_slots", "8_slots"])
def test_ssm_state_update_by_group(one_chip, on_tpu, slots):
    """The tick's one-step update with ``B`` and ``C`` by group at the
    published widths: 64 heads of 64 channels in 8 groups over a state of
    128 - resting two heads a lane row, ``(slots + 1, 32, 128, 128)``; one
    block of head rows holds the 8 groups - float32 states updated in place
    (aliased whole, no temporary of their size)."""
    compiled, resting = _compiled_ssm_state_update(one_chip, slots, 64, 64, 128, 8)
    assert resting == (slots + 1, 32, 128, 128)
    memory = compiled.memory_analysis()
    states = (slots + 1) * 64 * 64 * 128 * 4
    assert memory.alias_size_in_bytes == states
    assert memory.temp_size_in_bytes < states // 16


@pytest.mark.parametrize(
    "slots, heads, channels, n, groups, resting",
    [(8, 5, 64, 128, 1, (9, 5, 128, 64)), (8, 6, 64, 128, 6, (9, 6, 128, 64)),
     (8, 3, 256, 128, 1, (9, 3, 128, 256)), (12, 16, 64, 128, 2, (13, 8, 128, 128)),
     (8, 24, 64, 64, 1, (9, 12, 64, 128))],
    ids=["odd_heads", "a_head_a_group", "channels_over_a_row", "12_rows_2_groups", "state_of_64"],
)
def test_ssm_state_update_where_the_packing_does_not_fit(
    one_chip, on_tpu, slots, heads, channels, n, groups, resting
):
    """Shapes no served configuration has: odd heads, a group of one head
    and channels wider than a lane row rest a head a row (``k = 1``) and take
    the same body; rows that are no whole tile of 8, a narrower state."""
    import math

    compiled, rests = _compiled_ssm_state_update(one_chip, slots, heads, channels, n, groups)
    assert rests == resting
    assert compiled.memory_analysis().alias_size_in_bytes == 4 * math.prod(resting)


@pytest.mark.parametrize(
    "rows", [1152, 1536, 6144], ids=["tick_192_slots", "chunk_256", "chunk_1024"]
)
@pytest.mark.parametrize("d_out,d_in", [(1856, 2688), (2688, 1856)], ids=["up", "down"])
def test_grouped_matmul_at_nemotron_widths(one_chip, monkeypatch, rows, d_out, d_in):
    """6 assignments a token, 64 experts of 1,856 held, hidden 2,688 (21 x
    128): an output width and a contraction of 1,856 = 14.5 x 128
    (`grouped_matmul._tiling`)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _compile(
        grouped_matmul, one_chip,
        ((rows, d_in), BF16), ((64, d_out, d_in), BF16), ((64,), I32),
    )


def test_paged_decode_attention_at_nemotron_widths(one_chip):
    """The attention layers' tick: 32 query heads over 2 KV heads of 128 - a
    group of 16 query rows a KV head, rows of 256 lanes - 192 slots, a table
    one context (3,072 keys) wide over the cell's 36,865 blocks."""

    def fn(q, k, v, tables, counts):
        return paged_decode_attention(q, k, v, tables, counts, interpret=False)

    pool = ((36865, 16, 2 * 128), BF16)
    _compile(
        fn, one_chip, ((192, 32, 128), BF16), pool, pool, ((192, 192), I32),
        ((192,), I32),
    )


@pytest.mark.parametrize("name", ["tick", "chunk"], ids=["tick", "chunk256"])
def test_pool_programs_of_one_sublayer_layers(one_chip, on_tpu, name):
    """The engine's two programs over a Mamba-2 layer, an expert layer and
    an attention layer of one sublayer each at the published widths: the
    kernels are there (the chunk scans and attends in XLA), the expert
    layer's grouped matmuls are two an expert layer, the layer without a
    mixer has no pool entry, and the pool - state rows, conv rows, K and V -
    is aliased whole and never copied."""
    config = _cell_config("nemotron")
    jitted, args, pool = _pool_program(name, config, one_chip, None, slots=8)
    assert [sorted(entry) for entry in pool] == [["conv", "ssm"], [], ["k", "v"]]
    compiled = jitted.lower(*args).compile()
    text = compiled.as_text()
    calls = [l.split("=")[0] for l in text.splitlines() if " custom-call(" in l]
    assert any("ssm_state_update" in c for c in calls) == (name == "tick")
    assert any("paged_decode_attention" in c for c in calls) == (name == "tick")
    assert sum("gmm" in c for c in calls) == 2
    if name == "tick":
        assert _relayouts_around(text, "ssm_state_update") == []
    assert [line for line in _sorts(text) if f",{config.vocab_size}]" in line] == []
    leaves = jax.tree_util.tree_leaves(pool)
    shapes = {_shape_text(a) for a in leaves}
    assert shapes == {"f32[9,32,128,128]", "bf16[9,3,6144]", "bf16[2049,16,256]"}
    assert _pool_copies(text, {"f32[9,32,128,128]", "bf16[2049,16,256]"}) == []
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= sum(a.size * a.dtype.itemsize for a in leaves)


def _expert_stacks(params):
    """The shapes of the expert layers' stacked matrices (an ``ffn`` tree's
    leaves of three dimensions, the shared expert's included)."""
    return {
        _shape_text(leaf)
        for layer in params["layers"] if "ffn" in layer
        for leaf in jax.tree_util.tree_leaves(layer["ffn"]) if leaf.ndim == 3
    }


@pytest.mark.parametrize("name", ["tick", "chunk"], ids=["tick", "chunk256"])
@pytest.mark.parametrize("cell", ["nemotron", "cmdaplus", "longcat", "granite"])
def test_expert_stacks_are_read_where_they_rest(one_chip, on_tpu, cell, name):
    """The tree the weight pipeline hands the engine, at the cells' widths:
    no program copies or transposes an expert stack on its way into the
    grouped matmul.  nemotron's down projection is there relaid, in the
    shape its up projection has (`models/moe.serving_layout`: an expert
    width of 14.5 lane tiles); the other cells' widths are whole tiles and
    their trees the torch layout's."""
    config = _cell_config(cell)
    jitted, args, _ = _pool_program(name, config, one_chip, None, slots=8)
    stacks = _expert_stacks(args[0])
    relaid = "bf16[64,1856,2688]" in stacks
    assert relaid == (cell == "nemotron")
    assert "bf16[64,2688,1856]" not in stacks
    compiled = jitted.lower(*args).compile()
    assert _pool_copies(compiled.as_text(), stacks) == []
    if relaid:  # ... and no temporary is as large as a stack
        assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2688 * 1856 * 2


@pytest.mark.parametrize("name", ["tick", "chunk"], ids=["tick", "chunk256"])
def test_a_torch_layout_stack_of_nemotrons_width_would_be_copied(
    one_chip, on_tpu, monkeypatch, name
):
    """Why the pipeline relays it: over the torch-layout tree the same
    programs copy the whole ``(64, 2688, 1856)`` stack before the grouped
    matmul - the chip rests a parameter whose minor dimension is 14.5 lane
    tiles with the 2,688 minor, the transposed call wants it as its shape
    says - once an expert layer, in every launch (0.64 GB and 1.6 ms a layer
    at the cell's size, PERF.md section 6, PR 43), and the temporaries hold
    it.  If this stops holding the relayout is no longer needed; if the
    test above fails, a change brought the copy back."""
    from bpe_transformer_tpu.models import moe

    monkeypatch.setattr(moe, "serving_layout", lambda ffn: ffn)
    config = _cell_config("nemotron")
    jitted, args, _ = _pool_program(name, config, one_chip, None, slots=8)
    stack = "bf16[64,2688,1856]"
    assert stack in _expert_stacks(args[0])
    compiled = jitted.lower(*args).compile()
    copies = _pool_copies(compiled.as_text(), {stack})
    assert len(copies) == 1 and " copy(" in copies[0]
    assert compiled.memory_analysis().temp_size_in_bytes > 64 * 2688 * 1856 * 2


# ------------------------------------------- the summary-and-window cache


def _evabyte_config(**cut):
    import json
    from pathlib import Path

    from bpe_transformer_tpu.models.config import ModelConfig

    path = Path(__file__).resolve().parents[1] / "chipbench/configs/EvaByte.json"
    file = json.loads(path.read_text())
    return ModelConfig(**{**{k: file[k] for k in file["architecture_keys"]}, **cut})


def test_paged_decode_attention_at_evabyte_widths(one_chip):
    """The summary-and-window tick: 32 query heads over 32 K/V heads of 128
    (rows of 4,096 lanes, where gpt2 has 768 and granite 1,024), 32 slots, a
    table of 16 x 8 summary blocks and a window's 128."""

    def fn(q, k, v, tables, counts):
        return paged_decode_attention(q, k, v, tables, counts, interpret=False)

    pool = ((5825, 16, 32 * 128), BF16)
    _compile(
        fn, one_chip, ((32, 32, 128), BF16), pool, pool, ((32, 256), I32),
        ((32,), I32),
    )


@pytest.mark.parametrize(
    "name, bucket", [("tick", 0), ("chunk", 2048), ("chunk", 512)],
    ids=["tick", "chunk2048", "chunk512"],
)
def test_evabyte_pool_programs(one_chip, on_tpu, name, bucket):
    """The engine's two programs over the summary-and-window cache at the
    cell's widths - 32 slots, a pool of 5,825 blocks, chunks of the cell's
    largest and smallest bucket - cut to 2 of its 8 layers (every layer is
    the same block): the tick attends through the dense pool's paged kernel
    and no other Mosaic call, the chunk attends and summarises in XLA, the
    pool is aliased whole and never copied, and neither program's
    temporaries come near what the chip has beside weights and pool (the
    tick's stay under 64 MB, a 2,048-row chunk's under 512 MB: the full
    cell holds 3.26 GB of weights and 12.22 GB of pool of the chip's 16.9:
    15.9 GB with a chunk's temporaries, and the chip ran it)."""
    config = _evabyte_config(num_layers=2)
    jitted, args, pool = _pool_program(
        name, config, one_chip, None, slots=32, blocks=5825, bucket=bucket or 256
    )
    compiled = jitted.lower(*args).compile()
    text = compiled.as_text()
    calls = " ".join(l.split("=")[0] for l in text.splitlines() if " custom-call(" in l)
    assert ("paged_decode_attention" in calls) == (name == "tick")
    assert [line for line in _sorts(text) if f",{config.head_width}]" in line] == []
    leaves = jax.tree_util.tree_leaves(pool)
    assert {_shape_text(a) for a in leaves} == {"bf16[5825,16,4096]"}
    assert _pool_copies(text, {"bf16[5825,16,4096]"}) == []
    memory = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize for a in leaves)
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < (64 if name == "tick" else 512) * 2**20
    # All eight layers of pool, the weights and the temporaries fit the chip.
    weights = 2 * (8 * 202_391_552 + 11_800_576)
    assert 4 * pool_bytes + weights + memory.temp_size_in_bytes < 16.0e9


# ------------------------------- rows of keys and values by group (MiMo-V2.5)


@pytest.mark.parametrize("group", ["full", "window"])
def test_sink_paged_attention(one_chip, group):
    """The tick's kernel at the cell's widths, 64 slots: 64 query heads of
    192 over 4 K/V heads and a chain of 2,048 blocks (the full group; the
    whole table rides scalar prefetch), or over 8 K/V heads with a sink and
    rows of 136 blocks (the window group).  A row is K at 192 and V at 128 a
    head: 1,280 / 2,560 lanes, whole tiles."""
    from bpe_transformer_tpu.kernels.pallas.sink_attention import (
        sink_paged_attention,
    )

    kv_heads, blocks, row = (4, 73729, 2048) if group == "full" else (8, 713, 136)
    shapes = [
        ((64, 64, 192), BF16), ((blocks, 16, kv_heads * 320), BF16),
        ((64, row), I32), ((64,), I32), ((64,), I32),
    ]
    if group == "window":
        shapes.append(((64,), F32))

    def fn(q, pool, tables, counts, firsts, sink=None):
        return sink_paged_attention(
            q, pool, tables, counts, firsts, sink, kv_heads=kv_heads,
            window=group == "window", interpret=False,
        )

    text = _compile(fn, one_chip, *shapes)
    assert f"sink_paged_attention_{group}" in text


@pytest.mark.parametrize("bucket", [512, 1024, 2048])
@pytest.mark.parametrize("group", ["full", "window"])
def test_sink_chunk_attention(one_chip, group, bucket):
    """The chunk's kernel at the cell's widths and buckets: a full layer's
    gathered chain of 32,768 keys held in VMEM a K/V head (128 blocks of 256:
    25.2 MB with the key's 192 lanes in 256), a window layer's of 2,176 in 17
    blocks of 128; the walk over them is inside the kernel, so the grid is
    (K/V heads, blocks of 128 query rows, 1)."""
    from bpe_transformer_tpu.kernels.pallas.sink_attention import (
        chunk_held_blocks,
        chunk_tiles,
        sink_chunk_attention,
    )

    kv_heads, keys, window = (4, 32768, None) if group == "full" else (8, 2176, 128)
    tq, tk = chunk_tiles(bucket, keys, window)
    assert (tq, tk) == ((128, 256) if group == "full" else (128, 128))
    assert chunk_held_blocks(keys // tk, tk * (256 + 128) * 2) == keys // tk
    shapes = [
        ((bucket, 64, 192), BF16), ((keys, kv_heads, 192), BF16),
        ((keys, kv_heads, 128), BF16), ((), I32),
    ]
    if group == "window":
        shapes.append(((64,), F32))

    def fn(q, k, v, at, sink=None):
        return sink_chunk_attention(q, k, v, at, sink, window=window, interpret=False)

    text = _compile(fn, one_chip, *shapes)
    assert f"sink_chunk_attention_{group}" in text


@pytest.mark.parametrize("name", ["tick", "chunk"], ids=["tick", "chunk2048"])
def test_grouped_row_pool_programs(one_chip, on_tpu, name):
    """``mimo.serve.long-context``'s tick and chunk programs at its widths,
    slots, pool and bucket, cut to three layers (the dense leading layer, a
    window layer, a full layer that routes): both groups' kernels are there
    under their own names, the pool - a row K at 192 and V at 128 a head,
    1,280 lanes in the full group and 2,560 in the window group - is
    aliased whole and never copied, and the experts go through `gmm`."""
    config = _cell_config("mimo")
    jitted, args, pool = _pool_program(
        name, config, one_chip, None, False, slots=64, blocks=73729,
        bucket=2048, prefill_chunk=2048,
    )
    assert sorted({a.shape for a in pool}) == [(713, 16, 2560), (73729, 16, 1280)]
    compiled = jitted.lower(*args).compile()
    text = compiled.as_text()
    kernel = "sink_paged_attention" if name == "tick" else "sink_chunk_attention"
    assert f"{kernel}_full" in text and f"{kernel}_window" in text
    assert "gmm" in text and "block/ffn/dense" in text
    assert _pool_copies(text, {_shape_text(a) for a in pool}) == []
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= sum(a.size * 2 for a in pool)
    assert memory.temp_size_in_bytes < 700e6


# ------------- sarvam-105b (latent attention in the sequential block, YaRN)


#: ``sarvam.serve.doc-qa``'s softmax scale: ``192 ** -0.5`` times YaRN's
#: ``m(mscale_all_dim) ** 2``.
SARVAM_SCALE = 0.135234


def test_mla_paged_attention_at_a_32k_table(one_chip):
    """The tick's two kernels at ``sarvam.serve.doc-qa``'s sizes: 32 slots, a
    table of 2,048 blocks of 16 (32,768 positions a slot), the pool's 43,751
    blocks, under the config's scale."""
    from bpe_transformer_tpu.kernels.pallas.mla_attention import (
        mla_paged_attention,
    )

    def fn(q, pool, tables, counts):
        return mla_paged_attention(
            q, pool, tables, counts, rank=512, scale=SARVAM_SCALE,
            path="mla_paged", interpret=False,
        )

    text = _compile(
        fn, one_chip, ((32, 64, 576), BF16), ((43751, 16, 640), BF16),
        ((32, 2048), I32), ((32,), I32),
    )
    assert _mla_kernels(text) == {"mla_paged_attention", "mla_paged_attention_shared"}


@pytest.mark.parametrize("queries", [512, 1024, 2048])
def test_mla_chunk_attention_at_32k_keys(one_chip, queries):
    """A chunk's expanded latent attention over a slot's gathered chain of
    32,768 rows - twice the keys `test_mla_chunk_attention` compiles, the
    cell's three buckets, its scale: the grid is the 64 heads, the walk over
    up to 64 key blocks is inside the step, the bucket's state stays in VMEM
    and no (chunk x context) score leaves the kernel (the output is the
    heads' values alone)."""
    from bpe_transformer_tpu.kernels.pallas.mla_attention import (
        mla_chunk_attention,
        mla_chunk_path,
    )

    assert mla_chunk_path(queries, 128, 128, 512, backend="tpu") == "mla_chunk"

    def fn(q_nope, q_rope, rows, kv_b, positions, n_keys):
        return mla_chunk_attention(
            q_nope, q_rope, rows, kv_b, positions, n_keys, scale=SARVAM_SCALE,
            interpret=False,
        )

    text = _compile(
        fn, one_chip, ((64, queries, 128), BF16), ((64, queries, 64), BF16),
        ((32768, 640), BF16), ((64, 256, 512), BF16), ((queries,), I32),
        ((), I32),
    )
    assert _mla_kernels(text) == {"mla_chunk_attention"}
    assert f"f32[64,{queries},32768]" not in text and f"bf16[64,{queries},32768]" not in text


@pytest.mark.parametrize("rows", [256, 16384], ids=["tick_32_slots", "chunk_2048"])
@pytest.mark.parametrize("d_out,d_in", [(2048, 4096), (4096, 2048)], ids=["up", "down"])
def test_grouped_matmul_at_sarvam_widths(one_chip, monkeypatch, rows, d_out, d_in):
    """8 assignments a token, 32 experts of 2,048 held, hidden 4,096."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _compile(
        grouped_matmul, one_chip,
        ((rows, d_in), BF16), ((32, d_out, d_in), BF16), ((32,), I32),
    )


@pytest.mark.parametrize("name", ["tick", "chunk"], ids=["tick", "chunk2048"])
def test_latent_pool_programs_of_the_sequential_block(one_chip, on_tpu, name):
    """``sarvam.serve.doc-qa``'s tick and chunk programs at its widths,
    slots, pool and bucket, cut to two layers (the dense leading layer and a
    layer that routes): latent attention's kernels are there - one array a
    layer, not two - under the names the cell's metrics read, layer 0 runs
    under ``block/ffn/dense``, the experts go through `gmm` and the shared
    one under ``block/moe/shared``, and the pool is aliased whole and never
    copied.  The chunk attends over a table of 2,048 blocks: 32,768 rows."""
    config = _cell_config("sarvam")
    assert config.layer_kinds == "Aa" and not config.double_layer
    jitted, args, pool = _pool_program(
        name, config, one_chip, None, slots=32, blocks=43751, bucket=2048,
        prefill_chunk=2048,
    )
    leaves = jax.tree_util.tree_leaves(pool)
    assert [a.shape for a in leaves] == [(43751, 16, 640)] * 2
    compiled = jitted.lower(*args).compile()
    text = compiled.as_text()
    assert _mla_kernels(text) == (
        {"mla_paged_attention", "mla_paged_attention_shared"}
        if name == "tick" else {"mla_chunk_attention"}
    )
    assert "gmm" in text and "block/ffn/dense" in text and "block/moe/shared" in text
    assert "mla_q" in text and "mla_kv" in text
    assert [line for line in _sorts(text) if f",{config.vocab_size}]" in line] == []
    assert _pool_copies(text, {"bf16[43751,16,640]"}) == []
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= sum(a.size * 2 for a in leaves)
    assert memory.temp_size_in_bytes < 500e6
