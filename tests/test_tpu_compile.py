"""Compiles for a DESCRIBED v5e chip (no chip attached): what the chip's
compiler refuses costs no chip time. The one file that describes the
topology (only one process at a time may load the TPU's library), and it
does so inside a fixture, never while a module is imported."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("tokens", [512, 8192])
def test_grouped_expert_matmuls_compile_at_published_widths(
        one_chip, monkeypatch, tokens):
    """The expert layer's two grouped matmuls at the widths
    `nemotron3-super-ep4` runs (128 experts held, latent 1024, width
    2688, 22 a token), on the kernel the TPU branch picks."""
    from code2vec_tpu.ops import moe
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, held, lat, width = tokens * 22, 128, 1024, 2688

    def both(x, w1, w2, sizes):
        hidden = moe.grouped_matmul(x, w1, sizes, jnp.bfloat16)
        return moe.grouped_matmul(hidden, w2, sizes, jnp.float32)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    compiled = jax.jit(both).lower(
        shape((rows, lat), jnp.bfloat16),
        shape((held, lat, width), jnp.bfloat16),
        shape((held, width, lat), jnp.bfloat16),
        shape((held,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2 and "ragged-dot" not in text


@pytest.mark.parametrize("rows,target_rows", [
    (1024, 261245), (1024, 522490),
    (2048, 261245),     # `--batch_size 2048`: two blocks of rows
])
def test_the_heads_pass_b_kernel_compiles_at_published_widths(
        one_chip, rows, target_rows):
    """Pass B of the train head (ops/head_ce.py) at the widths java14m
    and java14m-ctx500 run: 1,024 rows of float32 logits over a target
    table whose rows no tile divides, 384 wide. Lowered for the TPU the
    op picks the kernel, and the chip's compiler takes its blocks."""
    from code2vec_tpu.ops import head_ce

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    compiled = jax.jit(
        lambda logits, row_max, table: head_ce._exp_sums_on_a_chip(
            logits, row_max, table, jnp.bfloat16)).lower(
                shape((rows, target_rows), jnp.float32),
                shape((rows,), jnp.float32),
                shape((target_rows, 384), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("table_rows,entries,runs", [
    (1301136, 409600, 1),   # java14m's token table, source + target ids
    (911417, 204800, 1),    # ... its path table
    (1301136, 1024000, 1),  # java14m-ctx500's 500 contexts
    (300, 2048, 1),         # a toy's table, under one tile
    (1301136, 409600, 4),   # `java14m.train_dp4`: four chips' lists
    (911417, 204800, 4),
    (300, 2000, 2),         # runs that are no whole chunks
])
def test_the_row_list_adam_compiles_at_published_widths(
        one_chip, table_rows, entries, runs):
    """Adam of a table from the backward's sorted row list
    (ops/adam_rows.py) at the tables java14m runs: rows no tile divides,
    bfloat16 moments, a batch's entries, of one chip or of the four of
    a data mesh laid end to end. Lowered for the TPU the op picks the
    kernel, the state is updated in place, and nothing table-shaped is
    left among the program's temporaries."""
    from code2vec_tpu.ops.adam_rows import adam_rows_into_table
    entries *= runs

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    compiled = jax.jit(
        lambda *args: adam_rows_into_table(
            *args, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8, runs=runs,
            name="adam_token_rows"),
        donate_argnums=(0, 1, 2)).lower(
            shape((table_rows, 128), jnp.float32),
            shape((table_rows, 128), jnp.bfloat16),
            shape((table_rows, 128), jnp.bfloat16),
            shape((entries,), jnp.int32),
            shape((entries, 128), jnp.bfloat16),
            shape((), jnp.float32), shape((), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "adam_token_rows" in text
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= table_rows * 128 * 8
    assert memory.temp_size_in_bytes < 16e6 + entries * 128 * 2


@pytest.mark.parametrize("chips", [1, 4], ids=["one_chip", "dp4"])
def test_the_row_list_step_compiles_without_a_table_shaped_gradient(
        topo, chips):
    """`java14m.train_hostfed`'s step for one described chip and
    `java14m.train_dp4`'s for the described 2x2 host: both tables' Adam
    is the row-list kernel, and no op of the program makes a float32
    array of either table's shape (the zeroed table, its scatter, a
    gradient operand, an all-reduce: each was one; the kernels' own
    results are the donated state). Across four chips the lists cross
    by all-gathers at the step's top level, not inside a branch."""
    import re
    from jax.sharding import NamedSharding
    from code2vec_tpu.config import Config
    from code2vec_tpu.models.code2vec import Code2VecModule, ModelDims
    from code2vec_tpu.parallel.mesh import MeshPlan, make_mesh
    from code2vec_tpu.training.state import (
        TrainState, init_params, make_optimizer, state_spec_tree)
    from code2vec_tpu.training.step import (
        TrainStepBuilder, _batch_spec_tuple)
    rows, contexts = 1024 * chips, 200
    config = Config(train_data_path_prefix="unused", train_batch_size=rows,
                    max_contexts=contexts, dp=chips)
    mesh = (make_mesh(MeshPlan(dp=chips, tp=1, cp=1), devices=topo.devices)
            if chips > 1 else None)
    dims = ModelDims(token_vocab_size=1301136, path_vocab_size=911417,
                     target_vocab_size=261245, token_dim=128, path_dim=128)
    module = Code2VecModule(dims=dims,
                            dropout_keep_rate=config.dropout_keep_rate,
                            compute_dtype=jnp.dtype(config.compute_dtype))
    optimizer = make_optimizer(config)

    def init(rng):
        params = init_params(module, rng)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=optimizer.init(params))

    def placed(x, spec=None):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=(SingleDeviceSharding(topo.devices[0]) if mesh is None
                      else NamedSharding(mesh, spec)))
    abstract = jax.eval_shape(init, jax.random.PRNGKey(0))
    batch = [jax.ShapeDtypeStruct(s, d) for s, d in
             [((rows, contexts), jnp.int32)] * 3
             + [((rows, contexts), jnp.float32), ((rows,), jnp.int32),
                ((rows,), jnp.bool_)]]
    key = jax.eval_shape(lambda: jax.random.key(
        0, impl=config.dropout_prng_impl))
    step = TrainStepBuilder(module, optimizer, config,
                            mesh=mesh).make_train_step(abstract)
    whole = jax.sharding.PartitionSpec()
    compiled = step.lower(
        jax.tree.map(placed, abstract, state_spec_tree(abstract)),
        *map(placed, batch, _batch_spec_tuple()),
        placed(key, whole)).compile()
    text = compiled.as_text()
    for kernel in ("adam_token_rows", "adam_path_rows"):
        assert re.search(rf"%{kernel}\S* = .*tpu_custom_call", text)
    made = re.findall(
        r"= f32\[(?:1301136|911417),128\]\S* ([a-z-]+)\(", text)
    assert set(made) <= {"parameter", "get-tuple-element"}, set(made)
    entry = text[text.index("\nENTRY "):]
    gathered = set(re.findall(
        r"= (s32\[\d+\]|bf16\[\d+,128\])\S* all-gather\(", entry))
    # (the head gathers every row's label too: `s32[4096]`)
    assert gathered - {"s32[4096]"} == (
        {"s32[1638400]", "bf16[1638400,128]", "s32[819200]",
         "bf16[819200,128]"} if chips > 1 else set())
    # the target table's Adam, which reads the float32 logits, is held
    # before the encoder's backward: the one-chip step with table-shaped
    # gradients compiled to 1,711,592,960 B of temporaries (PR 42's
    # tree), the dp4 step with them to 2,670,745,088 B (PR 45's)
    assert compiled.memory_analysis().temp_size_in_bytes < (
        1.70e9 if chips == 1 else 1.80e9)


@pytest.mark.parametrize("rows,vocab,dim,dtype,block", [
    (64, 261245, 384, "bfloat16", 16384),   # java14m.serve_open's step
    (64, 261245, 384, "bfloat16", 4096),    # ... under `--topk_block 4096`
    (1, 151936, 2048, "float32", 4096),     # a token model's one-row step
    (16, 151936, 5120, "float32", 4096),    # a rerank burst's sixteen rows
])
def test_the_blockwise_head_compiles_at_the_cells_shapes(
        one_chip, rows, vocab, dim, dtype, block):
    """The served head (ops/topk.py) at the cells' shapes, k 10, its
    table already in the compute dtype as the facade and the token
    models hand it over. At 64 rows the chip's compiler takes the group
    prefilter's reshape, gathers and two short sorts, and the compiled
    text holds no array of `block + k` columns (the whole block is
    never sorted); at the token models' one and sixteen rows the plain
    merge stays and the array is there. In neither is a `convert` that
    makes a table-shaped array: nothing that does not depend on the
    batch is left in the batch's program."""
    import re
    from code2vec_tpu.ops.topk import blockwise_matmul_top_k, sorted_columns
    dtype = jnp.dtype(dtype)

    def head(vectors, table):
        with jax.default_matmul_precision(
                "highest" if dtype == jnp.float32 else "default"):
            return blockwise_matmul_top_k(
                vectors, table, 10, block, valid_rows=vocab - 3,
                compute_dtype=dtype)
    text = jax.jit(head).lower(
        jax.ShapeDtypeStruct((rows, dim), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((vocab, dim), dtype, sharding=one_chip)
    ).compile().as_text()
    filtered = sorted_columns(rows, block, 10) < (block + 10) // 2
    assert filtered == (rows >= 32)
    assert (f"[{rows},{block + 10}]" in text) != filtered
    assert not re.search(rf"\[{vocab},{dim}\]\S* convert\(", text)


def test_tiles_divide_the_published_widths():
    from code2vec_tpu.ops.moe import _tile
    assert (_tile(1024), _tile(2688), _tile(4096), _tile(100)) == (
        1024, 896, 1024, 0)


@pytest.mark.parametrize("shape", ["16x16", "1x16", "register_2048"])
def test_the_retention_models_steps_compile_at_published_widths(
        one_chip, shape):
    """The scoring step of a rerank burst (sixteen rows of 16 on sixteen
    states), the one-row step and the 2,048-token registration chunk of
    `brumby-14b-pp8` at its published widths, against the whole state
    cache. Each program's temporaries stay under 2 GB: a `(16, 16, 40,
    8256)` float32 `phi(Q)` is 0.34 GB, the sixteen rows' states copied
    out of the cache 0.55 GB a LAYER (and hoisted, 2.7 GB), `phi` of
    every query of a registration chunk at once 2.7 GB a layer."""
    import json
    import os
    from code2vec_tpu.models import retention_lm as lm
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "brumby-14b-pp8.json")) as f:
        raw = json.load(f)
    cfg = lm.LMConfig.from_dict(raw)
    held = raw["serve"]["context_cache"]

    def spec(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    params = {leaf.name: spec(leaf.shape, jnp.dtype(leaf.dtype))
              for leaf in lm.leaf_specs(cfg)}
    cache = tuple(spec(layer.shape, layer.dtype) for layer in jax.eval_shape(
        lambda: lm.init_cache(cfg, held["slots"], held["tokens_per_slot"])))
    assert cache[0].shape == (33, 8, 129, 8256)
    scalar = spec((), jnp.int32)
    if shape == "register_2048":
        compiled = jax.jit(
            lambda p, c, ids, n, slot, start: lm.ctx_register_step(
                cfg, p, c, ids, n, slot, start), donate_argnums=(1,)).lower(
            params, cache, spec((held["register_chunk"],), jnp.int32),
            scalar, scalar, scalar).compile()
    else:
        rows, length = (int(n) for n in shape.split("x"))
        compiled = jax.jit(
            lambda p, ids, n, c, slot, at: lm.lm_score_step(
                cfg, 10, 4096, p, ids, n, c, slot, at)).lower(
            params, spec((rows, length), jnp.int32), spec((rows,), jnp.int32),
            cache, spec((rows,), jnp.int32), spec((rows,), jnp.int32)
        ).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 2e9, memory.temp_size_in_bytes
    if shape == "register_2048":
        # the donated cache is updated in place, not copied
        assert memory.alias_size_in_bytes > 5.6e9
    else:
        # weights and cache are the program's arguments: 6.4 + 5.6 GB
        assert 11.9e9 < memory.argument_size_in_bytes < 12.3e9


@pytest.mark.parametrize("shape", ["1x128", "4x64", "register_2048"])
def test_the_window_and_page_models_steps_compile_at_published_widths(
        one_chip, shape):
    """The one-row and the four-row scoring step, each row with a
    32-page list (a 65,536-token context), and the 2,048-token
    registration chunk of `trinity-mini-pp4` at its published widths,
    against the rings and the whole page pool. Each program's
    temporaries stay under 1.5 GB: a chunk's `(2048 queries, 32 heads,
    65536 keys)` float32 scores at once would be 17 GB, one page's 0.54
    GB, which is why the ops fold keys in blocks of 512 there."""
    import json
    import os
    from code2vec_tpu.models import window_moe_lm as lm
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "trinity-mini-pp4.json")) as f:
        raw = json.load(f)
    cfg = lm.LMConfig.from_dict(raw)
    held = raw["serve"]["context_cache"]
    chunk = held["register_chunk"]
    listed = held["tokens_per_slot"] // chunk

    def spec(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    params = {leaf.name: spec(leaf.shape, jnp.dtype(leaf.dtype))
              for leaf in lm.leaf_specs(cfg)}
    cache = tuple(spec(layer.shape, layer.dtype) for layer in jax.eval_shape(
        lambda: lm.init_cache(cfg, held["slots"], held["pages"], chunk)))
    assert listed == 32 and [c.shape[0] for c in cache] == [
        20, 20, 20, 160, 20, 20, 20, 160]
    scalar = spec((), jnp.int32)
    if shape == "register_2048":
        compiled = jax.jit(
            lambda p, c, ids, n, slot, start, pages: lm.ctx_register_step(
                cfg, p, c, ids, n, slot, start, pages),
            donate_argnums=(1,)).lower(
            params, cache, spec((chunk,), jnp.int32), scalar, scalar,
            scalar, spec((listed,), jnp.int32)).compile()
    else:
        rows, length = (int(n) for n in shape.split("x"))
        compiled = jax.jit(
            lambda p, ids, n, c, slot, at, pages: lm.lm_score_step(
                cfg, 10, 4096, p, ids, n, c, slot, at, pages)).lower(
            params, spec((rows, length), jnp.int32), spec((rows,), jnp.int32),
            cache, spec((rows,), jnp.int32), spec((rows,), jnp.int32),
            spec((rows, listed), jnp.int32)).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 1.5e9, memory.temp_size_in_bytes
    if shape == "register_2048":
        # the donated rings and pool are updated in place, not copied
        assert memory.alias_size_in_bytes > 1.8e9
    else:
        # weights, rings and pool are the program's arguments
        assert 13.7e9 < memory.argument_size_in_bytes < 13.9e9
        # and no array of the cache is re-laid: with a token a ROW of
        # the pool the four-row step copied each full layer's 671 MB
        # (0.70 GB of temporaries; ops/window_attn.py)
        assert memory.temp_size_in_bytes < 0.2e9, memory.temp_size_in_bytes
        assert "copy(" not in "".join(
            line for line in compiled.as_text().splitlines()
            if "bf16[160,1024,2048]" in line.split("=")[0])


@pytest.mark.parametrize("shape", ["1x1024", "4x256", "register_2048"])
def test_the_delta_rule_models_extending_step_compiles_at_published_widths(
        one_chip, shape):
    """`ctx_extend_step` of `solar-open2-ep8` at its published widths: a
    one-row and a four-row kept turn and the 2,048-token registration
    shape, each row with a 96-page list (a 196,608-token session),
    against the states and the whole page pool, both donated. Each
    program's temporaries stay under 1.5 GB: a chunk's `(2048 queries, 64
    heads, 131072 keys)` float32 scores at once would be 69 GB, and the
    delta rule's sub-block factors of every chunk at once 1.07 GB a
    layer, which is why the one folds keys in blocks of 512 and the other
    scans its chunks."""
    import json
    import os
    from code2vec_tpu.models import delta_moe_lm as lm
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "solar-open2-ep8.json")) as f:
        raw = json.load(f)
    cfg = lm.LMConfig.from_dict(raw)
    held = raw["serve"]["context_cache"]
    chunk = held["register_chunk"]
    listed = held["tokens_per_slot"] // chunk

    def spec(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    params = {leaf.name: spec(leaf.shape, jnp.dtype(leaf.dtype))
              for leaf in lm.leaf_specs(cfg)}
    cache = jax.tree.map(
        lambda a: spec(a.shape, a.dtype), jax.eval_shape(
            lambda: lm.init_cache(cfg, held["slots"], held["pages"], chunk)))
    assert listed == 96 and cache[0].shape == (416, 2048, 2048)
    assert [a.shape for a in cache[1]] == [(25, 64, 128, 128),
                                           (25, 3, 24576)]
    rows, length = ((1, chunk) if shape == "register_2048"
                    else (int(n) for n in shape.split("x")))
    compiled = jax.jit(
        lambda p, c, ids, n, slot, at, pages: lm.ctx_extend_step(
            cfg, 10, 4096, p, c, ids, n, slot, at, pages),
        donate_argnums=(1,)).lower(
        params, cache, spec((rows, length), jnp.int32),
        spec((rows,), jnp.int32), spec((rows,), jnp.int32),
        spec((rows,), jnp.int32), spec((rows, listed), jnp.int32)).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 1.5e9, memory.temp_size_in_bytes
    # weights (6.62 GB), states and pool are the program's arguments ...
    assert 10.4e9 < memory.argument_size_in_bytes < 10.5e9
    # ... and the donated states and pool are updated in place, not copied
    assert memory.alias_size_in_bytes > 3.8e9
    held_arrays = ("bf16[416,2048,2048]", "f32[25,64,128,128]")
    assert "copy(" not in "".join(
        line for line in compiled.as_text().splitlines()
        if any(a in line.split("=")[0] for a in held_arrays))
