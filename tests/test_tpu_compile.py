"""Programs of the main path compiled for the chip, without one.

libtpu compiles ahead of time for a TPU that is described and not attached
(`jax.experimental.topologies`), so what the chip's compiler would refuse -
a program that does not fit the device's memory, a layout it cannot make -
is refused here, at no chip time.  Nothing runs: this says nothing about
results or speed.

Only one process at a time may load libtpu, so the topology is described
inside a fixture (never while a module is imported), every test that needs
it lives in this one file, and where it cannot be described the tests skip.
"""

import math
import os
import re

import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    for key, value in (("TPU_LOG_DIR", "disabled"),
                       ("TPU_ACCELERATOR_TYPE", "v5litepod-4"),
                       ("TPU_WORKER_HOSTNAMES", "localhost"),
                       ("TPU_SKIP_MDS_QUERY", "1")):
        os.environ.setdefault(key, value)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


_SHAPE = re.compile(r"(f32|bf16|s32|u32|pred)\[([\d,]*)\](?:\{[^}]*\})?")


def _instructions(hlo: str):
    """(where, name, op, [(dtype, elements)] of its result, operand names,
    op_name) of every instruction; `where` is "entry", "fused" (a fusion's
    own body) or "loop" (any other computation: a while's body, a call)."""
    fused = set(re.findall(r"kind=k\w+, calls=%([\w.\-]+)", hlo))
    out, where = [], None
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%([\w.\-]+) \(", line)
        if head:
            where = ("entry" if head.group(1) else
                     "fused" if head.group(2) in fused else "loop")
            continue
        if where is None or " = " not in line:
            continue
        lhs, _, rhs = line.strip().partition(" = ")
        if rhs.startswith("("):         # a tuple: up to its closing paren
            depth = 0
            for end, ch in enumerate(rhs):
                depth += {"(": 1, ")": -1}.get(ch, 0)
                if depth == 0:
                    break
            shape, rest = rhs[:end + 1], rhs[end + 1:].strip()
        else:
            shape, _, rest = rhs.partition(" ")
        op = rest.split("(")[0]
        sizes = [(m.group(1), math.prod(int(d or 1)
                                        for d in m.group(2).split(",")))
                 for m in _SHAPE.finditer(shape)]
        operands = re.findall(r"%([\w.\-]+)",
                              rest[len(op) + 1:].split(")")[0])
        op_name = re.search(r'op_name="([^"]*)"', rest)
        out.append((where, lhs.split()[-1].lstrip("%"), op, sizes, operands,
                    op_name.group(1) if op_name else ""))
    return out


def _table_sized_in_loops(hlo: str, elements: int):
    """(name, op) of the instructions whose result is one array of
    `elements` or more, in every computation but the entry and the
    fusions' own bodies."""
    return [(name, op) for where, name, op, sizes, _, _ in _instructions(hlo)
            if where == "loop" and len(sizes) == 1
            and sizes[0][1] >= elements
            and op not in ("parameter", "get-tuple-element", "bitcast")]


def _criteo_deepfm():
    """(job, fields, rows of its tables, batch) of the benchmark's
    `deepfm_criteo.train_resident` cell: 13 numeric + 26 categorical fields
    of 1,300,000 buckets, latent dim 10, 400x400x400, batch 8,192, Adadelta
    over float32 tables (4.5 GB of state)."""
    from shifu_tpu.config import (DataConfig, JobConfig, ModelSpec,
                                  OptimizerConfig, TrainConfig)
    from shifu_tpu.data import synthetic

    n_num, n_cat, vocab, batch = 13, 26, 1_300_000, 8192
    schema = synthetic.make_schema(num_features=n_num + n_cat,
                                   num_categorical=n_cat, vocab_size=vocab)
    job = JobConfig(
        schema=schema, data=DataConfig(batch_size=batch),
        model=ModelSpec(model_type="deepfm", hidden_nodes=(400, 400, 400),
                        activations=("relu",) * 3, embedding_dim=10,
                        param_dtype="float32", compute_dtype="bfloat16"),
        train=TrainConfig(epochs=1, loss="weighted_mse",
                          optimizer=OptimizerConfig(name="adadelta",
                                                    learning_rate=1.0)),
    ).validate()
    return job, n_num + n_cat, n_cat * vocab, batch


def test_criteo_size_deepfm_epoch_fits_the_chip(one_chip, no_compile_cache,
                                                monkeypatch):
    """The epoch program of the benchmark's `deepfm_criteo.train_resident`
    cell (13 numeric + 26 categorical fields of 1,300,000 buckets, latent
    dim 10, 400x400x400, batch 8,192, 128 steps resident, Adadelta over
    float32 tables: 4.5 GB of state) compiles for one v5e chip - it fits its
    16 GB beside the copy of the state in the loop's own layout, which
    leaves a few hundred MB - and its loop makes nothing table-sized by
    casting, slicing, padding, reshaping or re-tiling a table."""
    from shifu_tpu.ops import pallas_common
    from shifu_tpu.train.loop import init_state
    from shifu_tpu.train.step import make_device_epoch_step

    job, fields, table_elements, batch = _criteo_deepfm()
    steps = 128

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    state = jax.tree_util.tree_map(
        on_chip, jax.eval_shape(lambda: init_state(job, fields)))
    blocks = {k: on_chip(jax.ShapeDtypeStruct((steps, batch, w), jnp.float32))
              for k, w in (("features", fields), ("target", 1),
                           ("weight", 1))}
    order = on_chip(jax.ShapeDtypeStruct((steps,), jnp.int32))
    # the code asks `jax.default_backend()`, which is the CPU here: steer it
    # onto its TPU branches while the program is traced
    monkeypatch.setattr(pallas_common.jax, "default_backend", lambda: "tpu")
    step = make_device_epoch_step(job, None)
    lowered = step._fn.trace(state, blocks, order).lower(
        lowering_platforms=("tpu",))
    monkeypatch.undo()
    compiled = lowered.compile()            # raises what the chip would
    hlo = compiled.as_text()

    made = _table_sized_in_loops(hlo, table_elements)
    assert made, "the reader found no table-sized instruction at all"
    bad = [(name, op) for name, op in made
           if op in ("convert", "slice", "reshape", "pad", "concatenate",
                     "transpose", "dynamic-slice", "copy")
           or name.startswith(("slice", "pad", "convert", "dynamic-slice"))]
    assert not bad, bad
    # ISSUE 37: Adadelta over the two tables is one in-place kernel call
    # each, on views that are bitcasts of the loop's own buffers - no
    # table-sized optimizer fusion, no copy of a slot beside it
    ins = _instructions(hlo)
    loop = [i for i in ins if i[0] == "loop"]
    made_by = {name: op for _, name, op, _, _, _ in loop}
    big = lambda sizes: any(n >= table_elements for _, n in sizes)
    assert not [name for _, name, op, sizes, _, op_name in loop
                if op == "fusion" and big(sizes) and "/optimizer/" in op_name]
    kernels = [(name, [made_by.get(o) for o in operands[1:]])
               for _, name, op, sizes, operands, _ in loop
               if op == "custom-call" and big(sizes)
               and "adadelta_apply" in name]
    assert len(kernels) == 2, kernels
    assert all(ops == ["bitcast"] * 4 for _, ops in kernels), kernels
    # the optimizer's two fusions and their copy held 15.15 GB of
    # temporaries at the parent; 10.82 GB here
    assert compiled.memory_analysis().temp_size_in_bytes < 12e9


def test_mlp30_epoch_holds_no_kernel(one_chip, no_compile_cache, monkeypatch):
    """The epoch program of the benchmark's `mlp30.train_resident` cell
    (30 inputs, 3x100, batch 65,536): no leaf reaches the fused Adadelta
    apply's 2**20 elements, so no kernel is in it."""
    from benchmarks import harness
    from shifu_tpu.ops import pallas_common
    from shifu_tpu.train.loop import init_state
    from shifu_tpu.train.step import make_device_epoch_step

    _, _, config, _, params, driver = harness.load_cell("mlp30.train_resident")
    job = driver.build_job(config, params, 1, 1)
    batch, width, steps = config["batch_size"], config["num_numeric"], 8

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    state = jax.tree_util.tree_map(
        on_chip, jax.eval_shape(lambda: init_state(job, width)))
    blocks = {k: on_chip(jax.ShapeDtypeStruct((steps, batch, w), dtype))
              for k, w, dtype in (("features", width, jnp.bfloat16),
                                  ("target", 1, jnp.float32),
                                  ("weight", 1, jnp.float32))}
    order = on_chip(jax.ShapeDtypeStruct((steps,), jnp.int32))
    monkeypatch.setattr(pallas_common.jax, "default_backend", lambda: "tpu")
    step = make_device_epoch_step(job, None)
    lowered = step._fn.trace(state, blocks, order).lower(
        lowering_platforms=("tpu",))
    monkeypatch.undo()
    assert "tpu_custom_call" not in lowered.compile().as_text()


def test_criteo_size_deepfm_resident_eval_fits_the_chip(one_chip,
                                                        no_compile_cache,
                                                        monkeypatch):
    """The resident eval program of the same cell (ISSUE 30: the forward
    mapped over the 15 blocks of 8,192 that hold its 116,508 valid rows,
    beside the 4.5 GB of state) compiles for one v5e chip.  It runs between
    two epoch programs, so its temporaries do not add to theirs; and what
    it makes table-sized - the copy of the k-dim table into the layout its
    gather wants, once a batch on the streamed path - it makes outside the
    loop over the blocks, once a pass."""
    from shifu_tpu.ops import pallas_common
    from shifu_tpu.train.loop import init_state
    from shifu_tpu.train.step import make_resident_eval_step

    job, fields, table_elements, batch = _criteo_deepfm()

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    state = jax.tree_util.tree_map(
        on_chip, jax.eval_shape(lambda: init_state(job, fields)))
    blocks = on_chip(jax.ShapeDtypeStruct((15, batch, fields), jnp.float32))
    monkeypatch.setattr(pallas_common.jax, "default_backend", lambda: "tpu")
    step = make_resident_eval_step(job)
    lowered = step._fn.trace(state, blocks).lower(lowering_platforms=("tpu",))
    monkeypatch.undo()
    compiled = lowered.compile()            # raises what the chip would
    hlo = compiled.as_text()
    assert " while(" in hlo, "the blocks are no longer walked by a loop"
    assert _table_sized_in_loops(hlo, table_elements) == []
    (scores,) = jax.eval_shape(step._fn, state, blocks)
    assert scores.shape == (15, batch) and scores.dtype == jnp.float32


def _compiled_epoch_program(cell: str, one_chip, monkeypatch, steps=8):
    """The epoch program of a sequence cell of the benchmark (`steps` steps
    of 8 rows of 4,096 positions), compiled for one v5e chip: raises what
    the chip's compiler would."""
    from benchmarks import harness
    from shifu_tpu.ops import pallas_common
    from shifu_tpu.train.loop import init_state
    from shifu_tpu.train.step import make_device_epoch_step

    _, _, config, _, params, driver = harness.load_cell(cell)
    job = driver.build_job(config, params, 1, 1)
    batch, width = config["batch_size"], config["num_categorical"]
    assert (batch, width, params["train_rows"] // batch) == (8, 4096, steps)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    state = jax.tree_util.tree_map(
        on_chip, jax.eval_shape(lambda: init_state(job, width)))
    blocks = {k: on_chip(jax.ShapeDtypeStruct((steps, batch, w), jnp.float32))
              for k, w in (("features", width), ("target", 1), ("weight", 1))}
    order = on_chip(jax.ShapeDtypeStruct((steps,), jnp.int32))
    monkeypatch.setattr(pallas_common.jax, "default_backend", lambda: "tpu")
    step = make_device_epoch_step(job, None)
    lowered = step._fn.trace(state, blocks, order).lower(
        lowering_platforms=("tpu",))
    monkeypatch.undo()
    return lowered.compile()


def _fits_in_place(compiled, low: float, high: float) -> None:
    """The arguments lie between `low` and `high` bytes, are carried in
    place, and fit the chip's 16 GiB with the temporaries."""
    memory = compiled.memory_analysis()
    arguments = memory.argument_size_in_bytes
    assert low < arguments < high
    assert memory.alias_size_in_bytes > 0.999 * memory.output_size_in_bytes
    assert arguments + memory.temp_size_in_bytes < 16 * 2 ** 30


def test_qwen3_next_ep16_epoch_fits_the_chip(one_chip, no_compile_cache,
                                             monkeypatch):
    """The epoch program of the benchmark's `qwen3_next_ep16.train_sequences`
    cell (the blocks `LFLFLFAF` at Qwen3-Next-80B-A3B's published widths, 32
    of 512 experts held, 586.8 M parameters with both Adadelta slots: 7.04 GB
    of arguments; 8 steps of 8 rows of 4,096 positions, a block
    rematerialized at a time) compiles for one v5e chip, the delta rule's
    chunk systems and the routed experts' walk among it: its arguments
    are carried in place and they and its temporaries fit the chip's 16 GiB
    together.  The chunk systems are inverted by products: the operation a
    triangular solve becomes on the chip is not in the program."""
    compiled = _compiled_epoch_program("qwen3_next_ep16.train_sequences",
                                       one_chip, monkeypatch)
    hlo = compiled.as_text()
    assert "InvertDiagBlocksLowerTriangular" not in hlo
    _fits_in_place(compiled, 7.0e9, 7.1e9)
    # ISSUE 37: the fused Adadelta apply takes the 39 leaves of 2**20
    # elements or more, and adds no copy of a large leaf (165 at the parent)
    copies = [name for _, name, op, sizes, _, _ in _instructions(hlo)
              if op == "copy" and any(t == "f32" and n >= 1 << 20
                                      for t, n in sizes)]
    assert len(copies) <= 165, len(copies)


def test_nemotron3_nano_ep16_epoch_fits_the_chip(one_chip, no_compile_cache,
                                                 monkeypatch):
    """The epoch program of the benchmark's
    `nemotron3_nano_ep16.train_sequences` cell (the blocks `MEMEM*EME` at
    Nemotron-3-Nano's published widths, 8 of 128 experts held, 622.9 M
    parameters with both Adadelta slots: 7.48 GB of arguments) compiles for
    one v5e chip.  It fits by 0.5 GB at the parent; the fused Adadelta
    apply (ISSUE 37) took its 27 large leaves row-major and held a copy of
    the 12 whose TPU layout is not (`(8, 2688, 1856)`, `(2688, 10304)`)
    beside the loop, and the compiler refused the program by 9.94 MB:
    they go in swapped now, and no copy of a large leaf is added (71 at
    the parent)."""
    compiled = _compiled_epoch_program("nemotron3_nano_ep16.train_sequences",
                                       one_chip, monkeypatch)
    _fits_in_place(compiled, 7.4e9, 7.55e9)
    copies = [name for _, name, op, sizes, _, _ in
              _instructions(compiled.as_text())
              if op == "copy" and any(t == "f32" and n >= 1 << 20
                                      for t, n in sizes)]
    assert len(copies) <= 71, len(copies)


@pytest.mark.parametrize("shape", [
    (8, 2688, 1856), (2688, 10304), (2048, 12288), (32, 2048, 512),
    (26, 1300000, 1), (26, 1300000, 10), (1000, 1100), (1100, 1000)])
def test_fused_adadelta_view_is_the_chip_layout(shape, one_chip,
                                                no_compile_cache):
    """The fused Adadelta kernel takes a leaf with its last two axes
    swapped exactly where the TPU holds the second-to-last dimension
    minor (ops/pallas_adadelta.minor_swapped): then its view is a bitcast
    and no copy of the leaf is made."""
    from shifu_tpu.ops.pallas_adadelta import minor_swapped

    leaf = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    hlo = jax.jit(lambda x: x * 2).trace(leaf).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    layout = re.search(r"\]\{([\d,]+)[:}][^\n]*parameter\(0\)", hlo)
    minor = int(layout.group(1).split(",")[0])
    assert minor_swapped(shape) == (minor == len(shape) - 2)


def test_joyai_flash_ep16_epoch_fits_the_chip(one_chip, no_compile_cache,
                                              monkeypatch):
    """The epoch program of the benchmark's `joyai_flash_ep16.train_sequences`
    cell (the blocks `CDCGCGCGCGCG` at JoyAI-LLM-Flash's published widths, 16
    of 256 experts held, 639.0 M parameters with both Adadelta slots: 7.67 GB
    of arguments; a block rematerialized at a time) compiles for one v5e
    chip, latent attention's 192-wide keys beside 128-wide values and the
    routed experts' walk among it.  It fits because a row's queries, keys
    and values are up-projected from the latents a row at a time: with the
    batch's up-projected together the compiler refuses the program ("Used
    17.42G of 15.75G hbm")."""
    compiled = _compiled_epoch_program("joyai_flash_ep16.train_sequences",
                                       one_chip, monkeypatch)
    _fits_in_place(compiled, 7.6e9, 7.75e9)


def test_lfm2_8b_a1b_ep4_epoch_fits_the_chip(one_chip, no_compile_cache,
                                             monkeypatch):
    """The epoch program of the benchmark's `lfm2_8b_a1b_ep4.train_sequences`
    cell (the blocks `VDQGVGVGVG` at LFM2-8B-A1B's published widths: four
    gated short convolutions, one QK-normed attention, the dense MLP and
    four expert layers of 8 held of 32 experts with no shared expert;
    507.8 M parameters with both Adadelta slots: 6.10 GB of arguments; 4
    steps of 8 rows of 4,096 positions, a block rematerialized at a time)
    compiles for one v5e chip at batch 8: its arguments are carried in
    place, and with its 2.99 GB of temporaries (the rehearsal when the cell
    was added) they fill 8.5 of the chip's 16 GiB."""
    compiled = _compiled_epoch_program("lfm2_8b_a1b_ep4.train_sequences",
                                       one_chip, monkeypatch, steps=4)
    _fits_in_place(compiled, 6.05e9, 6.15e9)
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9
