"""Programs compiled for a described (not attached) v5e chip: the
TPU's compiler is installed in the sandbox, so what it would write for
the chip is checked here at the benchmark cells' real shapes, at no
chip time. The compiler's estimates and its program text — never a
time.

ONE file on purpose: only one process may hold libtpu, a test file goes
to one xdist worker, and a second file's fixture would skip in silence.

- the KV tier's spill gather moves only its pages (PR 42);
- the step programs' layer loops hold no op that slices a layer's
  matrix out of its stack or copies it into another layout (PR 44);
- DeepSeek-V3's two step programs — the latent arena, the absorbed
  kernel at 128 heads x 640 lanes, the share — compile at serving
  shapes with their temporaries bounded (PR 45);
- the expert configurations' step programs multiply their expert
  layers in the repo's grouped-matmul kernel, under the name the
  benchmark's readers know (PR 46);
- DeepSeek-V3's ``mixed`` program attends its 512-token prompt row in
  the expanded flash kernel, one call a layer beside the decode rows'
  absorbed one, with none of the absorbed form's 84 MB query relayout
  and f32 output around it; no other program holds the call (PR 50);
- DeepSeek-V3's programs move only the rows their share holds around
  the grouped matmul: the two row kernels a layer, and no XLA op that
  writes a ``[padded_rows(N * K), d_model]`` array; no other
  configuration's program holds either kernel (PR 51).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def one_v5e():
    """One described (not attached) v5e chip: the TPU's compiler is
    installed in the sandbox. Made inside a fixture, never at import —
    only one process may hold libtpu."""
    from tools.step_hlo import describe_v5e

    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:  # or libtpu logs under /tmp
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            chip = describe_v5e()
        except Exception as e:  # no libtpu here, or another process's
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield chip


def _cell_planes():
    """tools/profile_kv.py's planes — the benchmark cells' pool planes
    with the page counts their spills pad to — one case a page count.
    Not among them: a scale plane with b = 1, where the compiler
    prefetches the whole 8 MB parameter (10 us) for any form."""
    from tools.profile_kv import _CELL_PLANES, parse_plane

    for spec in _CELL_PLANES:
        dt, shape, bs = parse_plane(spec)
        for b in bs:
            yield pytest.param(shape, dt, b,
                               id=f"{spec.rsplit(':', 1)[0]}:{b}")


@pytest.mark.parametrize("shape,dtype,b", _cell_planes())
def test_gather_compiled_for_a_v5e_moves_only_its_pages(one_v5e, shape,
                                                        dtype, b):
    """At the cells' plane shapes the compiled program touches at most
    4 x the bytes it has to move (b pages read, b written) and holds
    fewer temporaries than its output: a compiler or a refactor that
    brings the whole-pool copy back (2.2 GB of temporaries, 68 x the
    bytes at Mistral's plane) fails here, not on a ledger line. The
    compiler's estimates, no timing."""
    from localai_tfp_tpu.engine.kv_tier import _gather_pages

    compiled = _gather_pages.lower(
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e),
        jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_v5e)).compile()
    out_bytes = b * int(np.prod(shape)) // shape[1] \
        * jnp.dtype(dtype).itemsize
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= out_bytes
    assert cost["bytes accessed"] <= 4 * 2 * out_bytes, cost
    # (a scale plane's pages change layout on the way out: one more
    # copy of the 128 KB moved, nothing of the 8 MB plane)
    room = 2 if len(shape) == 3 else 1
    assert mem.temp_size_in_bytes < room * mem.output_size_in_bytes, (
        mem.temp_size_in_bytes, mem.output_size_in_bytes)


STEP_CONFIGS = ("mistral-7b-instruct-v0.3", "trinity-mini-pp4-stage",
                "olmo-hybrid-7b-pp2-stage")
EXPERT_CONFIGS = ("trinity-mini-pp4-stage", "deepseek-v3-ep16-share")
_COMPILED: dict = {}  # (configuration, kind) -> (config, executable)


def _step_program(one_v5e, name, kind):
    """A benchmark configuration's ``dispatch_<kind>`` compiled for the
    described v5e, once a module (15-60 s each)."""
    from tools.step_hlo import lower_program

    if (name, kind) not in _COMPILED:
        with open(os.path.join(ROOT, "benchmark", "configs",
                               f"{name}.json")) as f:
            config = json.load(f)
        # tests/conftest.py asks every matmul for HIGHEST precision (the
        # CPU comparisons need it); the server asks for none, and neither
        # the kernel nor the program compiled here is the served one
        # under it
        with jax.default_matmul_precision("default"):
            _COMPILED[name, kind] = (
                config, lower_program(config, kind, one_v5e).compile())
    return _COMPILED[name, kind]


@pytest.mark.parametrize("kind", ["decodek", "mixed"])
@pytest.mark.parametrize("name", STEP_CONFIGS)
def test_step_program_reads_its_weights_from_the_stack_in_place(
        one_v5e, name, kind):
    """The engine's own ``dispatch_decodek`` / ``dispatch_mixed`` at a
    benchmark configuration's published widths, as it serves them
    (abstract arrays, no weights), compiled for a v5e: no op of a layer
    loop's body that is neither a dot / convolution fusion nor a Pallas
    call moves a parameter leaf — 1 MB or more of it, sliced out of its
    stack or copied into another layout. Before PR 44 the compiler
    folded the projections' split into heads into their dots, wanted
    ``wq`` / ``wk`` / ``wv`` head-major, and wrote each layer's matrix
    out transposed on every layer of every step (13.8 % of the chip in
    Mistral's cell; both Mistral programs fail here on that tree, by
    ``constant_dynamic-slice_fusion`` over ``params['wk'].q``). It is a
    compiler heuristic: it comes back silently with a reshape next to a
    dot. ``tools/step_hlo.py`` prints the whole loop body."""
    from tools.step_hlo import offenders_of

    config, compiled = _step_program(one_v5e, name, kind)
    bad = offenders_of(compiled.as_text(), config)
    if bad:
        pytest.fail("a layer's weight moved by an op that is no matmul:\n"
                    + "\n".join(f"  {leaf}: {op.line[:150]}"
                                for _, op, leaf in bad), pytrace=False)


@pytest.mark.parametrize("kind", ["decodek", "mixed"])
def test_latent_step_programs_compile_with_temporaries_bounded(
        one_v5e, kind):
    """``deepseek-v3-ep16-share`` as served: Mosaic takes the ragged
    kernel's latent form at 128 query heads against [256, 640] pages
    (decode rows and a 512-token prompt row alike), no layer's weight
    is sliced out of its stack, and the program's temporaries stay
    under 1.5 GB beside 11 GB of weights and 1 GB of latent arena — a
    copy of an expert stack (2.8 GB a layer) or of the arena would not
    (PR 41 found 1.86 GB of copies this way before any run)."""
    from tools.step_hlo import offenders_of

    config, compiled = _step_program(one_v5e, "deepseek-v3-ep16-share",
                                     kind)
    text = compiled.as_text()
    assert "%latent_paged_attention" in text
    assert "%ragged_paged_attention" not in text  # ops named by kernel
    assert not offenders_of(text, config)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.5e9, mem.temp_size_in_bytes
    # the arena is updated in place: the program's outputs alias it
    assert mem.alias_size_in_bytes >= 6 * 513 * 256 * 640 * 2


@pytest.mark.parametrize("kind", ["decodek", "mixed"])
@pytest.mark.parametrize("name", STEP_CONFIGS + EXPERT_CONFIGS[1:])
def test_expert_layers_multiply_in_the_grouped_kernel(one_v5e, name, kind):
    """An expert configuration's step programs hold the repo's grouped
    matmul — a Pallas call whose INSTRUCTION name starts with
    ``ragged-dot`` (the prefix the benchmark's expert-layer readers
    find it by: ``benchmark/models/*.py`` ``EXPERT_KERNELS``; PR 46) —
    two calls a layer (gate and up share one), and no XLA ``ragged-dot``
    op; a dense configuration's hold neither."""
    from tools.step_hlo import loop_bodies

    _, compiled = _step_program(one_v5e, name, kind)
    bodies, _ = loop_bodies(compiled.as_text())
    ops = [op for body in bodies for op in body.ops
           if op.name.startswith("ragged-dot")]
    if name not in EXPERT_CONFIGS:
        assert not ops, [op.name for op in ops]
        return
    assert ops and {op.opcode for op in ops} == {"custom-call"}, [
        (op.name, op.opcode) for op in ops]
    assert all(op.name.startswith("ragged-dot-grouped") for op in ops)
    assert len(ops) % 2 == 0


@pytest.mark.parametrize("kind", ["decodek", "mixed"])
@pytest.mark.parametrize("name", STEP_CONFIGS + EXPERT_CONFIGS[1:])
def test_a_latent_prompt_row_attends_in_the_expanded_flash_kernel(
        one_v5e, name, kind):
    """``deepseek-v3-ep16-share``'s ``mixed`` program ([1, 512] prompt
    row, 512 >= ``expanded_from`` = 170.7): a layer holds ONE
    ``latent_paged_attention_expanded`` call (the prompt row) and ONE
    absorbed ``latent_paged_attention`` call (the 16 decode rows) — the
    benchmark finds both by the prefix ``latent_paged_attention`` —
    and neither the absorbed prompt form's query in the arena's 640
    lanes (bf16[1,512,128,640], built and then copied into the
    kernel's layout: 84 MB each a layer) nor its f32 output
    (f32[..,65536,512], 134 MB, rounded to bf16 by a ``convert``) nor a
    weight sliced out of its stack. Its ``decodek`` program and every
    other configuration's programs hold no expanded call."""
    from tools.step_hlo import loop_bodies, offenders_of

    config, compiled = _step_program(one_v5e, name, kind)
    text = compiled.as_text()
    bodies, _ = loop_bodies(text)
    calls = [[op.name for op in body.ops
              if op.name.startswith("latent_paged_attention")]
             for body in bodies]
    if (name, kind) != ("deepseek-v3-ep16-share", "mixed"):
        assert "latent_paged_attention_expanded" not in text
        return
    layers = [c for c in calls if c]
    assert layers, "no layer loop holds a latent attention call"
    for c in layers:
        expanded = [n for n in c
                    if n.startswith("latent_paged_attention_expanded")]
        assert len(expanded) == 1 and len(c) == 2, c
    shapes = [(op.opcode, op.shapes) for body in bodies for op in body.ops]
    assert not [o for o in shapes
                if o[0] == "copy" and ("bf16", (1, 512, 128, 640)) in o[1]]
    assert not [o for o in shapes for dt, dims in o[1]
                if dims[-2:] == (65536, 512)]
    assert not offenders_of(text, config)


@pytest.mark.parametrize("kind", ["decodek", "mixed"])
@pytest.mark.parametrize("name", STEP_CONFIGS + EXPERT_CONFIGS[1:])
def test_a_share_moves_only_its_rows_around_the_grouped_matmul(
        one_v5e, name, kind):
    """``deepseek-v3-ep16-share`` holds 16 of 256 experts: each expert
    layer of its step programs holds ONE ``expert-rows-gather`` and ONE
    ``expert-rows-combine`` call (ops/expert_rows.py) and, but for the
    grouped matmul's own output, nothing writes an array of all N * K
    sorted rows at the model's width — the parent's gather, mask and
    un-sort, 60.6 MB each a layer of a 512-token step, are gone. The
    configurations that hold every expert (or none) hold neither
    kernel: their dispatch is XLA's, as before."""
    from localai_tfp_tpu.ops import expert_rows as er
    from localai_tfp_tpu.ops import grouped_matmul as gm
    from tools.step_hlo import loop_bodies

    config, compiled = _step_program(one_v5e, name, kind)
    text = compiled.as_text()
    if name != "deepseek-v3-ep16-share":
        assert er.GATHER_NAME not in text and er.COMBINE_NAME not in text
        return
    bodies, _ = loop_bodies(text)
    tokens = 16 if kind == "decodek" else 16 + 512
    wide = (gm.padded_rows(tokens * config["num_experts_per_tok"]),
            config["hidden_size"])
    layers = 0
    for body in bodies:
        names = [op.name for op in body.ops]
        gathers = [n for n in names if n.startswith(er.GATHER_NAME)]
        combines = [n for n in names if n.startswith(er.COMBINE_NAME)]
        assert len(gathers) == len(combines) <= 1, names
        layers += len(gathers)
        for op in body.ops:
            if any(dims[-2:] == wide for _, dims in op.shapes):
                assert op.name.startswith(
                    (er.GATHER_NAME, gm.KERNEL_NAME)), op.line[:200]
    assert layers == 1  # the expert stack's layer loop
