"""The serving programs, compiled whole for a described TPU at real widths.

What the contract (llm/serving.py) hands the engine, as the benchmark's
configurations call it, for what only the compiled text shows; no chip
is needed (tests/test_tpu_aot_compile.py, whose kernels these programs
run, says why, and lends its shapes and two readers of a compiled
text).

The Llama-shaped programs (llm/paged_kv.py) are compiled at Mistral-7B
widths with a two-layer page pool of the benchmark's size: nothing
copies, slices out or writes back a layer's pages or more. So are the
hybrid model's (llm/hybrid_kv.py), at Nemotron-3-Nano's widths with 64
experts held, for the same of its pages, of a layer's per-slot state and
of an expert stack, and at granite-4.0-h-small's with 36 held, where a
chunk also attends a 16,384-token table without a score over it in HBM;
and the latent-attention model's (llm/latent_kv.py), at
openPangu-Ultra-MoE's widths with 16 experts held, for the same of its
latent pool. Both hold the kernel that reads the touched experts
(ops/pallas/expert_rows.py) wherever `moe_ffn` takes its every-row form,
and the compiler's grouped matmul above that. qwen3next-80b-serve1's own
programs are compiled at one Gated DeltaNet and the attention layer with
the whole configuration's pages and slots; the three decode programs are
held to making no pass of XLA's own over a layer's states beside the
state kernel. laguna-s21-serve1's own programs at its full layer and one
window layer with the whole configuration's pages and slots, at all
three table widths: a window layer's part is the same in each.
glm53flash-serve1's own programs at its KDA + dense and sparse-attention
+ expert layers with the whole configuration's pages and slots, at all
four table widths: no score over the table, at 65,536 keys either.
longcat-flash-omni-serve1's own programs at one double layer (two
latent-attention sublayers at 64 heads, two dense FFNs, the shortcut's
experts behind a 768-wide router) with the whole configuration's pages:
both latent kernels twice a layer, the identity outputs' sum no kernel's
work. phi4miniflash-serve1's own programs at 8 layers split as the model
splits its 32 (every letter) with the whole configuration's pages and
slots: both chunk programs and the decode program.
"""

import math
import os
import re
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from test_tpu_aot_compile import (
    DH,
    DH256,
    H,
    HKV,
    HW,
    MAX_PAGES,
    PAGE,
    POOL_PAGES,
    SLOTS,
    WINDOW,
    _copies_of,
    _kernel_calls_under,
)


def _combine_scatters(text: str) -> list[str]:
    """A compiled program's lines under ``moe:combine`` that name a
    scatter (the instruction, or the ``scatter-add`` its fusion was
    built around): XLA's row scatter-add of the sorted expert form,
    which ops/pallas/expert_combine.py replaces on a TPU."""
    return [
        line for line in text.splitlines()
        if "moe:combine" in line and "scatter" in line
    ]


def _expert_kernel_calls(text: str) -> list[str]:
    """A compiled program's calls of the touched-experts kernel: the
    experts' operation of the every-row form."""
    return [
        line for line in _kernel_calls_under(text, "moe:experts")
        if "jit(_experts_on_rows)" in line
    ]


def _grouped_kernel_calls(text: str) -> list[str]:
    """A compiled program's calls of ops/pallas/grouped_rows.py: the
    experts' operation of the sorted form over the pairs computed here,
    two a layer (the activation of the up projections, the down
    projection), under the scope the benchmark's reducers read."""
    return [
        line for line in _kernel_calls_under(text, "moe:experts")
        if "jit(_grouped_rows)" in line
    ]


# ------------------------------------------------- the serving programs
# At mistral7b-serve1's shapes (above) with prefill_chunk 2048; 2 of its
# 6 layers, enough for a loop.
LAYER_PAGES_ELEMS = POOL_PAGES * HKV * PAGE * DH

_MOVES = re.compile(
    r"=\s+(\(?[a-z0-9]+\[[^=]*?)\s+"
    r"(copy|copy-start|dynamic-slice|dynamic-update-slice)\("
)
_SHAPE = re.compile(r"[a-z0-9]+\[([\d,]+)\]")


def _pool_moves(text: str) -> list[str]:
    """Instructions of a compiled program, fused ones included, that
    copy, slice or write back an array of K/V pages (``[..., Hkv, P,
    Dh]``) the size of one layer's pages or more. A scatter or a kernel
    that updates the pool in place is not among them; nor are a layer's
    own weights, sliced out of their stack by the same loop."""
    found = []
    for line in text.splitlines():
        m = _MOVES.search(line)
        if not m:
            continue
        for dims in _SHAPE.findall(m.group(1)):
            shape = tuple(int(d) for d in dims.split(","))
            if (
                shape[-3:] == (HKV, PAGE, DH)
                and math.prod(shape) >= LAYER_PAGES_ELEMS
            ):
                found.append(f"{m.group(2)} {m.group(1)}")
    return found


def _serving_program(case: str, on):
    from ray_tpu.llm import paged_kv
    from ray_tpu.llm.paged_kv import matmul_weights
    from ray_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig(
        vocab_size=32768, d_model=H * DH, n_layers=2, n_heads=H,
        n_kv_heads=HKV, d_ff=14336, max_seq=MAX_PAGES * PAGE,
        rope_theta=1e6,
    )

    def shaped(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on),
            tree,
        )

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=on)

    # The weights as LLMEngine holds them: matmul leaves in cfg.dtype.
    params = shaped(
        jax.eval_shape(
            lambda key: matmul_weights(init_params(key, cfg=cfg), cfg),
            jax.random.key(0),
        )
    )
    pages = jax.ShapeDtypeStruct(
        (cfg.n_layers, POOL_PAGES, HKV, PAGE, DH), cfg.dtype
    )
    pool = shaped({"k": pages, "v": pages})
    if case.startswith("verify"):
        k = int(case[-1])
        return paged_kv.paged_verify.lower(
            params, i32(SLOTS, k), pool, i32(SLOTS, MAX_PAGES), i32(SLOTS),
            jax.ShapeDtypeStruct((SLOTS,), jnp.float32, sharding=on),
            shaped(jax.eval_shape(partial(jax.random.key, 0))),
            cfg=cfg, use_kernel=True, stochastic=False,
        )
    # "<program>" as a `tp` mesh or a CPU compiles it, "<program>_kernel"
    # as LLMEngine does on a bare TPU.
    use_kernel = case.endswith("_kernel")
    if case.startswith("prefill_1024"):
        return paged_kv.paged_prefill.lower(
            params, i32(1, 1024), pool, i32(1024 // PAGE), cfg=cfg,
            n_write_pages=1024 // PAGE, use_kernel=use_kernel,
        )
    return paged_kv.paged_prefill_chunk.lower(
        params, i32(1, 2048), pool, i32(8192 // PAGE), i32(), cfg=cfg,
        n_write_pages=8192 // PAGE, chunk_pages=2048 // PAGE,
        use_kernel=use_kernel,
    )


_DENSE_SCORES = re.compile(
    r"=\s+\(?[^=]*f32\[[\d,]*(2048,8192|2048,128,64|1024,1024)\]"
)


@pytest.mark.parametrize(
    "case",
    [
        "verify_k1", "verify_k4", "prefill_1024", "prefill_chunk_2048_of_8192",
        "prefill_1024_kernel", "prefill_chunk_2048_of_8192_kernel",
    ],
)
def test_serving_program_moves_no_layer_of_pages(v5e, case, monkeypatch):
    """The pool is one buffer in one layout from argument to result
    (llm/paged_kv.py): carried through the layer loop, written in place.
    Scanned in and stacked out, or scattered by XLA beside the Pallas
    attention, each program re-laid-out or copied 100 MB of pages
    several times a layer."""
    from ray_tpu._private import chip

    # The program asks which platform it runs on to choose between the
    # Mosaic kernels and their interpreter: here it is compiled for the
    # chip, from a CPU host.
    monkeypatch.setattr(chip, "platform", lambda: "tpu")
    compiled = _serving_program(case, v5e).compile()
    text = compiled.as_text()
    assert _pool_moves(text) == []
    if case.startswith("verify"):
        assert "tpu_custom_call" in text
    if case.startswith("prefill"):
        # The prefill kernel (ops/pallas/prefill_attention.py) where the
        # engine asks for it, and then no float32 scores over the table
        # (`[.., 2048, 8192]`, or `[.., 2048, 128, 64]` by pages) or the
        # prompt: the chunk program's 3.24 GB of temporaries were those.
        kernel = case.endswith("_kernel")
        assert ("tpu_custom_call" in text) == kernel
        assert bool(_DENSE_SCORES.search(text)) == (not kernel)
        if kernel:
            assert compiled.memory_analysis().temp_size_in_bytes < 2**30
    # A dense model's programs hold nothing of the sparse-expert layer.
    assert "moe:" not in text and _expert_kernel_calls(text) == []


# ------------------------------------------------------ the hybrid programs
def _hybrid_moves(text: str, shapes: dict[str, tuple]) -> list[str]:
    """Top-level instructions of a compiled program that copy, transpose,
    slice out or write back an array as large as one of ``shapes`` (name
    -> the trailing dimensions and the least number of elements that
    count): a layer's pages, a layer's state, an expert stack. An
    in-place scatter, a fused in-place update of the carried state and a
    kernel that reads its operand where it lies are not among them."""
    entry = text[text.index("ENTRY "):]
    moves = re.compile(
        r"=\s+(\(?[a-z0-9]+\[[^=]*?)\s+"
        r"(copy|copy-start|transpose|slice|dynamic-slice|"
        r"dynamic-update-slice|concatenate|pad)\("
    )
    found = []
    for line in entry.splitlines():
        m = moves.search(line)
        if not m:
            continue
        for dims in _SHAPE.findall(m.group(1)):
            shape = tuple(int(d) for d in dims.split(","))
            for name, (tail, least) in shapes.items():
                if shape[-len(tail):] == tail and math.prod(shape) >= least:
                    found.append(f"{name}: {m.group(2)} {m.group(1)}")
    return found


def _expert_arrays_of(text: str, shape: tuple) -> list[str]:
    """Every array type of ``shape``, whatever its dtype and layout, on
    a line of a compiled program's text that belongs to the expert
    layer: under one of `moe_ffn`'s scopes, or the compiler's grouped
    matmul (which carries none)."""
    dims = ",".join(str(n) for n in shape)
    found = set()
    for line in text.splitlines():
        if "moe:" in line or "ragged-dot" in line:
            found.update(re.findall(rf"\b\w+\[{dims}\]", line))
    return sorted(found)


def _expert_makers_of(text: str, shape: tuple) -> set[str]:
    """The opcodes of the expert layer's instructions whose result is an
    array of ``shape``: every pair's rows at once are the staged
    buffer seen flat (a bitcast) and the grouped-matmul kernel's
    results, never a gather or a fusion over all of them."""
    dims = ",".join(str(n) for n in shape)
    found = set()
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and "moe:" in line and re.search(rf"\b\w+\[{dims}\]", m.group(2)):
            found.add(m.group(3))
    return found


_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][a-z\-]*)\((.*)$"
)


def _elements(types: str) -> list[int]:
    """Elements of every array type in a piece of program text."""
    return [
        math.prod(int(d) for d in dims.split(","))
        for dims in _SHAPE.findall(types)
    ]


def _state_passes(text: str, scope: str, elements: int) -> list[str]:
    """Instructions of a compiled program under the named scope, the
    kernel's own call apart, that produce or take an array of at least
    ``elements`` elements: a pass of XLA's over the state of every slot
    of a layer (the read-out's read, the update's second read and masked
    write: what ops/pallas/state_step.py replaced)."""
    sizes, found = {}, []
    lines = [m for m in map(_INSTRUCTION.match, text.splitlines()) if m]
    for m in lines:
        sizes[m.group(1)] = max(_elements(m.group(2)), default=0)
    for m in lines:
        name, types, opcode, rest = m.groups()
        if f"/{scope}/" not in rest or opcode in (
            "custom-call", "get-tuple-element", "bitcast", "tuple",
            "parameter",
        ):
            continue
        operands = re.findall(r"%([^\s,()]+)", rest.split("metadata=")[0])
        touched = [sizes[name], *(sizes.get(o, 0) for o in operands)]
        if max(touched) >= elements:
            found.append(f"{opcode} %{name} {types}")
    return found


def _entry_results(text: str, shape: str) -> list[str]:
    """The instructions of a compiled program's entry computation whose
    result matches ``shape`` (a pattern: ``f32\\[2048,8192\\]``): arrays
    that lie in HBM between two of its operations."""
    entry = text[text.index("ENTRY "):]
    return [
        line.strip() for line in entry.splitlines()
        if re.match(rf"\s+(ROOT )?%\S+ = \(?{shape}", line)
    ]


@pytest.fixture(scope="module")
def hybrid_programs(v5e):
    """nemotron3nano-serve1's own sizes (benchmarks/configs) at 6 of its
    16 blocks, every kind among them: what `aot_fit_serve_model` lowers
    for the whole configuration."""
    import json

    from benchmarks import aot_fit_serve_model
    from ray_tpu._private import chip

    here = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    with open(os.path.join(here, "configs", "nemotron3nano-serve1.json")) as f:
        conf = json.load(f)
    conf["hybrid_override_pattern"] = "ME*EM*"
    conf["num_hidden_layers"] = 6
    traffic = {"fit_prefill_buckets": [512, 1024]}
    # A chunk of 1,024 rows, over `dense_expert_rows`: the one program
    # here whose experts run as grouped matmuls over sorted pairs.
    longer = {**conf, "engine": {**conf["engine"], "prefill_chunk": 1024}}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(chip, "platform", lambda: "tpu")
        device = next(iter(v5e.device_set))
        lowered = aot_fit_serve_model.lowered_programs(conf, traffic, device)
        lowered["prefill_chunk_1024_of_2048"] = (
            aot_fit_serve_model.lowered_programs(
                longer, {"fit_prefill_buckets": [2048]}, device
            )["prefill_chunk_1024_of_2048"]
        )
        return conf, {name: low.compile() for name, low in lowered.items()}


@pytest.mark.parametrize(
    "program",
    ["prefill_512", "prefill_chunk_512_of_1024", "prefill_chunk_1024_of_2048",
     "decode"],
)
def test_hybrid_program_moves_no_pages_state_or_expert_stack(
    hybrid_programs, program
):
    """The hybrid cache is one donated tree updated in place, and the
    expert stacks are read where they lie. (Held 1856 wide, each stack
    was copied, 0.64 GB, in front of every grouped matmul: the TPU's
    layout for that shape is not the kernel's. models/nemotron_h.py
    holds them 1920 wide.)"""
    conf, programs = hybrid_programs
    eng = conf["engine"]
    pages = (eng["num_pages"] + 1) * conf["num_key_value_heads"] * PAGE * DH
    state = eng["max_batch"] * 64 * 64 * 128
    d, f = conf["hidden_size"], 1920
    shapes = {
        "pages": ((conf["num_key_value_heads"], PAGE, DH), pages),
        "state": ((64, 64, 128), state),
        "w_up": ((d, f), 64 * d * f),
        "w_down": ((f, d), 64 * d * f),
        "w_up at its own width": ((d, 1856), 64 * d * 1856),
    }
    compiled = programs[program]
    text = compiled.as_text()
    assert _hybrid_moves(text, shapes) == []
    # The grouped-matmul kernel above `dense_expert_rows`, twice an
    # expert block, and up to it the kernel that reads the touched
    # experts, once an expert block; the paged-attention and cell-write
    # kernels in the decode program.
    sorted_form = program == "prefill_chunk_1024_of_2048"
    assert "ragged-dot" not in text
    # (Two expert blocks; relu^2 experts: one matrix in the first call.)
    assert len(_grouped_kernel_calls(text)) == (4 if sorted_form else 0)
    # The prefill kernel where the table holds more than 1,024 keys,
    # dense scores up to it: the benchmark's Nemotron programs, all at
    # or under it, attend as they did before the kernel came.
    assert ("prefill_attention" in text) == sorted_form
    assert len(_expert_kernel_calls(text)) == (0 if sorted_form else 2)
    if program == "decode":
        assert "paged_attention" in text and "write_kv_cells" in text
    # Nothing the size of an expert stack is made beside the arguments
    # (the decode program's temporaries are a few MB).
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 64 * d * f * 2
    # The chunked scan is `ops/pallas/ssd_chunk.py`'s one call a Mamba
    # block from 1,024 tokens a program on (PR 67;
    # `nemotron_h._SCAN_KERNEL_TOKENS`): the cell's own programs, of 64
    # to 512 tokens, keep XLA's form.
    assert len(_kernel_calls_under(text, "ssm:scan")) == (
        2 if sorted_form else 0
    )
    if program == "decode":
        assert temp < state * 4
        # The state update is the kernel's one pass over the decoding
        # slots (ops/pallas/state_step.py), once a Mamba block: XLA
        # makes no pass of its own over a layer's states, and the
        # donated stack is the result.
        assert len(_kernel_calls_under(text, "ssm:update")) == 2
        assert _state_passes(text, "ssm:update", state) == []
        assert _copies_of(text, (2, eng["max_batch"], 64, 64, 128)) == []


# ------------------------------------------------------ the latent programs
@pytest.fixture(scope="module")
def latent_programs(v5e):
    """pangu-ultra-moe-serve1's own sizes (benchmarks/configs) at 2 of
    its 5 layers, the dense one and an expert one, with the whole
    configuration's pages: what `aot_fit_serve_family` lowers for the
    whole configuration, and a whole 2,048-token bucket beside it."""
    import json

    from benchmarks import aot_fit_serve_family
    from ray_tpu._private import chip

    here = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    with open(os.path.join(here, "configs", "pangu-ultra-moe-serve1.json")) as f:
        whole = json.load(f)
    conf = {**whole, "num_hidden_layers": 2}
    traffic = {"fit_prefill_buckets": [2048, 8192]}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(chip, "platform", lambda: "tpu")
        lowered = aot_fit_serve_family.lowered_programs(
            conf, traffic, next(iter(v5e.device_set))
        )
        return whole, {name: low.compile() for name, low in lowered.items()}


@pytest.mark.parametrize(
    "program", ["prefill_2048", "prefill_chunk_2048_of_8192", "decode"]
)
def test_latent_program_moves_no_pool_or_expert_stack_and_fits(
    latent_programs, program
):
    """The latent pool is one donated array updated in place, in one
    layout from argument to result (held 576 wide it was copied whole,
    2.9 GiB, in every prefill program, and the decode kernel's page
    copies were refused: 576 is 4.5 tiles of 128 lanes; the cells are
    held 640 wide); the expert stacks are read where they lie (2,048 is
    lane-aligned); the decode program holds the latent kernel; and the
    program's temporaries beside the WHOLE configuration's weights and
    pages stay under what a v5e offers a program."""
    from benchmarks.models import pangu_ultra_moe as family

    conf, programs = latent_programs
    eng = conf["engine"]
    d, f, cell = conf["hidden_size"], conf["moe_intermediate_size"], 640
    layer_pages = (eng["num_pages"] + 1) * PAGE * cell
    shapes = {
        "pages": ((PAGE, cell), layer_pages),
        "w_up": ((d, f), 16 * d * f),
        "w_down": ((f, d), 16 * d * f),
    }
    compiled = programs[program]
    text = compiled.as_text()
    assert _hybrid_moves(text, shapes) == []
    # The grouped-matmul kernel over a 2,048-row chunk (above
    # `dense_expert_rows`), twice in the one expert layer; in the decode
    # program the latent kernel and, in its expert layer, the kernel
    # that reads the touched experts. The compiler's grouped matmul in
    # neither.
    assert "ragged-dot" not in text
    assert len(_grouped_kernel_calls(text)) == 2 * (program != "decode")
    assert len(_expert_kernel_calls(text)) == (program == "decode")
    if program == "decode":
        assert "latent_paged_attention" in text
    else:
        # The sorted form gathers the pairs computed here, a block at a
        # time: nothing the size of all 16,384 pairs' rows is gathered,
        # the kernel's result is read by the combine where it was
        # written, and the sum back to tokens is not made over every pair.
        k = conf["num_experts_per_tok"]
        assert _expert_makers_of(text, (2048 * k, d)) <= {
            "bitcast", "custom-call"}
        assert _copies_of(text, (2048 * k, d)) == []
        assert _copies_of(text, (2048 * k // 1024, 1024, d)) == []
        assert _expert_arrays_of(text, (2048, k, d)) == []
        # (The helper sees the expert layer's arrays: its blocks' rows.)
        assert _expert_arrays_of(text, (1024, d)) != []
    memory = compiled.memory_analysis()
    # Under the two layers' pool: no copy of it is among the temporaries.
    assert memory.temp_size_in_bytes < 2 * layer_pages * 2
    arguments = (
        family.held_parameters(conf) * 2
        + conf["num_hidden_layers"] * layer_pages * 2
    )
    assert arguments > 0.25 * 16 * 2**30  # the floor a new cell is held to
    assert arguments + memory.temp_size_in_bytes < 15.75 * 2**30


# ------------------------------------------- the hybrid programs, two sublayers
@pytest.fixture(scope="module")
def granite_programs(v5e):
    """granite4hsmall-serve1's own sizes (benchmarks/configs) at 2 of its
    10 layers, a Mamba-2 and the attention layer, each with its expert
    FFN, with the whole configuration's pages and slots: what
    `aot_fit_serve_family` lowers for the whole configuration, at the
    widest table of the mix (256 pages)."""
    import json

    from benchmarks import aot_fit_serve_family
    from ray_tpu._private import chip

    here = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    with open(os.path.join(here, "configs", "granite4hsmall-serve1.json")) as f:
        whole = json.load(f)
    conf = {**whole, "num_hidden_layers": 2,
            "layer_types": ["mamba", "attention"]}
    traffic = {"fit_prefill_buckets": [16384]}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(chip, "platform", lambda: "tpu")
        lowered = aot_fit_serve_family.lowered_programs(
            conf, traffic, next(iter(v5e.device_set))
        )
        return whole, {name: low.compile() for name, low in lowered.items()}


@pytest.mark.parametrize("program", ["prefill_chunk_2048_of_16384", "decode"])
def test_granite_program_moves_no_pages_state_or_stack_and_fits(
    granite_programs, program
):
    """The same one donated cache updated in place and expert stacks
    read where they lie (768 is six tiles of 128 lanes) as Nemotron's
    programs above, through the same `llm/hybrid_kv.py`; and a
    2,048-token chunk at a 256-page table attends by the prefill kernel:
    no `[heads, chunk, table]` float32 scores (4.29 GB: with them the
    program cannot fit beside 12.9 GB of arguments), and the program's
    temporaries beside the WHOLE configuration's arguments stay under
    what a v5e offers a program."""
    from benchmarks.models import granite_hybrid as family

    conf, programs = granite_programs
    eng = conf["engine"]
    d, f = conf["hidden_size"], conf["intermediate_size"]
    held, hkv = conf["num_local_experts"], conf["num_key_value_heads"]
    layer_pages = (eng["num_pages"] + 1) * hkv * PAGE * DH
    state = eng["max_batch"] * 128 * 64 * 128
    shapes = {
        "pages": ((hkv, PAGE, DH), layer_pages),
        "state": ((128, 64, 128), state),
        "w_up": ((d, f), held * d * f),
        "w_down": ((f, d), held * d * f),
    }
    compiled = programs[program]
    text = compiled.as_text()
    assert _hybrid_moves(text, shapes) == []
    memory = compiled.memory_analysis()
    chunk, table = eng["prefill_chunk"], 16384
    if program == "decode":
        assert "paged_attention" in text and "write_kv_cells" in text
        assert "ragged-dot" not in text
        assert len(_expert_kernel_calls(text)) == 2  # one a layer's FFN
        assert memory.temp_size_in_bytes < state * 4
        # The state update is the kernel's one pass over the decoding
        # slots: no pass of XLA's over the layer's states, no copy of
        # the donated stack.
        assert len(_kernel_calls_under(text, "ssm:update")) == 1
        assert _state_passes(text, "ssm:update", state) == []
        assert _copies_of(text, (1, eng["max_batch"], 128, 64, 128)) == []
    else:
        assert "prefill_attention" in text and "ragged-dot" not in text
        assert _expert_kernel_calls(text) == []
        assert len(_grouped_kernel_calls(text)) == 4  # two a layer's FFN
        # The chunked scan is `ops/pallas/ssd_chunk.py`'s one call the
        # Mamba layer (PR 67): XLA's dual form wrote the decays and the
        # decayed scores as float32 [8, 1, 128, 256, 256] (268 MB each)
        # and y through a copy out of the head-major layout; x, B and C
        # are views of the convolution's one result, not slices of it.
        assert len(_kernel_calls_under(text, "ssm:scan")) == 1
        assert not re.search(r"f32\[8,1,128,256,256\]", text)
        assert _copies_of(text, (8, 256, 1, 128, 64)) == []
        assert _entry_results(text, r"f32\[2048,8192\]\S* slice") == []
        # No array with the chunk's queries against the table's keys,
        # whatever the leading dimensions and the dtype.
        assert not re.search(rf"\[[\d,]*{chunk},{table}\]", text)
        assert memory.temp_size_in_bytes < 32 * chunk * table * 4 // 2
        # The sorted form gathers the pairs computed here, a block at a
        # time: nothing the size of all 20,480 pairs' rows is gathered,
        # the kernel's result is read by the combine where it was
        # written, and the sum back to tokens is not made over every pair.
        k = conf["num_experts_per_tok"]
        assert _expert_makers_of(text, (chunk * k, d)) <= {
            "bitcast", "custom-call"}
        assert _copies_of(text, (chunk * k, d)) == []
        assert _copies_of(text, (chunk * k // 1024, 1024, d)) == []
        assert _expert_arrays_of(text, (chunk, k, d)) == []
    pool = 2 * layer_pages * 2  # K and V of the one attention layer
    arguments = (
        family.held_parameters(conf) * 2 + pool
        + 9 * eng["max_batch"] * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    )
    assert arguments > 0.25 * 16 * 2**30  # the floor a new cell is held to
    assert arguments + memory.temp_size_in_bytes < 15.75 * 2**30


@pytest.fixture(scope="module")
def qwen3next_programs(v5e):
    """qwen3next-80b-serve1's own sizes (benchmarks/configs) at 2 of its
    4 layers: a period of two, so that layer 0 is a Gated DeltaNet and
    layer 1 the gated attention layer, each with its expert FFN, with
    the whole configuration's pages and slots: what
    `aot_fit_serve_family` lowers, at the widest table of the mix."""
    import json

    from benchmarks import aot_fit_serve_family
    from ray_tpu._private import chip

    here = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    with open(os.path.join(here, "configs", "qwen3next-80b-serve1.json")) as f:
        whole = json.load(f)
    conf = {**whole, "num_hidden_layers": 2, "full_attention_interval": 2}
    traffic = {"fit_prefill_buckets": [16384]}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(chip, "platform", lambda: "tpu")
        lowered = aot_fit_serve_family.lowered_programs(
            conf, traffic, next(iter(v5e.device_set))
        )
        return whole, {name: low.compile() for name, low in lowered.items()}


@pytest.mark.parametrize("program", ["prefill_chunk_2048_of_16384", "decode"])
def test_qwen3next_program_moves_no_pages_state_or_stack_and_fits(
    qwen3next_programs, program
):
    """As granite's programs above, through the same `llm/hybrid_kv.py`
    with the fourth letter: the donated cache updated in place (pages of
    256-wide cells, a float32 `[32, 128, 128]` matrix state a slot), the
    256 held experts' stacks read where they lie, the three attention
    kernels at a head of 256, and the temporaries beside the WHOLE
    configuration's arguments under what a v5e offers a program."""
    from benchmarks.models import qwen3_next as family

    conf, programs = qwen3next_programs
    eng = conf["engine"]
    d, f = conf["hidden_size"], conf["moe_intermediate_size"]
    held, hkv = conf["num_experts"], conf["num_key_value_heads"]
    layer_pages = (eng["num_pages"] + 1) * hkv * PAGE * DH256
    state = eng["max_batch"] * 32 * 128 * 128
    shapes = {
        "pages": ((hkv, PAGE, DH256), layer_pages),
        "state": ((32, 128, 128), state),
        "w_up": ((d, f), held * d * f),
        "w_down": ((f, d), held * d * f),
    }
    compiled = programs[program]
    text = compiled.as_text()
    assert _hybrid_moves(text, shapes) == []
    memory = compiled.memory_analysis()
    chunk, table = eng["prefill_chunk"], 16384
    if program == "decode":
        assert "paged_attention" in text and "write_kv_cells" in text
        assert "ragged-dot" not in text
        assert len(_expert_kernel_calls(text)) == 2  # one a layer's FFN
        assert memory.temp_size_in_bytes < state * 4
        # The delta rule's step is the kernel's one pass over the
        # decoding slots: no pass of XLA's over the layer's states, no
        # copy of the donated stack.
        assert len(_kernel_calls_under(text, "gdn:update")) == 1
        assert _state_passes(text, "gdn:update", state) == []
        assert _copies_of(text, (1, eng["max_batch"], 32, 128, 128)) == []
    else:
        assert "prefill_attention" in text and "ragged-dot" not in text
        assert _expert_kernel_calls(text) == []
        assert len(_grouped_kernel_calls(text)) == 4  # two a layer's FFN
        assert not re.search(rf"\[[\d,]*{chunk},{table}\]", text)
        # The delta rule is ONE kernel call a layer under its scope: no
        # scan over the rule chunks, none of its per-chunk float32
        # intermediates ([64 chunks, 16 key heads, 2, 32, ..]) in HBM.
        assert len(_kernel_calls_under(text, "gdn:scan")) == 1
        assert not [
            line for line in text.splitlines()
            if "gdn:scan" in line and " while(" in line
        ]
        assert not re.search(r"f32\[64,16,2,32,\d+\]", text)
        k = conf["num_experts_per_tok"]
        assert _expert_makers_of(text, (chunk * k, d)) <= {
            "bitcast", "custom-call"}
        assert _copies_of(text, (chunk * k, d)) == []
        assert _copies_of(text, (chunk * k // 1024, 1024, d)) == []
        assert memory.temp_size_in_bytes < 2**30
    arguments = conf["fit"]["argument_bytes"]
    counted = (
        family.held_parameters(conf) * 2 + 2 * layer_pages * 2
        + 3 * eng["max_batch"]
        * (family.gdn_state_bytes_per_slot(conf) + 3 * 8192 * 2)
    )
    # The float32 leaves (routers, norms, convolutions) are 9 MB more.
    assert abs(arguments - counted) < 16e6
    assert arguments > 0.25 * 16 * 2**30  # the floor a new cell is held to
    assert arguments + memory.temp_size_in_bytes < 15.75 * 2**30


@pytest.fixture(scope="module")
def laguna_programs(v5e):
    """laguna-s21-serve1's own sizes (benchmarks/configs) at 2 of its 5
    layers, so that layer 0 is the full layer with the dense FFN and
    layer 1 a window layer with its expert FFN, with the whole
    configuration's pages and slots: what `aot_fit_serve_family` lowers,
    at every table of the mix. Compiled when first asked for."""
    import json

    from benchmarks import aot_fit_serve_family
    from ray_tpu._private import chip

    here = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    with open(os.path.join(here, "configs", "laguna-s21-serve1.json")) as f:
        whole = json.load(f)
    conf = {**whole, "num_hidden_layers": 2}
    traffic = {"fit_prefill_buckets": [4096, 8192, 16384]}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(chip, "platform", lambda: "tpu")
        lowered = aot_fit_serve_family.lowered_programs(
            conf, traffic, next(iter(v5e.device_set))
        )
    compiled = {}

    def program(name):
        if name not in compiled:
            compiled[name] = lowered[name].compile()
        return compiled[name]

    return whole, program


def _arrays_under(text: str, scope: str) -> set[str]:
    """Every array type on the lines of a compiled program's text that
    carry the named scope."""
    found = set()
    for line in text.splitlines():
        if f"/{scope}/" in line:
            found.update(re.findall(r"\b[a-z]+\d*\[[\d,]+\]", line))
    return found


@pytest.mark.parametrize(
    "program",
    ["prefill_chunk_2048_of_4096", "prefill_chunk_2048_of_8192",
     "prefill_chunk_2048_of_16384", "decode"],
)
def test_laguna_program_moves_no_pages_or_stack_and_fits(
    laguna_programs, program
):
    """Through the same `llm/hybrid_kv.py` with the letters `W` and `D`:
    the donated cache updated in place (pages for the full layer, a
    `[512, 8, 128]` ring a slot for the window layer), the 128 held
    experts' stacks read where they lie, the band kernel at 72 heads and
    the prefill and paged kernels at 48, and the temporaries beside the
    WHOLE configuration's arguments under what a v5e offers a program.
    A window layer's part of a chunk program is the same at every table
    width: it holds no array that grows with the context."""
    from benchmarks.models import laguna as family

    conf, compiled_program = laguna_programs
    eng = conf["engine"]
    d, f = conf["hidden_size"], conf["moe_intermediate_size"]
    held, hkv = conf["num_experts"], conf["num_key_value_heads"]
    layer_pages = (eng["num_pages"] + 1) * hkv * PAGE * DH
    shapes = {
        "pages": ((hkv, PAGE, DH), layer_pages),
        "w_up": ((d, f), held * d * f),
        "w_down": ((f, d), held * d * f),
    }
    compiled = compiled_program(program)
    text = compiled.as_text()
    assert _hybrid_moves(text, shapes) == []
    memory = compiled.memory_analysis()
    chunk = eng["prefill_chunk"]
    if program == "decode":
        assert "paged_attention" in text and "write_kv_cells" in text
        assert "window_attention" not in text and "ragged-dot" not in text
        assert len(_expert_kernel_calls(text)) == 1  # the one sparse FFN
        # A window layer's decode is XLA's: scores [slots, 8, 9, 512].
        assert f"f32[{eng['max_batch']},{hkv},{HW // hkv},{WINDOW}]" in text
        assert memory.temp_size_in_bytes < 2**28
    else:
        table = int(program.rsplit("_", 1)[1])
        assert "prefill_attention" in text and "window_attention" in text
        assert "ragged-dot" not in text and _expert_kernel_calls(text) == []
        assert len(_grouped_kernel_calls(text)) == 2  # the one sparse FFN
        # No score over the table or over the band in HBM.
        assert not re.search(rf"\[[\d,]*{chunk},{table}\]", text)
        assert not re.search(rf"\[[\d,]*{chunk},{WINDOW + chunk}\]", text)
        k = conf["num_experts_per_tok"]
        assert _expert_makers_of(text, (chunk * k, d)) <= {
            "bitcast", "custom-call"}
        assert _copies_of(text, (chunk * k, d)) == []
        assert _copies_of(text, (chunk * k // 1024, 1024, d)) == []
        assert memory.temp_size_in_bytes < 2**30
        # The window layer's operations and the ring's write are the
        # narrowest table's, array for array.
        narrow = compiled_program("prefill_chunk_2048_of_4096").as_text()
        for scope in ("attn:window", "attn:window_write"):
            mine = _arrays_under(text, scope)
            assert mine and mine == _arrays_under(narrow, scope)
            assert f"bf16[{hkv},{WINDOW + chunk},{DH}]" in _arrays_under(
                text, "attn:window")
    arguments = conf["fit"]["argument_bytes"]
    counted = (
        family.held_parameters(conf) * 2 + 2 * 2 * layer_pages * 2
        + family.window_layers(conf) * eng["max_batch"]
        * family.window_bytes_per_slot(conf)
    )
    # The float32 leaves (routers, norms) are 4 MB more.
    assert abs(arguments - counted) < 8e6
    assert arguments > 0.25 * 16 * 2**30  # the floor a new cell is held to
    assert arguments + memory.temp_size_in_bytes < 15.75 * 2**30


# ------------------------------------- the sorted expert form's combine
@pytest.mark.parametrize("family", ["latent", "granite", "qwen3next"])
def test_chunk_program_sums_expert_rows_by_the_kernel(family, request):
    """A 2,048-token chunk's expert layers (the sorted form over the
    pairs computed here) sum their rows onto the tokens by
    ops/pallas/expert_combine.py: no scatter instruction is left under
    ``moe:combine``, where the benchmark's reducers read the kernel's
    call; the decode programs (the every-row form) hold neither."""
    _, programs = request.getfixturevalue(f"{family}_programs")
    chunk = next(name for name in programs if name.startswith("prefill_chunk"))
    text = programs[chunk].as_text()
    assert _combine_scatters(text) == []
    assert _kernel_calls_under(text, "moe:combine") != []
    decode = programs["decode"].as_text()
    assert _combine_scatters(decode) == []
    assert _kernel_calls_under(decode, "moe:combine") == []


@pytest.fixture(scope="module")
def glm5_next_programs(v5e):
    """glm53flash-serve1's own sizes (benchmarks/configs) at 2 of its 5
    layers (KDA + dense FFN, sparse latent attention + experts: all four
    kinds of sublayer), with the whole configuration's pages and slots:
    what `aot_fit_serve_family` lowers, at every table of the mix.
    Compiled when first asked for."""
    import json

    from benchmarks import aot_fit_serve_family
    from ray_tpu._private import chip

    here = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    with open(os.path.join(here, "configs", "glm53flash-serve1.json")) as f:
        whole = json.load(f)
    conf = {**whole, "num_hidden_layers": 2, **{
        key: whole[key][:2]
        for key in ("layer_types", "mlp_layer_types", "indexer_types")
    }}
    traffic = {"fit_prefill_buckets": [8192, 16384, 32768, 65536]}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(chip, "platform", lambda: "tpu")
        lowered = aot_fit_serve_family.lowered_programs(
            conf, traffic, next(iter(v5e.device_set))
        )
    compiled = {}

    def program(name):
        if name not in compiled:
            compiled[name] = lowered[name].compile()
        return compiled[name]

    program.lowered = lowered
    return whole, program


@pytest.mark.parametrize(
    "program",
    ["prefill_chunk_2048_of_8192", "prefill_chunk_2048_of_16384",
     "prefill_chunk_2048_of_32768", "prefill_chunk_2048_of_65536", "decode"],
)
def test_glm5_next_program_moves_no_pool_or_stack_and_fits(
    glm5_next_programs, program
):
    """Through the same `llm/hybrid_kv.py` with the letters `K` and `L`
    and four residual streams: the donated cache updated in place (the
    latent and index pools, the matrix state a slot), the 36 held
    experts' stacks read where they lie, a chunk's attention following
    the selection (no score over the table in HBM, at 65,536 keys
    either), and the temporaries beside the WHOLE configuration's
    arguments under what a v5e offers a program."""
    from benchmarks.models import glm5_next as family

    conf, compiled_program = glm5_next_programs
    eng = conf["engine"]
    d, f = conf["hidden_size"], conf["moe_intermediate_size"]
    held, rank = conf["n_routed_experts"], conf["kv_lora_rank"]
    pages = eng["num_pages"] + 1
    shapes = {
        "latent": ((PAGE, rank), pages * PAGE * rank),
        "state": ((64, 128, 128), eng["max_batch"] * 64 * 128 * 128),
        "w_up": ((d, f), held * d * f),
        "w_down": ((f, d), held * d * f),
    }
    compiled = compiled_program(program)
    text = compiled.as_text()
    assert _hybrid_moves(text, shapes) == []
    memory = compiled.memory_analysis()
    chunk = eng["prefill_chunk"]
    if program == "decode":
        assert "jit(kda_state_step)" in text
        assert len(_expert_kernel_calls(text)) == 1  # the one sparse FFN
        assert memory.temp_size_in_bytes < 2**30
        # 16 slots are under `mhc._MHC_KERNEL_ROWS`: the residual
        # path keeps XLA's form here (it reads no slower alone, PR 64).
        assert _kernel_calls_under(text, "mhc:") == []
        assert _arrays_under(text, "mhc:mix") != set()
    else:
        table = int(program.rsplit("_", 1)[1])
        assert len(_grouped_kernel_calls(text)) == 2  # the one sparse FFN
        # The per-channel delta rule is ONE kernel call a KDA layer under
        # its scope: no scan over the rule chunks, none of its float32
        # intermediates a rule chunk ([64 chunks, 64 heads, 32, ..] and
        # the columns' [.., 2 sub-chunks, 32, 128]) in HBM.
        assert len(_kernel_calls_under(text, "kda:scan")) == 1
        assert "jit(kda_chunk_rule)" in text
        assert not [
            line for line in text.splitlines()
            if "kda:scan" in line and " while(" in line
        ]
        assert not re.search(r"f32\[64,64,(2,)?(16|32),\d+\]", text)
        # Between the in-projections' matmuls and the out-projection's
        # nothing is XLA's (PR 62). The one float32 [chunk, 3 H dk] in HBM
        # is the in-projection's result: none behind the convolution, and
        # no [chunk + K - 1, ..] of the tail's rows before it. The two
        # float32 [chunk, H dk] are the decay's and the output gate's
        # pre-activations: no q, k, v, g, and `o` leaves in bfloat16.
        lin = conf["linear_attn_config"]
        kda_heads, dk = lin["num_heads"], lin["head_dim"]
        rows = f"({chunk}|{chunk + lin['short_conv_kernel_size'] - 1})"
        wide = _entry_results(text, rf"f32\[{rows},{3 * kda_heads * dk}\]")
        assert len(wide) == 1 and "kda:in/dot_general" in wide[0]
        # (The indexer's scores over a 32,768-token table are as wide.)
        tall = [
            line for line in _entry_results(
                text, rf"f32\[{rows},({kda_heads * dk}|{kda_heads},{dk})\]"
            ) if "/dsa:" not in line
        ]
        assert len(tall) == 2 and all("kda:in/dot_general" in t for t in tall)
        assert f"bf16[{chunk},{kda_heads * dk}]" in _kernel_calls_under(
            text, "kda:scan"
        )[0]
        # No score of the attention over the table in HBM: the indexer's
        # [chunk, blocks] float32 is the one array as wide as the context.
        # (A bare [2048, 16384] is the heads' width, 64 x 256.)
        heads = conf["num_attention_heads"]
        for keys in (table, table // 4):
            assert f"[{heads},{chunk},{keys}]" not in text
            assert f"[{chunk},{heads},{keys}]" not in text
        assert f"f32[{chunk},{table // 4}]" in text
        assert f"s32[{chunk},512]" in text  # the selection
        assert memory.temp_size_in_bytes < 3 * 2**30
        # The residual path (PR 64): a sublayer's mix and its spread are
        # each ONE call of ops/pallas/mhc_streams.py under its scope (the
        # fixture's two layers are four sublayers), lowered once a
        # program and called from every sublayer; the streams pass from a
        # spread to the next mix as the [chunk, n d] bf16 array the calls
        # take, and no float32 copy of them ([.., 4, 4096] or [.., 16384]
        # a token) is left among the program's operations there.
        n, sublayers = conf["hc_mult"], 4
        lowered = compiled_program.lowered[program].as_text()
        under = set()
        for scope, kernel in (("mhc:mix", "mhc_mix"),
                              ("mhc:spread", "mhc_spread")):
            calls = _kernel_calls_under(text, scope)
            assert len(calls) == sublayers
            assert all(f"jit({kernel})" in call for call in calls)
            assert len(re.findall(rf"func\.func private @{kernel}\(", lowered)) == 1
            assert len(re.findall(rf"call @{kernel}\(", lowered)) == sublayers
            under |= _arrays_under(text, scope)
        assert f"bf16[{chunk},{n * d}]" in under
        assert not [
            a for a in under
            if a.startswith("f32[") and max(_elements(a)) >= chunk * d
        ]
        # A 16k chunk program's temporaries at the fixture's two layers:
        # 0.659 GiB, for 0.640 in XLA's form, whose ``h`` [chunk, d] (16
        # MB) and two ``H`` (1 MB each, 128 lanes a row) were fused into
        # their readers.
        if table <= 16384:
            assert memory.temp_size_in_bytes < 0.665 * 2**30
    arguments = conf["fit"]["argument_bytes"]
    counted = (
        family.held_parameters(conf) * 2
        + pages * PAGE * (rank + conf["index_head_dim"] // 4) * 2
        + family.kda_layers(conf) * eng["max_batch"] * (
            family.kda_state_bytes_per_slot(conf) + 3 * 24576 * 2)
    )
    # The float32 leaves (routers, norms, the residual mixing's P) are
    # 15.7 MB more, the indexer's tails 25 KB.
    assert abs(arguments - counted) < 3.2e7
    assert arguments > 0.25 * 16 * 2**30  # the floor a new cell is held to
    assert arguments + memory.temp_size_in_bytes < 15.75 * 2**30


@pytest.fixture(scope="module")
def motif_programs(v5e):
    """motif3beta-serve1's own sizes (benchmarks/configs) at 3 of its 5
    layers (published layers 1-3: a window layer with the dense FFN, a
    window layer and the full layer with experts: all four letters),
    with the whole configuration's pages and slots: what
    `aot_fit_serve_family` lowers, at the narrowest and the widest table
    of the mix. Compiled when first asked for."""
    import json

    from benchmarks import aot_fit_serve_family
    from ray_tpu._private import chip

    here = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    with open(os.path.join(here, "configs", "motif3beta-serve1.json")) as f:
        whole = json.load(f)
    conf = {**whole, "num_hidden_layers": 3}
    traffic = {"fit_prefill_buckets": [8192, 65536]}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(chip, "platform", lambda: "tpu")
        lowered = aot_fit_serve_family.lowered_programs(
            conf, traffic, next(iter(v5e.device_set))
        )
    compiled = {}

    def program(name):
        if name not in compiled:
            compiled[name] = lowered[name].compile()
        return compiled[name]

    return whole, program


@pytest.mark.parametrize(
    "program",
    ["prefill_chunk_2048_of_8192", "prefill_chunk_2048_of_65536", "decode"],
)
def test_motif_program_moves_no_pool_ring_or_stack_and_fits(
    motif_programs, program
):
    """Through the same `llm/hybrid_kv.py` with the letters `A` and `R`:
    the donated cache updated in place (the full layer's pool of cells,
    the window layers' rings a slot), the 48 held experts' stacks read
    where they lie, a chunk's attention by the two kernels (no score
    over the table or the band in HBM), a step's full layer by the paged
    latent kernel, and the temporaries beside the WHOLE configuration's
    arguments under what a v5e offers a program."""
    from benchmarks.models import motif as family

    conf, compiled_program = motif_programs
    eng = conf["engine"]
    d, f, held = (conf["hidden_size"], conf["moe_intermediate_size"],
                  conf["num_experts"])
    pages, slots, w = eng["num_pages"] + 1, eng["max_batch"], conf["sliding_window"]
    width = 640
    shapes = {
        "cells": ((PAGE, width), pages * PAGE * width),
        "win_cells": ((w, width), 2 * slots * w * width),
        "w_up": ((d, f), held * d * f),
        "w_down": ((f, d), held * d * f),
    }
    compiled = compiled_program(program)
    text = compiled.as_text()
    # (The decode program's `copy-start` of the rings is the compiler's
    # own prefetch of 5 MB into VMEM, `S(1)`, in the layout they have: it
    # is no re-layout and no second buffer in HBM. A `copy`, `transpose`
    # or slice of them is what a gather by ring index cost: `_rolled`.)
    assert [
        move for move in _hybrid_moves(text, shapes)
        if not move.startswith("win_cells: copy-start")
    ] == []
    memory = compiled.memory_analysis()
    heads, chunk = conf["num_attention_heads"], eng["prefill_chunk"]
    if program == "decode":
        assert len(_kernel_calls_under(text, "mla:attend")) == 1
        assert "jit(latent_paged_attention)" in text
        assert len(_expert_kernel_calls(text)) == 2  # two sparse FFNs
        assert memory.temp_size_in_bytes < 2**28
    else:
        table = int(program.rsplit("_", 1)[1])
        assert len(_kernel_calls_under(text, "mla:attend")) == 1
        assert "jit(latent_prefill_attention)" in text
        assert len(_kernel_calls_under(text, "attn:window")) == 2
        assert len(_grouped_kernel_calls(text)) == 4  # two sparse FFNs
        for keys in (table, w + chunk):
            assert f"[{heads},{chunk},{keys}]" not in text
            assert f"[16,5,{chunk},{keys}]" not in text
        assert memory.temp_size_in_bytes < 2**30
    arguments = conf["fit"]["argument_bytes"]
    counted = (
        family.held_parameters(conf) * 2
        + family.full_layers(conf) * pages * PAGE * width * 2
        + family.window_layers(conf) * slots * w * width * 2
    )
    # The float32 leaves (routers, norms, the residual mixing's P) are
    # 16 MB more.
    assert abs(arguments - counted) < 3.2e7
    assert arguments > 0.25 * 16 * 2**30  # the floor a new cell is held to
    assert arguments + memory.temp_size_in_bytes < 15.75 * 2**30


@pytest.fixture(scope="module")
def phi4flash_programs(v5e):
    """phi4miniflash-serve1's own sizes (benchmarks/configs) at 8 of its
    32 layers, split as the model splits them (Mamba, window, Mamba, the
    full layer; GMU, cross, GMU, cross: every letter), with the whole
    configuration's pages and slots: what `aot_fit_serve_family` lowers,
    BOTH chunk programs at the narrowest and the widest table of the
    mix. Compiled when first asked for."""
    import json

    from benchmarks import aot_fit_serve_family
    from ray_tpu._private import chip

    here = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    with open(os.path.join(here, "configs", "phi4miniflash-serve1.json")) as f:
        whole = json.load(f)
    conf = {**whole, "num_hidden_layers": 8, "mb_per_layer": 0}
    traffic = {"fit_prefill_buckets": [4096, 26112]}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(chip, "platform", lambda: "tpu")
        lowered = aot_fit_serve_family.lowered_programs(
            conf, traffic, next(iter(v5e.device_set))
        )
    compiled = {}

    def program(name):
        if name not in compiled:
            compiled[name] = lowered[name].compile()
        return compiled[name]

    return whole, program


@pytest.mark.parametrize(
    "program",
    ["prefill_chunk_2048_of_4096_self", "prefill_chunk_2048_of_26112",
     "decode"],
)
def test_phi4flash_program_moves_no_pool_ring_or_state_and_stops_halfway(
    phi4flash_programs, program
):
    """Through the same `llm/hybrid_kv.py` with the letters `S`, `U` and
    `C`: the donated cache updated in place (the ONE pool layer, the
    rings as a second small pool, Mamba's state stack); the scan and the
    state update by `ops/pallas/selective_scan.py`, with nothing of
    shape [tokens, 16, 5,120] beside them; the program that stops before
    the cross-decoder holds none of its scopes and none of its weights
    (nor the full layer's own attention: its output is read by nobody);
    the one that runs it attends the pool once more a cross layer, on
    one row; the decode program attends the pool once a block that reads
    it and the rings by the pool's own kernels."""
    conf, compiled_program = phi4flash_programs
    eng = conf["engine"]
    pages, slots, w = eng["num_pages"] + 1, eng["max_batch"], conf["sliding_window"]
    chunk, wide, n = eng["prefill_chunk"], 2 * conf["hidden_size"], 16
    ring_pages = slots * (w // PAGE) + 1
    shapes = {
        "pool": ((10, PAGE, 128), pages * 10 * PAGE * 128),
        "rings": ((10, PAGE, 128), ring_pages * 10 * PAGE * 128),
        "state": ((n, wide // 128, 128), slots * n * wide),
    }
    compiled = compiled_program(program)
    text = compiled.as_text()
    assert _hybrid_moves(text, shapes) == []
    memory = compiled.memory_analysis()
    if program == "decode":
        # Layer 3's write and attend and two cross attends of the pool;
        # the window layer's write and attend of its ring.
        assert len(_kernel_calls_under(text, "attn:full/self")) == 2
        assert len(_kernel_calls_under(text, "attn:full/cross")) == 2
        assert len(_kernel_calls_under(text, "attn:window")) == 2
        assert len(_kernel_calls_under(text, "ssm:update")) == 2
        assert "jit(selective_state_step)" in text
        assert memory.temp_size_in_bytes < 2**27
        return
    assert len(_kernel_calls_under(text, "ssm:scan")) == 2
    assert "jit(selective_scan_chunk)" in text
    for shape in (f"[{chunk},{n},{wide}]", f"[{chunk},{wide},{n}]",
                  f"[{chunk},{n},{wide // 128},128]"):
        assert shape not in text
    assert len(_kernel_calls_under(text, "attn:window")) == 1
    assert "attn:full/self/scatter" in text  # the chunk's pages, written
    table = int(program.split("_of_")[1].split("_")[0])
    assert f"[40,{chunk},{table}]" not in text  # no score over the table
    cross = [scope for scope in ("attn:full/cross", "gmu:gate", "gmu:out")
             if scope in text]
    if program.endswith("_self"):
        assert cross == [] and f"[1,1,{conf['vocab_size']}]" not in text
        # Nothing reads what the full layer's attention and its MLP
        # would add to the stream, so the compiler drops both: the
        # layer's keys and values are all this program needs of it.
        assert _kernel_calls_under(text, "attn:full") == []
    else:
        assert len(cross) == 3
        assert "jit(prefill_attention)" in text
        assert len(_kernel_calls_under(text, "attn:full/self")) == 1
        assert len(_kernel_calls_under(text, "attn:full/cross")) == 2
    assert memory.temp_size_in_bytes < 2**29


@pytest.mark.parametrize("family", ["hybrid", "granite", "qwen3next", "laguna"])
def test_a_program_of_one_residual_stream_holds_no_stream_kernel(
    family, request
):
    """`hybrid_kv._read` / `_residual` are every hybrid family's, and
    reach `mhc.mhc_mix` / `mhc_spread` (on a TPU: the two calls of
    ops/pallas/mhc_streams.py) only where the config carries more than
    one residual stream, which GLM-5.3-Flash's alone does: no other
    family's program holds either scope or either call. (Their lowered
    texts at PR 64 are the parent's, the kernels' source paths apart:
    CHANGES.md.)"""
    _, programs = request.getfixturevalue(f"{family}_programs")
    if callable(programs):  # laguna's, compiled when first asked for
        programs = {name: programs(name) for name in (
            "prefill_chunk_2048_of_4096", "prefill_chunk_2048_of_8192",
            "prefill_chunk_2048_of_16384", "decode",
        )}
    for compiled in programs.values():
        assert "mhc" not in compiled.as_text()


# ------------------------------------- the latent programs, a double layer
@pytest.fixture(scope="module")
def longcat_programs(v5e):
    """longcat-flash-omni-serve1's own sizes (benchmarks/configs) at 1 of
    its 4 double layers, with the whole configuration's pages: what
    `aot_fit_serve_family` lowers, a whole prompt's program, a chunk's
    and the decode program. Compiled when first asked for."""
    import json

    from benchmarks import aot_fit_serve_family
    from ray_tpu._private import chip

    here = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    with open(os.path.join(here, "configs", "longcat-flash-omni-serve1.json")) as f:
        whole = json.load(f)
    conf = {**whole, "num_layers": 1}
    traffic = {"fit_prefill_buckets": [2048, 8192]}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(chip, "platform", lambda: "tpu")
        lowered = aot_fit_serve_family.lowered_programs(
            conf, traffic, next(iter(v5e.device_set))
        )
    compiled = {}

    def program(name):
        if name not in compiled:
            compiled[name] = lowered[name].compile()
        return compiled[name]

    return whole, program


@pytest.mark.parametrize(
    "program", ["prefill_2048", "prefill_chunk_2048_of_8192", "decode"]
)
def test_longcat_program_runs_both_sublayers_through_the_kernels_and_fits(
    longcat_programs, program
):
    """One double layer: each latent kernel is called twice, at 64 heads
    (they had only ever been lowered at 128), once an attention sublayer
    with a row of the pool each; the pool is updated in place and the
    expert stacks read where they lie; the shortcut's experts are the
    grouped-matmul kernel over a chunk's sorted pairs and the
    touched-experts kernel in a decode step, and the identity outputs'
    sum is under `moe:combine/moe:zero` and is no kernel's; and the
    program's temporaries beside the WHOLE configuration's weights and
    pages stay under what a v5e offers a program."""
    from benchmarks.models import longcat_flash as family

    conf, programs = longcat_programs
    eng = conf["engine"]
    d, f, cell = conf["hidden_size"], conf["expert_ffn_hidden_size"], 640
    heads = conf["num_attention_heads"]
    sublayer_pages = (eng["num_pages"] + 1) * PAGE * cell
    shapes = {
        "pages": ((PAGE, cell), sublayer_pages),
        "w_up": ((d, f), 16 * d * f),
        "w_down": ((f, d), 16 * d * f),
    }
    compiled = programs(program)
    text = compiled.as_text()
    assert _hybrid_moves(text, shapes) == []
    assert "ragged-dot" not in text
    assert "moe:combine/moe:zero" in text and "dense:mlp" in text
    assert not [
        line for line in _kernel_calls_under(text, "moe:zero")
    ]
    attend = _kernel_calls_under(text, "mla:attend")
    assert len(attend) == 2
    if program == "decode":
        assert all("jit(latent_paged_attention)" in line for line in attend)
        assert f"bf16[{eng['max_batch']},{heads},512]" in "".join(attend)
        assert len(_expert_kernel_calls(text)) == 1
        assert len(_grouped_kernel_calls(text)) == 0
    else:
        assert all("jit(latent_prefill_attention)" in line for line in attend)
        assert f"bf16[{heads},2048,128]" in "".join(attend)
        assert len(_grouped_kernel_calls(text)) == 2
        assert len(_expert_kernel_calls(text)) == 0
        # No score over the table in HBM, and nothing the size of all
        # 24,576 routes' rows is gathered: a third of them are no pair.
        k = conf["moe_topk"]
        assert f"[{heads},2048,8192]" not in text
        assert _copies_of(text, (2048 * k, d)) == []
        assert _expert_arrays_of(text, (2048, k, d)) == []
    memory = compiled.memory_analysis()
    # Under the two sublayers' pool: no copy of it among the temporaries.
    assert memory.temp_size_in_bytes < 2 * sublayer_pages * 2
    counted = (
        family.held_parameters(conf) * 2
        + family.attention_sublayers(conf) * sublayer_pages * 2
    )
    # The float32 leaves, counted here at two bytes, are 38 MB more: the
    # four routers, 6,144 x 768 each, and the norms.
    assert abs(conf["fit"]["argument_bytes"] - counted) < 4e7
    assert counted > 12e9  # what the issue asks the fullest device to hold
    assert counted + memory.temp_size_in_bytes < 15.75 * 2**30


# ------------------------------- the chunk programs' expansion, bounded
# A chunk program's temporaries at the parent (PR 65's tree: the two
# einsums over the whole table), compiled here for the same described
# v5e at these fixtures' cuts; bytes. This tree's read 607,101,440 /
# 1,011,811,328, 497,884,160 / 500,013,056 and 297,347,584 /
# 827,197,952: the outputs are the einsums' arrays, and the compiler's
# schedule moves a few hundred KB either way.
_PARENT_TEMP = {
    ("latent", "prefill_2048"): 607_585_280,
    ("latent", "prefill_chunk_2048_of_8192"): 1_013_037_056,
    ("longcat", "prefill_2048"): 497_368_064,
    ("longcat", "prefill_chunk_2048_of_8192"): 500_238_848,
    ("motif", "prefill_chunk_2048_of_8192"): 297_315_328,
    ("motif", "prefill_chunk_2048_of_65536"): 828_712_448,
}


@pytest.mark.parametrize("family, program", list(_PARENT_TEMP))
def test_chunk_program_expands_by_the_bounded_kernel_and_fills_nothing(
    family, program, request
):
    """New cases of the three `..._and_fits` tests above, on the programs
    their fixtures compiled: under `mla:expand` a chunk program holds
    one call of `latent_expand` an attention sublayer over the table and
    no product of XLA's (ops/pallas/latent_attention.py: the key blocks
    past the chunk's end are never written), nothing broadcasts a
    constant into an array of the expanded keys' shape `[G, T, 128]` (a
    fill of the dead part would cost half of what the bound saves), and
    the temporaries are the parent's to within a MiB."""
    conf, programs = request.getfixturevalue(f"{family}_programs")
    compiled = programs(program) if callable(programs) else programs[program]
    text = compiled.as_text()
    # (Motif: 16 KV groups under its 80 heads, and one full layer among
    # the fixture's three; its window layers' expansion of ring + chunk,
    # under `attn:window`, is XLA's as it was.)
    groups, sublayers = (
        (16, 1) if family == "motif" else (conf["num_attention_heads"], 2)
    )
    table = int(program.rsplit("_", 1)[1])
    calls = _kernel_calls_under(text, "mla:expand")
    assert len(calls) == sublayers
    assert all("jit(latent_expand)" in line for line in calls)
    assert all(f"bf16[{groups},{table},128]" in line for line in calls)
    assert not [
        line for line in text.splitlines()
        if "mla:expand" in line and "attn:window" not in line
        and ("convolution" in line or " dot(" in line)
    ]
    assert not [
        line.strip()[:160] for line in text.splitlines()
        if re.search(rf"= \w+\[{groups},{table},128\]\S* broadcast\(", line)
    ]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= _PARENT_TEMP[family, program] + 2**20

