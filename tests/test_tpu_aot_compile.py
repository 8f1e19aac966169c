"""The main path's kernels, compiled for a described TPU at real widths.

No chip is needed: the TPU compiler is installed, and compiles for a
device that is described and not attached (on-chip-measurement guide,
section 2). This finds what interpret mode cannot — misaligned tiles, too
much fast memory, a kernel the compiler replaces — at about two seconds a
case and no chip time. Widths are Llama-3-8B's, as chip_smoke.py runs
them: 32 query / 8 KV heads of 128.

The three attention kernels are also compiled at a head of 256 with 16
query and 2 KV heads (Qwen3-Next's). The kernel that reads the touched
experts (ops/pallas/expert_rows.py), the sorted form's combine and its
grouped matmuls are compiled at the served widths, the decode step's
state kernel (ops/pallas/state_step.py) at the three served stacks, the
gated delta rule's chunk kernel at its served shape, Mamba-2's chunked
scan (ops/pallas/ssd_chunk.py) at Granite's and Nemotron's, Mamba-1's two
kernels (ops/pallas/selective_scan.py) at Phi-4-mini-flash's. The band kernel
(ops/pallas/window_attention.py) is compiled at 72 query heads over 8 KV
heads and the prefill and paged kernels at 48 over 8 (groups of 9 and
6). For GLM-5.3-Flash (models/glm5_next.py): the state kernel's third
body at its stack, the two expert kernels with the clamp, the KDA
mixer's kernel between its matmuls (ops/pallas/kda_chunk.py: the
convolution and gates, the chunked per-channel rule, the head norm) and
XLA's form of the rule, the exact top-k and the row gather of selected
cells (both XLA) at the served shapes.

The serving programs these kernels run in are compiled whole in
tests/test_tpu_aot_programs.py, which takes this file's shapes and
its two readers of a compiled text; the described device is
tests/conftest.py's `v5e`.
"""

import os
import re
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp

import jax
import jax.numpy as jnp
import pytest

H, HKV, DH = 32, 8, 128
# mistral7b-serve1's shapes (benchmarks/configs): 32 slots, 768 pages of
# 64 tokens and the dump page, max_seq 8448.
POOL_PAGES, PAGE, SLOTS, MAX_PAGES = 769, 64, 32, 132


def _flash(grad: bool):
    from ray_tpu.ops.pallas.flash_attention import flash_attention

    if not grad:
        return flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2))


def _paged(q, k_pool, v_pool, tables, positions):
    from ray_tpu.ops.pallas.paged_attention import paged_attention

    return paged_attention(
        q, k_pool, v_pool, tables, positions, n_kv_heads=HKV
    )


def _prefill_attn(q, k_pages, v_pages, start):
    from ray_tpu.ops.pallas.prefill_attention import prefill_attention

    return prefill_attention(q, k_pages, v_pages, start)


def _prefill_attn_args(on, queries: int, keys: int):
    # mistral7b-serve1's prefill: a chunk's (or a prompt's) queries over
    # the request's pages, gathered (or fresh) in the pool's cell layout.
    pages = jax.ShapeDtypeStruct(
        (keys // PAGE, HKV, PAGE, DH), jnp.bfloat16, sharding=on
    )
    return (
        jax.ShapeDtypeStruct((queries, H, DH), jnp.bfloat16, sharding=on),
        pages,
        pages,
        jax.ShapeDtypeStruct((), jnp.int32, sharding=on),
    )


def _flash_args(on):
    # chip_smoke's train phase: batch 2, seq 4096.
    def bf16(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=on)

    return bf16(2, 4096, H, DH), bf16(2, 4096, HKV, DH), bf16(2, 4096, HKV, DH)


def _paged_args(on, k: int, b=64, max_pages=32, pool_pages=64 * 32 + 1):
    # Batch 64, 64-token pages, 32 pages a sequence + the dump page.
    page = 64
    pool = jax.ShapeDtypeStruct(
        (pool_pages, HKV, page, DH), jnp.bfloat16, sharding=on
    )
    return (
        jax.ShapeDtypeStruct((b, k, H, DH), jnp.bfloat16, sharding=on),
        pool,
        pool,
        jax.ShapeDtypeStruct((b, max_pages), jnp.int32, sharding=on),
        jax.ShapeDtypeStruct((b,), jnp.int32, sharding=on),
    )


def _paged_serve_args(on, k: int):
    # mistral7b-serve1: 32 slots of 132 pages over six layers' pools
    # (769 pages each) in one flat view, as the decode program's layer
    # loop carries them.
    return _paged_args(
        on, k, b=SLOTS, max_pages=MAX_PAGES, pool_pages=6 * POOL_PAGES
    )


@pytest.mark.parametrize(
    "case",
    [
        "flash_fwd", "flash_fwd_bwd", "paged_k1", "paged_k4",
        "paged_serve_k1", "paged_serve_k5",
        "prefill_attn_2048_of_8192", "prefill_attn_2048_of_8448",
        "prefill_attn_1024", "prefill_attn_64",
    ],
)
def test_kernel_compiles_for_v5e_at_llama3_8b_widths(v5e, case):
    """A compile that succeeds is also the proof that the kernel's
    buffers fit the chip's VMEM."""
    fn, args = {
        "flash_fwd": (_flash(False), _flash_args(v5e)),
        "flash_fwd_bwd": (_flash(True), _flash_args(v5e)),
        "paged_k1": (_paged, _paged_args(v5e, 1)),
        "paged_k4": (_paged, _paged_args(v5e, 4)),
        "paged_serve_k1": (_paged, _paged_serve_args(v5e, 1)),
        "paged_serve_k5": (_paged, _paged_serve_args(v5e, 5)),
        # A chunk over the 8,192 bucket's table and over max_seq's (132
        # pages: no power of two), a whole prompt, one page.
        "prefill_attn_2048_of_8192": (
            _prefill_attn, _prefill_attn_args(v5e, 2048, 8192)
        ),
        "prefill_attn_2048_of_8448": (
            _prefill_attn, _prefill_attn_args(v5e, 2048, MAX_PAGES * PAGE)
        ),
        "prefill_attn_1024": (
            _prefill_attn, _prefill_attn_args(v5e, 1024, 1024)
        ),
        "prefill_attn_64": (_prefill_attn, _prefill_attn_args(v5e, 64, 64)),
    }[case]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _expert_rows_args(on, n: int, held: int, d: int, f: int, gated: bool):
    def on_chip(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on)

    return (
        on_chip((n, d)), on_chip((held, d, f)) if gated else None,
        on_chip((held, d, f)), on_chip((held, f, d)),
        on_chip((n, held), jnp.float32), on_chip((held,), jnp.int32),
        on_chip((), jnp.int32),
    )


@pytest.mark.parametrize(
    "case",
    {
        # Nemotron-3-Nano: 64 of 128 experts held 1920 wide, relu^2; a
        # decode step's rows and a prefill chunk's.
        "nemotron_32_rows": (32, 64, 2688, 1920, False),
        "nemotron_512_rows": (512, 64, 2688, 1920, False),
        # openPangu-Ultra-MoE: 16 of 256 held, SwiGLU.
        "pangu_32_rows": (32, 16, 7680, 2048, True),
    }.items(),
    ids=lambda case: case[0],
)
def test_expert_rows_kernel_compiles_for_v5e_at_served_widths(v5e, case):
    """The tile that `_width_tile` picks, the rows, the accumulator and
    a row block's temporaries fit the VMEM the call asks for, and the
    stacks are taken as they lie: nothing beside the arguments."""
    from ray_tpu.ops.pallas.expert_rows import experts_on_rows

    compiled = jax.jit(experts_on_rows).lower(
        *_expert_rows_args(v5e, *case[1])
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.parametrize(
    "case",
    {
        # A 2,048-token chunk's pair rows, rounded up to whole blocks of
        # the sorted order: granite-4.0-h-small and Qwen3-Next ten a
        # token, openPangu-Ultra-MoE eight.
        "granite_4096": (20480, 4096),
        "qwen3next_2048": (20480, 2048),
        "pangu_7680": (16384, 7680),
    }.items(),
    ids=lambda case: case[0],
)
def test_expert_combine_kernel_compiles_for_v5e_at_served_widths(v5e, case):
    """The accumulator `_column_tile` sizes, indexed by a token's row at
    run time, and the row blocks fit the VMEM the call asks for; the
    rows are taken as the grouped matmul left them (bfloat16, 7,680
    columns as they are) and nothing is made beside the arguments."""
    from ray_tpu.ops.pallas.expert_combine import combine_rows

    total, d = case[1]

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    compiled = jax.jit(partial(combine_rows, n=2048)).lower(
        on_chip((total // 1024, 1024, d), jnp.bfloat16),
        on_chip((total,), jnp.int32),
        on_chip((total,), jnp.float32), on_chip((), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.parametrize("call", ["up_projections", "down_projection"])
@pytest.mark.parametrize(
    "case",
    {
        # A 2,048-token chunk's pair rows, model width, expert width,
        # experts held and of the router: the four served families.
        "granite": (20480, 4096, 768, 36, 72),
        "qwen3next": (20480, 2048, 512, 256, 512),
        "laguna": (20480, 3072, 1024, 128, 256),
        "pangu": (16384, 7680, 2048, 16, 256),
    }.items(),
    ids=lambda case: case[0],
)
def test_grouped_rows_kernel_compiles_for_v5e_at_served_widths(
    v5e, case, call
):
    """The copies of a group's matrices out of the stacks in HBM, the
    row tiles at a run-time start (64, 48, 64 and 64 rows by
    `_tile_rows`) and the column passes `_column_tile` sizes (openPangu's
    up projections in four, its down projection in two, the others
    whole) lower for the chip inside the VMEM the call asks for; the
    stacks are read where they lie and nothing is made beside the
    arguments."""
    from ray_tpu.ops.pallas import grouped_rows

    name, (total, d, f, held, experts) = case
    mean = total // experts
    assert grouped_rows._tile_rows(mean, 16, 512) == {
        "granite": 64, "qwen3next": 48, "laguna": 64, "pangu": 64}[name]
    up = call == "up_projections"
    k, n = (d, f) if up else (f, d)
    assert n // grouped_rows._column_tile(k, n, 1 + up, 2) == (
        (4 if up else 2) if name == "pangu" else 1)

    def on_chip(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def experts_on(rows, sizes, *stacks):
        return grouped_rows.grouped_rows(
            rows, stacks, sizes, "swiglu" if up else None, mean
        )

    compiled = jax.jit(experts_on).lower(
        on_chip((total, k)), on_chip((held,), jnp.int32),
        *[on_chip((held, k, n))] * (1 + up),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _copies_of(text, (held, k, n)) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.parametrize(
    "case",
    {
        # The three served stacks: [layers, slots, heads, a, b], and the
        # heads that share a row of the slot's small operands.
        "granite_mamba": ("mamba", (9, 32, 128, 64, 128), 128),
        "nemotron_mamba": ("mamba", (7, 32, 64, 64, 128), 8),
        "qwen3next_gdn": ("gdn", (3, 32, 32, 128, 128), 2),
    }.items(),
    ids=lambda case: case[0],
)
def test_state_step_kernel_compiles_for_v5e_at_served_shapes(v5e, case):
    """The head tile `_head_tile` takes from the shapes, the rows turned
    into columns and the sums over lanes lower for the chip, inside the
    VMEM the call asks for; the donated stack is the result (aliased:
    nothing the size of a state is made beside the arguments)."""
    from ray_tpu.ops.pallas import state_step

    rule, stack, per_row = case[1]
    _, slots, heads, a, b = stack

    def on_chip(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    if rule == "mamba":
        step = state_step.mamba_state_step
        operands = (
            on_chip(slots, heads), on_chip(slots, heads, a),
            on_chip(slots, heads // per_row, b),
            on_chip(slots, heads // per_row, b),
        )
    else:
        step = state_step.gdn_state_step
        operands = (
            on_chip(slots, heads), on_chip(slots, heads),
            on_chip(slots, heads // per_row, a),
            on_chip(slots, heads // per_row, a), on_chip(slots, heads, b),
        )
    compiled = jax.jit(step, donate_argnums=0).lower(
        on_chip(*stack), on_chip(dtype=jnp.int32),
        on_chip(slots, dtype=jnp.int32), on_chip(1, dtype=jnp.int32),
        *operands,
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _copies_of(text, stack) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_gdn_chunk_kernel_compiles_for_v5e_at_the_served_shape(v5e):
    """qwen3next-80b-serve1's prefill chunk: 2,048 tokens, 16 key heads
    and 32 value heads of 128 x 128, rule chunks of 32. The masked
    inverse by halves, the columns and the row taken out under a mask and
    the product that contracts the tokens of a chunk lower for the chip,
    inside the VMEM the call asks for; none of XLA's `[n, Hk, r, C, 128]`
    intermediates is made beside the arguments (cols, rows and the
    results: 1.3 MB and what leaves)."""
    from ray_tpu.ops.pallas import gdn_chunk

    t, hk, rep, dk, dv = 2048, 16, 2, 128, 128

    def on_chip(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    compiled = jax.jit(partial(gdn_chunk.gdn_chunk_rule, chunk=32)).lower(
        on_chip(t, hk, dk), on_chip(t, hk, dk), on_chip(t, hk, rep, dv),
        on_chip(t, hk, rep), on_chip(t, hk, rep), on_chip(hk, rep, dk, dv),
        on_chip(dtype=jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert len(_kernel_calls_under(text, "")) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**20


# name: (tokens, heads, head dim, groups, state, chunk)
SSD_CASES = {
    "granite4hsmall_2048": (2048, 128, 64, 1, 128, 256),
    "nemotron3nano_512": (512, 64, 64, 8, 128, 128),
    "nemotron3nano_64": (64, 64, 64, 8, 128, 64),
}


@pytest.mark.parametrize("case", list(SSD_CASES))
def test_ssd_chunk_kernel_compiles_for_v5e_at_the_served_shapes(v5e, case):
    """Mamba-2's chunked scan (ops/pallas/ssd_chunk.py) at
    granite4hsmall-serve1's prefill chunk (128 heads of 64 in one group,
    chunks of 256) and at nemotron3nano-serve1's longest and shortest
    programs (64 heads in 8 groups, chunks of 128; 64 tokens are one
    chunk of 64): the running sum by a product with ones, the transpose
    of a grid step's per-token numbers, the gather along lanes that
    spreads two heads' columns over a tile and the product that
    contracts a chunk's tokens lower for the chip, inside the VMEM the
    call asks for; beside the arguments only `dt` head-major is made."""
    from ray_tpu.ops.pallas import ssd_chunk

    t, h, p, g, n, chunk = SSD_CASES[case]

    def on_chip(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    compiled = jax.jit(
        partial(ssd_chunk.ssd_chunk_rule, groups=g, chunk=chunk)
    ).lower(
        on_chip(t, h * p + 2 * g * n), on_chip(t, h), on_chip(h), on_chip(h),
        on_chip(h, p, n), on_chip(dtype=jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert len(_kernel_calls_under(text, "")) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**20


@pytest.mark.parametrize("case", ["chunk", "step"])
def test_selective_scan_kernels_compile_for_v5e_at_the_served_shapes(v5e, case):
    """Mamba-1's two kernels (ops/pallas/selective_scan.py) at
    phi4miniflash-serve1's shapes: a 2,048-token chunk of 5,120 channels
    and 16 state indices (x, dt and y bfloat16 in [T, 40, 128] views, B
    and C a block of scalars in SMEM, the state a VMEM scratch) inside
    the VMEM the call asks for; and the decode step's update of 32 slots
    in the nine layers' stack, the stack aliased to the result and
    copied nowhere."""
    from ray_tpu.ops.pallas import selective_scan

    t, width, n, rows, slots, layers = 2048, 5120, 16, 40, 32, 9

    def on_chip(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    bf16 = partial(on_chip, dtype=jnp.bfloat16)
    if case == "chunk":
        compiled = jax.jit(selective_scan.selective_scan_chunk).lower(
            bf16(t, width), bf16(t, width), bf16(t, n), bf16(t, n),
            on_chip(n, rows, 128), on_chip(width), on_chip(width),
            on_chip(n, rows, 128), on_chip(dtype=jnp.int32),
        ).compile()
        text = compiled.as_text()
        # Nothing of [T, N, d_inner] beside the kernel.
        assert f"[{t},{n}," not in text.replace(f"[{t},{n}]", "")
    else:
        stack = (layers, slots, n, rows, 128)
        compiled = jax.jit(
            selective_scan.selective_state_step, donate_argnums=0
        ).lower(
            on_chip(*stack), on_chip(dtype=jnp.int32),
            on_chip(slots, dtype=jnp.int32), on_chip(1, dtype=jnp.int32),
            on_chip(slots, width), on_chip(slots, width), on_chip(slots, n),
            on_chip(slots, n), on_chip(n, rows, 128),
        ).compile()
        text = compiled.as_text()
        assert _copies_of(text, stack) == []
    assert len(_kernel_calls_under(text, "")) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**20


def _copies_of(text: str, shape: tuple) -> list[str]:
    """Copies of an array of exactly ``shape`` anywhere in a compiled
    program: the donated state stack made a second time."""
    dims = ",".join(str(n) for n in shape)
    return [
        line.strip()[:160] for line in text.splitlines()
        if re.search(rf"= \w+\[{dims}\]\S* copy(-start)?\(", line)
    ]


def _kernel_calls_under(text: str, scope: str) -> list[str]:
    """A compiled program's Mosaic calls whose ``op_name`` lies under
    ``scope``, which is where the benchmark's reducers look for them."""
    return [
        line for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line and scope in line
    ]


# --------------------------------------------- a head of 256 (Qwen3-Next)
H256, HKV256, DH256 = 16, 2, 256


def _head256_case(case: str, on):
    """The kernel and its arguments at qwen3next-80b-serve1's shapes: 32
    slots, 8,321 pages of 64 tokens, tables of 260 pages."""
    def bf16(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=on)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=on)

    pool = bf16(8321, HKV256, PAGE, DH256)
    if case.startswith("prefill_attn"):
        from ray_tpu.ops.pallas.prefill_attention import prefill_attention

        queries, keys = {"prefill_attn_2048_of_16384": (2048, 16384),
                         "prefill_attn_4096": (4096, 4096)}[case]
        pages = bf16(keys // PAGE, HKV256, PAGE, DH256)
        return prefill_attention, (
            bf16(queries, H256, DH256), pages, pages, i32()
        )
    if case == "paged":
        from ray_tpu.ops.pallas.paged_attention import paged_attention

        return partial(paged_attention, n_kv_heads=HKV256), (
            bf16(SLOTS, 1, H256, DH256), pool, pool, i32(SLOTS, 260),
            i32(SLOTS),
        )
    from ray_tpu.ops.pallas.kv_cell_write import write_kv_cells

    return write_kv_cells, (
        pool, pool, bf16(SLOTS, HKV256, DH256), bf16(SLOTS, HKV256, DH256),
        i32(SLOTS), i32(SLOTS),
    )


@pytest.mark.parametrize(
    "case",
    ["prefill_attn_2048_of_16384", "prefill_attn_4096", "paged", "kv_write"],
)
def test_kernel_compiles_for_v5e_at_a_head_of_256(v5e, case):
    """16 query heads over 2 KV heads of 256: a group of 8 x 256 = 2,048
    lanes in the prefill kernel, ``(1, 2, 8, 256)`` query blocks in the
    paged kernel, 256-wide cells in the write (ROADMAP R8)."""
    fn, args = _head256_case(case, v5e)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ---------------- two kinds of attention layer, groups of 9 and 6 (Laguna)
HW, HF, WINDOW = 72, 48, 512


def _two_kinds_case(case: str, on):
    """The kernel and its arguments at laguna-s21-serve1's shapes: 16
    slots, 4,161 pages of 64 tokens, tables of 260 pages, a 2,048-token
    chunk, 8 KV heads of 128 under 72 query heads in a window layer and
    48 in a full one."""
    def bf16(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=on)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=on)

    if case == "band_72_of_8":
        from ray_tpu.ops.pallas.window_attention import window_attention

        keys = bf16(HKV, WINDOW + 2048, DH)
        return partial(window_attention, window=WINDOW), (
            bf16(2048, HW, DH), keys, keys, i32()
        )
    if case == "prefill_attn_48_of_8":
        from ray_tpu.ops.pallas.prefill_attention import prefill_attention

        pages = bf16(16384 // PAGE, HKV, PAGE, DH)
        return prefill_attention, (bf16(2048, HF, DH), pages, pages, i32())
    from ray_tpu.ops.pallas.paged_attention import paged_attention

    pool = bf16(2 * 4161, HKV, PAGE, DH)
    return partial(paged_attention, n_kv_heads=HKV), (
        bf16(16, 1, HF, DH), pool, pool, i32(16, 260), i32(16)
    )


@pytest.mark.parametrize(
    "case", ["band_72_of_8", "prefill_attn_48_of_8", "paged_48_of_8"]
)
def test_kernel_compiles_for_v5e_at_query_groups_of_9_and_6(v5e, case):
    """Nine query heads a KV head in the band kernel (a query block of
    ``[256, 9 x 128]`` lanes against the band's three key tiles) and six
    in the prefill and the paged kernel (``(1, 8, 6, 128)`` query blocks):
    neither group size had been lowered (ROADMAP R8)."""
    fn, args = _two_kinds_case(case, v5e)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# --------------------------- GLM-5.3-Flash: KDA, sparse latent, clamp
def test_kda_state_kernel_compiles_for_v5e_at_the_served_stack(v5e):
    """`kda_state_step` at glm53flash-serve1's stack ([4, 16, 64, 128,
    128]): the decay a key channel turned into a column lowers for the
    chip, and the donated stack is the result."""
    from ray_tpu.ops.pallas import state_step

    stack = (4, 16, 64, 128, 128)

    def on_chip(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    compiled = jax.jit(state_step.kda_state_step, donate_argnums=0).lower(
        on_chip(*stack), on_chip(dtype=jnp.int32),
        on_chip(16, dtype=jnp.int32), on_chip(1, dtype=jnp.int32),
        on_chip(16, 64, 128), on_chip(16, 64), on_chip(16, 64, 128),
        on_chip(16, 64, 128), on_chip(16, 64, 128),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _copies_of(text, stack) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.parametrize("call", ["every_row", "up_projections"])
def test_expert_kernels_compile_for_v5e_with_the_clamp(v5e, call):
    """`expert_rows` and `grouped_rows` at glm53flash-serve1's 36 held
    experts of 2,048 behind a model width of 4,096 with ``limit`` 10: the
    clamp is two more vector operations on the float32 products, inside
    the VMEM the calls ask for."""
    from ray_tpu.ops.pallas import grouped_rows
    from ray_tpu.ops.pallas.expert_rows import experts_on_rows

    d, f, held = 4096, 2048, 36

    def on_chip(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    if call == "every_row":
        compiled = jax.jit(partial(experts_on_rows, limit=10.0)).lower(
            *_expert_rows_args(v5e, 16, held, d, f, True)
        ).compile()
    else:
        total = 2048 * 8

        def experts_on(rows, sizes, *stacks):
            return grouped_rows.grouped_rows(
                rows, stacks, sizes, "swiglu", total // 288, limit=10.0
            )

        compiled = jax.jit(experts_on).lower(
            on_chip((total, d)), on_chip((held,), jnp.int32),
            on_chip((held, d, f)), on_chip((held, d, f)),
        ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _copies_of(text, (held, d, f)) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_kda_chunk_kernel_compiles_for_v5e_at_the_served_shape(v5e):
    """glm53flash-serve1's prefill chunk: 2,048 tokens, 64 heads of 128
    x 128, rule chunks of 32 in sub-chunks of 16, from the matmuls'
    float32 results to the gated output in bfloat16 (PR 62). The three
    views of the in-projection's result, the taps' rolls down the
    sublanes with the eight rows before a block, silu, the unit lengths
    and both sigmoids; the running sum down the rows, a sub-chunk's
    middle row spread over it, the two score products under their masks,
    the masked inverse by halves and the state transposed in and out;
    the head norm and the cast lower for the chip, inside the VMEM the
    call asks for; nothing is made beside the arguments (the eight rows
    before the sequence and the decay's two rows a channel are
    kilobytes)."""
    from ray_tpu.ops.pallas import kda_chunk

    t, h, dk = 2048, 64, 128

    def on_chip(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    compiled = jax.jit(partial(
        kda_chunk.kda_chunk_rule, chunk=32, sub=16, lower=-5.0, l2_eps=1e-6,
        norm_eps=1e-5, dtype=jnp.bfloat16,
    )).lower(
        on_chip(t, 3 * h * dk), on_chip(3, 3 * h * dk, dtype=jnp.bfloat16),
        on_chip(4, 3 * h * dk), on_chip(t, h * dk), on_chip(h, dk),
        on_chip(h), on_chip(t, h), on_chip(t, h * dk), on_chip(dk),
        on_chip(h, dk, dk), on_chip(dtype=jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert len(_kernel_calls_under(text, "")) == 1
    assert f"bf16[{t},{h * dk}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**20


@pytest.mark.parametrize("tokens", [2048, 16])
def test_residual_stream_kernels_compile_for_v5e_at_the_served_shapes(
    v5e, tokens
):
    """glm53flash-serve1's four residual streams of 4,096 in bfloat16,
    a prefill chunk's 2,048 tokens and a decode step's 16 slots (one
    block that hangs over the arrays' end), flat as a program carries
    them between two sublayers. `mhc_mix`: ``P`` cut into its three
    bf16 parts on the bits in the first step, the product against the
    parts' rows, the lane rolls, the transposes either side of
    Sinkhorn's sublane rolls and exact divisions, the [tokens, 16] and
    [tokens, 4] float32 results stored under lane masks. `mhc_spread`
    writes over the streams' buffer. Each is ONE Mosaic call and nothing
    is made beside the arguments: ``P`` goes in as it is held
    (transposed: a bitcast of the layout the chip keeps it in), no
    float32 copy of the streams."""
    from ray_tpu.ops.pallas import mhc_streams

    n, d = 4, 4096

    def on_chip(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def flat_mix(flat, *p):
        return mhc_streams.mhc_mix(
            flat.reshape(tokens, n, d), *p, iters=20, eps=1e-6
        )

    mix = jax.jit(flat_mix).lower(
        on_chip(tokens, n * d, dtype=jnp.bfloat16), on_chip(n * d, 24),
        on_chip(3), on_chip(n), on_chip(n), on_chip(n, n),
    ).compile()
    text = mix.as_text()
    assert len(_kernel_calls_under(text, "")) == 1
    assert f"bf16[{tokens},{d}]" in text and f"f32[{tokens},16]" in text
    assert not re.search(rf"f32\[{tokens},({n},{d}|{n * d})\]", text)
    assert mix.memory_analysis().temp_size_in_bytes < 2**20

    def flat_spread(flat, out, h_res, h_post):
        return mhc_streams.mhc_spread(
            flat.reshape(tokens, n, d), out, h_res, h_post
        ).reshape(flat.shape)

    spread = jax.jit(flat_spread, donate_argnums=0).lower(
        on_chip(tokens, n * d, dtype=jnp.bfloat16),
        on_chip(tokens, d, dtype=jnp.bfloat16), on_chip(tokens, n, n),
        on_chip(tokens, n),
    ).compile()
    text = spread.as_text()
    assert len(_kernel_calls_under(text, "")) == 1
    assert _copies_of(text, (tokens, n * d)) == []
    assert not re.search(rf"f32\[{tokens},({n},{d}|{n * d})\]", text)
    assert spread.memory_analysis().temp_size_in_bytes < 2**20


def test_the_sub_chunked_rule_and_the_selection_lower_for_v5e(v5e):
    """What no kernel computes in a program, at the served shapes: the
    exact top-k of 512 of 16,384 blocks for 2,048 queries and the gather
    of a query block's selected cells, a row a position, from a 64k
    context's cells; and XLA's form of KDA's chunked rule over 2,048
    tokens of 64 heads (chunk 64 in sub-chunks of 16: the decays inside
    the products, the triangular inverse, the scan over chunks), which
    since PR 60 no program on a TPU holds and `scripts/glm5_next_layer.py`
    still times there beside the kernel."""
    from ray_tpu.models import glm5_next

    def on_chip(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    rule = jax.jit(
        partial(glm5_next._kda_rule, size=64, sub=16)
    ).lower(
        on_chip(2048, 64, 128), on_chip(2048, 64, 128),
        on_chip(2048, 64, 128), on_chip(2048, 64), on_chip(2048, 64, 128),
        on_chip(64, 128, 128),
    ).compile()
    assert rule.memory_analysis().temp_size_in_bytes < 3 * 2**30
    cfg = glm5_next.Glm5NextConfig()
    select = jax.jit(partial(glm5_next._select, cfg=cfg)).lower(
        on_chip(2048, 16384), on_chip(2048, dtype=jnp.int32)
    ).compile()
    assert "s32[2048,512]" in select.as_text()
    def selected_cells(context, ids):
        rows, hidden = glm5_next._block_rows(ids, cfg.index_kpool)
        return jnp.take(context, rows, axis=0, mode="clip"), hidden

    gather = jax.jit(selected_cells).lower(
        on_chip(65536, 512, dtype=jnp.bfloat16),
        on_chip(128, 512, dtype=jnp.int32),
    ).compile()
    assert "bf16[128,2048,512]" in gather.as_text()
    assert _copies_of(gather.as_text(), (65536, 512)) == []


# ------------- Motif-3-Beta: grouped latent attention, PolyNorm experts
def _motif_case(case: str, on):
    """The kernel and its arguments at motif3beta-serve1's shapes: 16
    slots, 16,449 pages of 64 cells 640 wide, tables of 1,028 pages, a
    2,048-token chunk, 80 query heads (192 | 128) over 16 expanded KV
    groups, a window of 128, 48 held experts of 1,280 behind a model
    width of 4,096."""
    from ray_tpu.ops.pallas import grouped_rows
    from ray_tpu.ops.pallas.expert_rows import experts_on_rows
    from ray_tpu.ops.pallas.latent_attention import (
        latent_paged_attention,
        latent_prefill_attention,
    )
    from ray_tpu.ops.pallas.window_attention import window_attention

    def arr(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=on)

    i32 = partial(arr, dtype=jnp.int32)
    f32 = partial(arr, dtype=jnp.float32)
    d, f, held, heads, groups = 4096, 1280, 48, 80, 16
    if case == "grouped_prefill_80_of_16":
        keys = 65536
        return partial(latent_prefill_attention, scale=192**-0.5), (
            arr(heads, 2048, 128), arr(heads, 2048, 128),
            arr(groups, keys, 128), arr(keys, 128), arr(groups, keys, 128),
            i32(),
        )
    if case == "paged_80_rows":
        return partial(latent_paged_attention, v_width=512, scale=192**-0.5), (
            arr(16, 1, heads, 640), arr(16449, 64, 640), i32(16, 1028), i32(16)
        )
    if case == "band_192_over_128":
        return partial(window_attention, window=128, scale=192**-0.5), (
            arr(2048, heads, 256), arr(groups, 128 + 2048, 256),
            arr(groups, 128 + 2048, 128), i32(),
        )
    if case == "every_row_polynorm":
        def every_row(*args):
            return experts_on_rows(*args[:-1], poly=args[-1], eps=1e-5)

        return every_row, (
            *_expert_rows_args(on, 16, held, d, f, True), f32(held, 4)
        )
    total = 2048 * 8

    def experts_on(rows, sizes, poly, *stacks):
        return grouped_rows.grouped_rows(
            rows, stacks, sizes, "polynorm", total // 384, poly=poly, eps=1e-5
        )

    return experts_on, (
        arr(total, d), i32(held), f32(held, 4), arr(held, d, f),
        arr(held, d, f),
    )


@pytest.mark.parametrize(
    "case",
    ["grouped_prefill_80_of_16", "paged_80_rows", "band_192_over_128",
     "every_row_polynorm", "up_projections_polynorm"],
)
def test_kernel_compiles_for_v5e_at_motif3betas_shapes(v5e, case):
    """What motif3beta-serve1 changed in four kernels lowers for the
    chip: five query heads reading one expanded group's blocks in the
    latent prefill kernel, 80 rows a slot in the latent decode kernel, a
    band whose values are narrower than its keys (five heads of 256 a
    query block against three key tiles), and a PolyNorm expert's whole
    width of 1,280 in one step of both expert kernels (63 MB of weight
    buffers in the every-row form, over the budget the other kinds tile
    under), its numbers scalars in SMEM; no expert stack is copied."""
    fn, args = _motif_case(case, v5e)
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _copies_of(text, (48, 4096, 1280)) == []
    assert _copies_of(text, (16449, 64, 640)) == []
