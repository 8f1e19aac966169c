"""ops/pallas/expert_combine.py, interpreted, against `sums.at[token].add`.

`models/moe.py _experts_on_pairs_here` sums its rows onto their tokens
by the kernel on a TPU and by one XLA scatter-add elsewhere. Here the
kernel is run interpreted on the CPU against that scatter-add, alone and
through `moe_ffn` told it is on a TPU. What only the chip's compiler
shows (the dynamic-sublane accumulator, VMEM) is in
tests/test_tpu_aot_compile.py.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import moe
from ray_tpu.ops.pallas import expert_combine, grouped_rows
from test_moe import _BOUND_CASES, _bound_layer

N = 48  # tokens


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Row blocks of 32 and a budget under which the accumulator is 128
    columns wide, so that a call here has several of both; the jitted
    call reads them when it is traced."""
    monkeypatch.setattr(expert_combine, "_ROW_BLOCK", 32)
    monkeypatch.setattr(expert_combine, "_ACC_VMEM_BYTES", N * 128 * 4)
    expert_combine._combine_rows.clear_cache()
    yield
    expert_combine._combine_rows.clear_cache()


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """`moe_ffn` takes the kernels' branch, with this kernel and the
    grouped matmuls' (tests/test_grouped_rows_kernel.py) interpreted."""

    def switch():
        monkeypatch.setattr(moe.chip, "platform", lambda: "tpu")
        monkeypatch.setattr(
            moe, "combine_rows",
            functools.partial(expert_combine.combine_rows, interpret=True),
        )
        monkeypatch.setattr(
            moe, "grouped_rows",
            functools.partial(grouped_rows.grouped_rows, interpret=True),
        )

    return switch


def _oracle(rows, token, gate, m, n):
    """What runs off the TPU: one scatter-add of the live rows."""
    weighted = rows[:m].astype(jnp.float32) * gate[:m, None]
    sums = jnp.zeros((n, rows.shape[1]), jnp.float32)
    return sums.at[token[:m]].add(weighted).astype(rows.dtype)


def _case(total, d, m, dtype, seed=0, tokens=None):
    """Rows past ``m`` hold NaN, with a token and a gate like any other:
    one of them added anywhere shows."""
    rng = np.random.default_rng(seed)
    rows = jnp.asarray(rng.normal(size=(total, d)), dtype).at[m:].set(jnp.nan)
    if tokens is None:
        tokens = rng.integers(0, N, total)
    gate = jnp.asarray(rng.uniform(0.1, 1.0, total), jnp.float32)
    return rows, jnp.asarray(tokens, jnp.int32), gate


def _tolerance(dtype):
    # float32: the order of the sums. bfloat16: the oracle rounds the
    # same float32 sum, so the two differ by one rounding at most.
    return 2e-6 if dtype == jnp.float32 else 2.0**-7


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("d", [256, 384, 640])
@pytest.mark.parametrize(
    "m", [0, 1, 31, 64, 77, 128], ids=lambda m: f"m{m}"
)
def test_kernel_sums_the_live_rows_onto_their_tokens(m, d, dtype):
    """128 rows in four blocks onto 48 tokens, so that a token occurs
    several times within a block and across blocks; ``m`` none, one, a
    block less a row, two whole blocks, a part of the third, every row;
    widths of two, three and five column tiles."""
    rows, token, gate = _case(128, d, m, dtype)
    assert expert_combine._column_tile(N, d) == 128
    got = expert_combine.combine_rows(
        rows.reshape(4, 32, d), token, gate, m, N, True
    )
    assert got.shape == (N, d) and got.dtype == dtype
    want = _oracle(rows, token, gate, m, N)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=_tolerance(dtype), rtol=_tolerance(dtype),
    )
    if m == 0:
        assert not np.asarray(got, np.float32).any()


def test_one_token_takes_every_row_in_order():
    """Every row onto token 7, with gates that grow by a factor of
    eight a row: float32 addition in any other order gives other bits
    (the oracle's scatter-add here adds in row order too)."""
    total, d = 64, 256
    rows, token, _ = _case(total, d, total, jnp.float32, tokens=[7] * total)
    gate = jnp.asarray(8.0 ** (np.arange(total) % 9), jnp.float32)
    got = np.asarray(expert_combine.combine_rows(
        rows.reshape(2, 32, d), token, gate, total, N, True
    ))
    want = np.zeros((d,), np.float32)
    for i in range(total):
        want = want + np.asarray(gate[i] * rows[i], np.float32)
    assert np.array_equal(got[7], want)
    assert not got[:7].any() and not got[8:].any()


@pytest.mark.parametrize("blocks, m", [(1, 43), (3, 43), (3, 120)])
def test_a_width_of_no_whole_lane_tile_and_blocks_of_no_whole_group(blocks, m):
    """Off the served shapes: 200 columns are one tile, and blocks of 50
    rows are each padded to the kernel's groups of 16 with rows that are
    never live."""
    rows, token, gate = _case(blocks * 50, 200, m, jnp.float32)
    got = expert_combine.combine_rows(
        rows.reshape(blocks, 50, 200), token, gate, m, N, True
    )
    np.testing.assert_allclose(
        got, _oracle(rows, token, gate, m, N), atol=2e-6, rtol=2e-6
    )


@pytest.mark.parametrize("case", list(_BOUND_CASES))
def test_kernel_through_moe_ffn_gives_the_scatter_adds_sums(case, as_on_a_tpu):
    """Every case of test_moe.py's row bound, by the kernel as by the
    scatter-add that tier 1 runs."""
    held, n_live, top_k, forced, d_model = _BOUND_CASES[case]
    cfg, _, mine, x = _bound_layer(held, top_k, forced, d_model)
    rows_live = jnp.arange(64) < n_live
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "_PAIR_BLOCK", 32)
        want, aux = moe.moe_ffn(x, mine, cfg, rows_live=rows_live)
        as_on_a_tpu()
        got, aux_k = moe.moe_ffn(x, mine, cfg, rows_live=rows_live)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    assert (aux_k["expert_load"] == aux["expert_load"]).all()
    assert (aux_k["sorted_rows"] == aux["sorted_rows"]).all()
    if n_live:
        assert np.abs(np.asarray(want)).max() > 0.1  # not a sum of nothing


def test_bfloat16_rows_are_not_rounded_before_the_sum(as_on_a_tpu):
    """Operands in bfloat16 as served: both paths weight the grouped
    matmul's bfloat16 rows by float32 gates and add in float32, so they
    differ by the last rounding alone."""
    cfg, _, mine, x = _bound_layer((4, 8), 2, False, 128)
    cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    x = x.astype(jnp.bfloat16)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "_PAIR_BLOCK", 32)
        want, _ = moe.moe_ffn(x, mine, cfg)
        as_on_a_tpu()
        got, _ = moe.moe_ffn(x, mine, cfg)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2.0**-7, rtol=2.0**-7,
    )


def test_differentiating_through_the_kernel_raises():
    rows, token, gate = _case(64, 256, 64, jnp.float32)

    def loss(r):
        return expert_combine.combine_rows(
            r.reshape(2, 32, 256), token, gate, 64, N, True
        ).sum()

    assert np.isfinite(float(loss(rows)))
    with pytest.raises(NotImplementedError, match="no backward pass"):
        jax.grad(loss)(rows)


@pytest.mark.parametrize("model", ["llama", "experts", "experts_as_on_a_tpu"])
def test_stats_name_the_combine_the_programs_were_compiled_with(
    model, monkeypatch
):
    """``stats()['moe_combine_kernel']``: true where the engine's
    programs are compiled for a TPU, false on the CPU, and no key for a
    model without expert layers; no option of its own."""
    from ray_tpu._private import chip
    from ray_tpu.llm.engine import LLMEngine, SamplingParams

    if model == "llama":
        from ray_tpu.models.llama import PRESETS, init_params

        cfg = PRESETS["tiny"]
    else:
        from ray_tpu.models.qwen3_next import QWEN3_NEXT_PRESETS, init_params

        cfg = QWEN3_NEXT_PRESETS["qwen3_next_tiny"]
    params = init_params(jax.random.key(0), cfg)
    if model == "experts_as_on_a_tpu":
        # What the engine asks when it is built; the attention kernels
        # are held off, so no program is compiled for a chip that is
        # not there.
        monkeypatch.setenv("RAY_TPU_PAGED_ATTN", "0")
        monkeypatch.setattr(chip, "platform", lambda: "tpu")
    eng = LLMEngine(cfg, max_batch=2, max_seq=64, page_size=16, params=params)
    if model != "experts_as_on_a_tpu":
        eng.generate([[1, 2, 3] * 6], SamplingParams(max_tokens=2))
    stats = eng.stats()
    if model == "llama":
        assert "moe_combine_kernel" not in stats
    else:
        assert stats["moe_combine_kernel"] is (model == "experts_as_on_a_tpu")
        assert stats["moe_combine_kernel"] == (stats["platform"] == "tpu")
