"""ops/pallas/grouped_rows.py, interpreted, against `jax.lax.ragged_dot`.

`models/moe.py _experts_on_pairs_here` applies the experts to the sorted
rows by the kernel on a TPU and by `ragged_dot`, a block of the order at
a time, elsewhere. Here the kernel is run interpreted on the CPU against
`ragged_dot`, alone and through `moe_ffn` told it is on a TPU. What only
the chip's compiler shows (the copies from HBM, VMEM, the dynamic row
slices) is in tests/test_tpu_aot_compile.py.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import moe
from ray_tpu.ops.pallas import grouped_rows
from test_expert_combine_kernel import as_on_a_tpu  # noqa: F401: a fixture
from test_moe import _BOUND_CASES, _bound_layer

BLOCK = 32  # rows of a streamed block here


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Row blocks of 32, so that a call here has several and a group can
    span two; the jitted call reads the constant when it is traced."""
    monkeypatch.setattr(grouped_rows, "_ROW_BLOCK", BLOCK)
    grouped_rows._grouped_rows.clear_cache()
    yield
    grouped_rows._grouped_rows.clear_cache()


def _operands(total, k, n, sizes, dtype, n_w=1, seed=0):
    """Rows at and past the groups' sum hold NaN: one of them multiplied
    into anything shows."""
    rng = np.random.default_rng(seed)
    sizes = jnp.asarray(sizes, jnp.int32)
    m = int(sizes.sum())
    rows = jnp.asarray(rng.normal(size=(total, k)), dtype).at[m:].set(jnp.nan)
    weights = [
        jnp.asarray(rng.normal(size=(len(sizes), k, n)) * k**-0.5, dtype)
        for _ in range(n_w)
    ]
    return rows, weights, sizes, m


def _oracle(rows, weights, sizes, m, act):
    """What runs off the TPU: `ragged_dot` over the live rows, float32
    sums, the activation on them, one rounding."""
    product = lambda w: jax.lax.ragged_dot(  # noqa: E731
        rows[:m], w, sizes, preferred_element_type=jnp.float32
    )
    out = product(weights[-1])
    if act == "swiglu":
        out = jax.nn.silu(product(weights[0])) * out
    elif act == "relu2":
        out = jnp.square(jax.nn.relu(out))
    return out.astype(rows.dtype)


def _tolerance(dtype):
    # float32: the order of a product's sums. bfloat16: both round the
    # same float32 value, bar that order.
    return 1e-5 if dtype == jnp.float32 else 2.0**-7


def _check(total, k, n, sizes, dtype, act=None, group_rows=16, seed=0):
    n_w = 2 if act == "swiglu" else 1
    rows, weights, sizes, m = _operands(total, k, n, sizes, dtype, n_w, seed)
    got = grouped_rows.grouped_rows(
        rows, weights, sizes, act, group_rows, True
    )
    assert got.shape == (total, n) and got.dtype == dtype
    want = _oracle(rows, weights, sizes, m, act)
    np.testing.assert_allclose(
        np.asarray(got[:m], np.float32), np.asarray(want, np.float32),
        atol=_tolerance(dtype), rtol=_tolerance(dtype),
    )
    if m:
        assert np.abs(np.asarray(want, np.float32)).max() > 0.1


# 128 rows in four blocks of 32, tiles of 16 rows.
_GROUPS = {
    "empty_groups_between": [0, 20, 0, 0, 44, 0, 64, 0],
    "a_group_ends_inside_a_tile": [21, 107],
    "a_tile_holds_three_groups": [18, 3, 4, 5, 98],
    "a_group_spans_two_blocks": [25, 30, 73],
    "a_group_spans_every_block": [128],
    "fewer_rows_than_the_blocks": [7, 0, 30, 11],
    "fewer_rows_than_one_block": [3, 2],
    "one_row_a_group": [1] * 40,
    "no_row_at_all": [0, 0, 0],
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(_GROUPS))
def test_kernel_multiplies_each_group_by_its_own_matrix(case, dtype):
    _check(128, 128, 256, _GROUPS[case], dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("act", ["swiglu", "relu2"])
def test_kernel_applies_the_experts_activation_to_float32_products(act, dtype):
    _check(128, 128, 256, [18, 3, 40, 0, 50], dtype, act=act)


@pytest.mark.parametrize("group_rows", [1, 16, 40, 80, 284])
def test_the_tile_follows_the_groups_mean_size(group_rows):
    """Tiles of 16, 16, 48, 64 and 64 rows (bfloat16 packs 16) over the
    same 320 rows in ONE block, the case the compiler's kernel refused
    at 256-row tiles ("expecting m % mt == 0, got: 320 % 256")."""
    pack = grouped_rows._packing(jnp.bfloat16)
    want = {1: 16, 16: 16, 40: 48, 80: 64, 284: 64}[group_rows]
    assert grouped_rows._tile_rows(group_rows, pack, 320) == want
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(grouped_rows, "_ROW_BLOCK", 512)
        _check(320, 128, 128, [100, 0, 90, 7, 120], jnp.bfloat16,
               group_rows=group_rows)


@pytest.mark.parametrize(
    "family, d, f, held",
    [("granite", 512, 96, 9), ("qwen3next", 256, 64, 64),
     ("laguna", 384, 128, 32), ("pangu", 960, 256, 4)],
)
def test_the_four_families_width_ratios_scaled_down(family, d, f, held):
    """An eighth of each served width, the gated up projection and the
    down projection, under a budget that takes the widest in column
    passes as openPangu's are taken."""
    rng = np.random.default_rng(3)
    sizes = rng.multinomial(200, np.ones(held) / held)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(
            grouped_rows, "_WEIGHT_VMEM_BYTES", 2 * 2 * 512 * 128 * 2
        )
        passes = f // grouped_rows._column_tile(d, f, 2, 2)
        assert passes == (2 if family == "pangu" else 1)
        _check(256, d, f, sizes, jnp.bfloat16, act="swiglu",
               group_rows=200 // held)
        _check(256, f, d, sizes, jnp.bfloat16, group_rows=200 // held)


def test_a_column_pass_streams_every_group_again():
    """Three passes of 128 columns: each starts its own stream of the
    groups' weights."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(grouped_rows, "_WEIGHT_VMEM_BYTES", 2 * 128 * 128 * 4)
        assert grouped_rows._column_tile(128, 384, 1, 4) == 128
        _check(128, 128, 384, [0, 50, 0, 3, 60], jnp.float32)


@pytest.mark.parametrize("case", list(_GROUPS))
def test_three_buffers_stream_the_same_groups(case):
    """Two groups' weights on their way while a third is multiplied."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(grouped_rows, "_BUFFERS", 3)
        _check(128, 128, 256, _GROUPS[case], jnp.bfloat16, act="swiglu")


def test_rows_of_no_whole_packed_tile_are_padded():
    _check(6, 16, 8, [1, 2, 3], jnp.float32, group_rows=2)
    _check(50, 128, 128, [20, 23], jnp.bfloat16, group_rows=8)


@pytest.mark.parametrize("case", list(_BOUND_CASES))
def test_kernel_through_moe_ffn_gives_the_grouped_matmuls_sums(
    case, as_on_a_tpu
):
    """Every case of test_moe.py's row bound, by the kernels as by the
    `ragged_dot` and the scatter-add that tier 1 runs: every pair
    computed here, the dead ones adding nothing."""
    held, n_live, top_k, forced, d_model = _BOUND_CASES[case]
    cfg, _, mine, x = _bound_layer(held, top_k, forced, d_model)
    rows_live = jnp.arange(64) < n_live
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "_PAIR_BLOCK", 32)
        want, aux = moe.moe_ffn(x, mine, cfg, rows_live=rows_live)
        as_on_a_tpu()
        got, aux_k = moe.moe_ffn(x, mine, cfg, rows_live=rows_live)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=1e-5)
    assert (aux_k["expert_load"] == aux["expert_load"]).all()
    assert (aux_k["sorted_rows"] == aux["sorted_rows"]).all()
    if n_live:
        assert np.abs(np.asarray(want)).max() > 0.1  # not a sum of nothing


@pytest.mark.parametrize("kind", ["swiglu", "relu2"])
def test_bfloat16_experts_through_moe_ffn(kind, as_on_a_tpu):
    """Operands in bfloat16 as served, both expert kinds: the kernel
    keeps the two products in float32 up to the activation where
    `ragged_dot` rounds each first, so the two differ by those
    roundings."""
    cfg, _, mine, x = _bound_layer((4, 8), 2, False, 128)
    cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16, expert_kind=kind)
    x = x.astype(jnp.bfloat16)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "_PAIR_BLOCK", 32)
        want, _ = moe.moe_ffn(x, mine, cfg)
        as_on_a_tpu()
        got, _ = moe.moe_ffn(x, mine, cfg)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2.0**-6, rtol=2.0**-6,
    )


def test_differentiating_through_the_kernel_raises():
    rows, weights, sizes, _ = _operands(64, 128, 128, [30, 34], jnp.float32)

    def loss(r):
        return grouped_rows.grouped_rows(
            r, weights, sizes, None, 16, True
        ).sum()

    assert np.isfinite(float(loss(rows)))
    with pytest.raises(NotImplementedError, match="no backward pass"):
        jax.grad(loss)(rows)
