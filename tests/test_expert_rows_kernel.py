"""ops/pallas/expert_rows.py, interpreted, against the einsum form.

`models/moe.py _experts_on_every_row` runs the kernel on a TPU and two
batched einsums elsewhere. Here the same function is run both ways on
the CPU: as it is (the einsum form, the oracle), and told it is on a TPU
with the kernel interpreted. What only the chip's compiler shows (tiles,
VMEM, copies of a stack) is in tests/test_tpu_aot_compile.py.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import moe
from ray_tpu.ops.pallas import expert_rows

D, F, EXPERTS, TOP_K = 128, 256, 8, 2
# float32 throughout: what is left is the order of the sums (the latent
# kernel's tests hold theirs to the same).
TOL = 2e-5


@pytest.fixture(autouse=True)
def two_width_tiles(monkeypatch):
    """A budget under which F is two tiles of 128 lanes, for two and for
    three matrices; the jitted call reads it when it is traced."""
    monkeypatch.setattr(expert_rows, "_WEIGHT_VMEM_BYTES", 2 * 2 * D * 128 * 4)
    assert expert_rows._width_tile(D, F, 2, 4) == 128
    assert expert_rows._width_tile(D, F, 3, 4) == 128
    expert_rows._experts_on_rows.clear_cache()
    yield
    expert_rows._experts_on_rows.clear_cache()


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """`moe_ffn` takes the kernel's branch, with the kernel interpreted."""

    def switch():
        monkeypatch.setattr(moe.chip, "platform", lambda: "tpu")
        monkeypatch.setattr(
            moe, "experts_on_rows",
            functools.partial(expert_rows.experts_on_rows, interpret=True),
        )

    return switch


def _cfg(kind, held=None, dtype=jnp.float32):
    return dataclasses.replace(
        moe.MOE_PRESETS["moe_tiny"], d_model=D, d_ff=F, num_experts=EXPERTS,
        top_k=TOP_K, expert_kind=kind, experts_held=held, dtype=dtype,
        dense_expert_rows=10**6, router_kind="sigmoid", norm_topk_prob=True,
    )


def _layer(cfg, seed=0):
    """One layer's expert weights as a serving model holds them: the
    router over all experts, the stacks over the held ones."""
    keys = jax.random.split(jax.random.key(seed), 4)
    held = (cfg.experts_held or (0, cfg.num_experts))[1]
    normal = lambda k, shape, fan: (  # noqa: E731
        jax.random.normal(k, shape, jnp.float32) * fan**-0.5
    ).astype(cfg.dtype)
    return {
        "router": jax.random.normal(keys[0], (D, cfg.num_experts)),
        "router_bias": jnp.zeros((cfg.num_experts,)),
        "w_gate": normal(keys[1], (held, D, F), D),
        "w_up": normal(keys[2], (held, D, F), D),
        "w_down": normal(keys[3], (held, F, D), F),
    }


def _rows(n, seed=1, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(seed), (1, n, D)).astype(dtype)


@pytest.mark.parametrize("n", [1, 32, 37])
@pytest.mark.parametrize("live", [None, "dead_rows"])
@pytest.mark.parametrize("held", [None, (2, 4)])
@pytest.mark.parametrize("kind", ["relu2", "swiglu"])
def test_kernel_matches_einsum_form_through_moe_ffn(
    as_on_a_tpu, kind, held, live, n
):
    cfg = _cfg(kind, held)
    p, x = _layer(cfg), _rows(n)
    rows_live = None if live is None else jnp.arange(n) % 3 != 1
    want, aux = moe.moe_ffn(x, p, cfg, rows_live=rows_live)
    as_on_a_tpu()
    got, aux_k = moe.moe_ffn(x, p, cfg, rows_live=rows_live)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    # The router's record is computed as before.
    assert (aux_k["expert_load"] == aux["expert_load"]).all()
    assert (aux_k["routes"] == aux["routes"]).all()
    if live is None and n > 1:
        assert np.abs(np.asarray(want)).max() > 0.1  # not a sum of nothing


def _direct(kind, n, chosen_from, held=None, seed=3):
    """`_experts_on_every_row`'s arguments with every route drawn from
    ``chosen_from`` (ids among all the model's experts)."""
    cfg = _cfg(kind, held)
    rng = np.random.default_rng(seed)
    routes = jnp.asarray(
        np.stack([
            rng.choice(chosen_from, TOP_K, replace=False) for _ in range(n)
        ]), jnp.int32,
    )
    gates = jnp.asarray(rng.uniform(0.2, 1.0, (n, TOP_K)), jnp.float32)
    first, count = held or (0, EXPERTS)
    here = None
    if held is not None:
        here = (routes >= first) & (routes < first + count)
    return _rows(n)[0], _layer(cfg), cfg, routes, gates, here


@pytest.mark.parametrize(
    "touched, chosen_from, held",
    [
        ("none", [0, 1, 6, 7], (2, 4)),  # every pair's expert is elsewhere
        ("one", [5, 7], (4, 2)),  # ... but expert 5, the share's second
        ("some", [1, 4, 6], None),
        ("all", list(range(EXPERTS)), None),
    ],
)
@pytest.mark.parametrize("kind", ["relu2", "swiglu"])
def test_kernel_by_how_many_experts_got_a_row(
    as_on_a_tpu, kind, touched, chosen_from, held
):
    args = _direct(kind, 32, chosen_from, held)
    want, load = moe._experts_on_every_row(*args)
    n_touched = {"none": 0, "one": 1, "some": 3, "all": EXPERTS}[touched]
    assert int((load > 0).sum()) == n_touched
    as_on_a_tpu()
    got, load_k = moe._experts_on_every_row(*args)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert (load_k == load).all()
    if touched == "none":
        assert not np.asarray(got).any()


@pytest.mark.parametrize("kind", ["relu2", "swiglu"])
def test_an_untouched_experts_weights_are_not_multiplied(as_on_a_tpu, kind):
    """The skip is real, not a product with a zero gate: NaN in every
    weight of every expert that got no row leaves the result as it was
    (the einsum form, which reads them all, gives NaN)."""
    x, p, cfg, routes, gates, here = _direct(kind, 32, [1, 4, 6])
    untouched = ~jnp.isin(jnp.arange(EXPERTS), jnp.asarray([1, 4, 6]))
    poisoned = {
        name: jnp.where(untouched[:, None, None], jnp.nan, w)
        if name.startswith("w_") else w
        for name, w in p.items()
    }
    want, _ = moe._experts_on_every_row(x, p, cfg, routes, gates, here)
    oracle, _ = moe._experts_on_every_row(x, poisoned, cfg, routes, gates, here)
    assert np.isnan(np.asarray(oracle)).all()
    as_on_a_tpu()
    got, _ = moe._experts_on_every_row(x, poisoned, cfg, routes, gates, here)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_more_rows_than_a_row_block_in_bfloat16(as_on_a_tpu):
    """300 rows: two row blocks inside a step, the second partial.
    Operands in bfloat16 as served; the kernel keeps an expert's output
    in float32 up to the weighted sum where the einsum form rounds it,
    so the two differ by bfloat16's rounding of a value of magnitude ~4."""
    cfg = _cfg("swiglu", dtype=jnp.bfloat16)
    p, x = _layer(cfg), _rows(300, dtype=jnp.bfloat16)
    assert 300 > expert_rows._ROW_BLOCK
    want, _ = moe.moe_ffn(x, p, cfg)
    as_on_a_tpu()
    got, _ = moe.moe_ffn(x, p, cfg)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2 * 2.0**-7, rtol=2.0**-7,
    )


@pytest.mark.parametrize(
    "load, ids, count",
    [
        ([0, 0, 0, 0], [0, 0, 0, 0], 0),
        ([0, 3, 0, 1], [1, 3, 3, 3], 2),
        ([2, 0, 0, 0], [0, 0, 0, 0], 1),
        ([1, 1, 1, 1], [0, 1, 2, 3], 4),
    ],
)
def test_the_work_list_packs_touched_experts_first(load, ids, count):
    """Past the count the last touched id repeats: those grid steps name
    the block that is already there, and nothing is copied."""
    got_ids, got_count = moe.touched_first(jnp.asarray(load, jnp.int32))
    assert got_ids.dtype == got_count.dtype == jnp.int32
    assert got_ids.tolist() == ids and int(got_count) == count


def test_differentiating_through_the_kernel_raises():
    x, p, *_ = _direct("relu2", 8, [1, 4, 6])
    weight = jnp.zeros((8, EXPERTS)).at[:, 1].set(1.0)
    ids, count = moe.touched_first(jnp.asarray([0, 8] + [0] * 6, jnp.int32))

    def loss(rows):
        return expert_rows.experts_on_rows(
            rows, None, p["w_up"], p["w_down"], weight, ids, count, True
        ).sum()

    assert np.isfinite(float(loss(x)))
    with pytest.raises(NotImplementedError, match="no backward pass"):
        jax.grad(loss)(x)
