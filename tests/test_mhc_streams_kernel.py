"""ops/pallas/mhc_streams.py interpreted, against what it replaces on a
TPU: `models/mhc.py mhc_mix` / `mhc_spread` in XLA's own
operations (the bodies those functions keep off the TPU).

The two calls are made alone on streams of tiny and of the served width,
in bf16 and float32, over token counts of one tile, several, no multiple
of the tile, fewer than a tile and one, with the leading axes the
programs pass (``[B, S, n, d]`` a prefill chunk, ``[B, n, d]``), and
with a scale that puts large entries through the ``exp`` before
Sinkhorn; and through the model's two functions as on a TPU, either
side of the row count that chooses the path. The three ``H`` must be
the oracle's to float32 rounding (`H_TOL`; the largest distance found
is 1.3e-6, at the served width), ``Hres`` doubly stochastic as
tests/test_glm5_next.py asks of `sinkhorn`, and ``h`` and the written
streams within one unit of the streams' dtype. Compiled for a described
v5e at the served shapes in tests/test_tpu_aot_compile.py and inside
GLM-5.3-Flash's programs in tests/test_tpu_aot_programs.py.
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import glm5_next, mhc
from ray_tpu.ops.pallas import mhc_streams

TILE = mhc_streams._TILE
H_TOL = 4e-6  # of numbers in [0, 2]: a few float32 roundings over 20 rounds


@dataclasses.dataclass(frozen=True)
class Case:
    lead: tuple  # the streams' axes before [n, d]
    d: int = 64
    dtype: str = "bfloat16"
    a_res: float = 1.0
    # "calls": the two kernels alone. "model": `mhc.mhc_mix` /
    # `mhc_spread` as on a TPU, which take them from `_MHC_KERNEL_ROWS` on.
    through: str = "calls"

    @property
    def id(self):
        lead = "x".join(map(str, self.lead))
        extra = f"-a_res{self.a_res:g}" if self.a_res != 1.0 else ""
        return f"{self.through}-{lead}-d{self.d}-{self.dtype}{extra}"


CASES = [
    *(Case((t,), dtype=dtype)
      for dtype in ("bfloat16", "float32")
      for t in (TILE, 3 * TILE, 2 * TILE + 44, 40, 1)),
    Case((TILE,), d=4096),
    Case((TILE + 16,), d=4096),
    Case((24,), d=4096, dtype="float32"),
    Case((TILE,), a_res=6.0),
    Case((24,), a_res=6.0, dtype="float32"),
    Case((1, TILE + 70)),  # a prefill chunk's [B, S, n, d]
    Case((2, 70)),
    Case((16,), d=4096),  # a decode step's [B, n, d]
    Case((1, mhc._MHC_KERNEL_ROWS), through="model"),
    Case((mhc._MHC_KERNEL_ROWS - 1, 1), through="model"),
]


def _one_unit(got, want) -> None:
    """``got`` within one bf16 unit in the last place of ``want`` (float32
    streams: within 32 of float32's), and the float32 rounding of a sum
    of terms of size ~8 where they cancel."""
    mantissa = 7 if want.dtype == jnp.bfloat16 else 18
    got = np.asarray(got.astype(jnp.float32), np.float64)
    want = np.asarray(want.astype(jnp.float32), np.float64)
    size = np.maximum(np.abs(got), np.abs(want))
    unit = 2.0 ** (np.floor(np.log2(np.maximum(size, 1e-30))) - mantissa)
    assert (np.abs(got - want) <= unit + H_TOL).all()


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.id)
def test_the_kernels_are_xlas_form(case, monkeypatch):
    dtype = jnp.dtype(case.dtype)
    cfg = dataclasses.replace(
        glm5_next.GLM5_NEXT_PRESETS["glm5_next_tiny"], d_model=case.d,
        dtype=dtype,
    )
    n = cfg.hc_mult
    p = mhc.init_hc(jax.random.key(1), cfg)
    p["scale"] = jnp.array([0.7, 1.3, case.a_res], jnp.float32)
    p["b_pre"] = p["b_pre"] + 0.3
    p["b_post"] = p["b_post"] - 0.2
    keys = jax.random.split(jax.random.key(len(case.id)), 2)
    x = (2.0 * jax.random.normal(keys[0], (*case.lead, n, case.d))).astype(dtype)
    y = jax.random.normal(keys[1], (*case.lead, case.d)).astype(dtype)
    # The oracle: XLA's form, which is what runs off the TPU.
    want_h, (want_res, want_post) = mhc.mhc_mix(x, p, cfg)
    want_x = mhc.mhc_spread(x, y, want_res, want_post)

    calls = []

    def counted(fn):
        def call(*args, **kwargs):
            calls.append(fn.__name__)
            return fn(*args, **kwargs, interpret=True)
        return call

    interpreted = types.SimpleNamespace(
        mhc_mix=counted(mhc_streams.mhc_mix),
        mhc_spread=counted(mhc_streams.mhc_spread),
    )
    if case.through == "model":
        monkeypatch.setattr(
            mhc, "chip", types.SimpleNamespace(platform=lambda: "tpu")
        )
        monkeypatch.setattr(mhc, "mhc_streams", interpreted)
        h, (h_res, h_post) = mhc.mhc_mix(x, p, cfg)
        wrote = mhc.mhc_spread(x, y, want_res, want_post)
        rows = int(np.prod(case.lead))
        by_kernels = rows >= mhc._MHC_KERNEL_ROWS
        assert calls == (["mhc_mix", "mhc_spread"] if by_kernels else [])
    else:
        h, h_res, h_post = interpreted.mhc_mix(
            x, p["proj"], p["scale"], p["b_pre"], p["b_post"], p["b_res"],
            iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
        )
        wrote = interpreted.mhc_spread(x, y, want_res, want_post)
    assert h.shape == want_h.shape and h.dtype == dtype
    assert h_res.shape == (*case.lead, n, n) and h_res.dtype == jnp.float32
    assert h_post.shape == (*case.lead, n) and h_post.dtype == jnp.float32
    assert wrote.shape == x.shape and wrote.dtype == dtype
    np.testing.assert_allclose(h_res, want_res, rtol=0, atol=H_TOL)
    np.testing.assert_allclose(h_post, want_post, rtol=0, atol=H_TOL)
    # As `test_sinkhorn_leaves_a_doubly_stochastic_matrix` asks: the
    # columns, divided last, to float32; the rows as far as twenty rounds
    # bring them (the oracle's are as far off: 3e-3 on one token in a
    # hundred here, 0.3 at a_res 6).
    np.testing.assert_allclose(np.asarray(h_res).sum(-2), 1.0, atol=1e-5)
    if case.a_res == 1.0:
        np.testing.assert_allclose(np.asarray(h_res).sum(-1), 1.0, atol=1e-2)
    _one_unit(h, want_h)
    _one_unit(wrote, want_x)
