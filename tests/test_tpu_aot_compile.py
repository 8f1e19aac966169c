"""The main path's kernels, compiled for a described TPU at real widths.

No chip is needed: the TPU compiler is installed, and compiles for a
device that is described and not attached (on-chip-measurement guide,
section 2). This finds what interpret mode cannot — misaligned tiles, too
much fast memory, a kernel the compiler replaces — at about two seconds a
case and no chip time. Widths are Llama-3-8B's, as chip_smoke.py runs
them: 32 query / 8 KV heads of 128.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

H, HKV, DH = 32, 8, 128


@pytest.fixture(scope="module")
def v5e():
    """One device of a described v5e:2x2, with the persistent compile
    cache off: such a compile is written to it but cannot be read back
    without a chip, and the next one would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # libtpu lets one process a host load it, to protect an attached
    # chip. Nothing is attached here, and test processes run side by
    # side (xdist): read when libtpu loads, which is the call below.
    with pytest.MonkeyPatch.context() as env:
        env.setenv("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        # tpulint: allow(broad-except reason=whatever keeps the TPU compiler from describing a topology here (no libtpu, no compiler for this chip) skips these tests; they have no CPU meaning)
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


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


def _flash_args(on):
    # chip_smoke's train phase: batch 2, seq 4096.
    def bf16(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=on)

    return bf16(2, 4096, H, DH), bf16(2, 4096, HKV, DH), bf16(2, 4096, HKV, DH)


def _paged_args(on, k: int):
    # Batch 64, 64-token pages, 32 pages a sequence + the dump page.
    b, page, max_pages = 64, 64, 32
    pool = jax.ShapeDtypeStruct(
        (b * max_pages + 1, HKV, page, DH), jnp.bfloat16, sharding=on
    )
    return (
        jax.ShapeDtypeStruct((b, k, H, DH), jnp.bfloat16, sharding=on),
        pool,
        pool,
        jax.ShapeDtypeStruct((b, max_pages), jnp.int32, sharding=on),
        jax.ShapeDtypeStruct((b,), jnp.int32, sharding=on),
    )


@pytest.mark.parametrize(
    "case",
    ["flash_fwd", "flash_fwd_bwd", "paged_k1", "paged_k4"],
)
def test_kernel_compiles_for_v5e_at_llama3_8b_widths(v5e, case):
    fn, args = {
        "flash_fwd": (_flash(False), _flash_args(v5e)),
        "flash_fwd_bwd": (_flash(True), _flash_args(v5e)),
        "paged_k1": (_paged, _paged_args(v5e, 1)),
        "paged_k4": (_paged, _paged_args(v5e, 4)),
    }[case]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
