"""One decode step's recurrent-state updates alone, on the chip: the
masked whole-batch form XLA compiles (what `mamba_step` / `gdn_step` and
`hybrid_decode`'s write-back were on a TPU up to PR 52), an XLA loop over
the live slots, and `ops/pallas/state_step.py` at several head tiles.

    chiprun -- python scripts/state_step_layer.py

Every layer of a cell's stack is stepped once a call (a decode step's
worth), the stack donated and carried from call to call. Prints a JSON
line a (shape, live slots, variant): ms a layer and the GB/s of one read
plus one write of the live slots' state. PERF.md section 6, PR 53, has
the table this made.
"""

import json
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")

from ray_tpu.ops.pallas import state_step  # noqa: E402
from ray_tpu.ops.pallas.state_step import (  # noqa: E402
    gdn_state_step,
    live_order,
    mamba_state_step,
)

# The three cells' stacks: (layers, slots, heads, a, b), and the heads
# that share a small operand's row (Mamba-2: heads a group; delta rule:
# value heads a key head).
SHAPES = {
    "granite mamba": ("mamba", (9, 32, 128, 64, 128), 128),
    "nemotron mamba": ("mamba", (7, 32, 64, 64, 128), 8),
    "qwen3next gdn": ("gdn", (3, 32, 32, 128, 128), 2),
}


def _mamba(s, keep, xdt, b, c):
    """`mamba_step`'s update of states s [.., H, P, N]."""
    lead, (h, p, n) = s.shape[:-3], s.shape[-3:]
    g = b.shape[-2]
    state = s.reshape(*lead, g, h // g, p, n)
    state = state * keep.reshape(*lead, g, -1, 1, 1) + xdt.reshape(
        *lead, g, -1, p, 1
    ) * b[..., :, None, None, :]
    y = (state * c[..., :, None, None, :]).sum(-1)
    return state.reshape(s.shape), y.reshape(*lead, h, p)


def _gdn(s, decay, beta, q, k, v):
    """`gdn_step`'s update of states s [.., Hv, dk, dv]."""
    lead, (hv, dk, dv) = s.shape[:-3], s.shape[-3:]
    hk = k.shape[-2]
    state = s.reshape(*lead, hk, hv // hk, dk, dv)
    state = state * decay.reshape(*lead, hk, -1, 1, 1)
    k_col = k[..., :, None, :, None]
    read = (state * k_col).sum(-2)
    delta = beta.reshape(*lead, hk, -1, 1) * (
        v.reshape(*lead, hk, -1, dv) - read
    )
    state = state + k_col * delta[..., None, :]
    o = (state * q[..., :, None, :, None]).sum(-2)
    return state.reshape(s.shape), o.reshape(*lead, hv, dv)


RULES = {"mamba": (_mamba, mamba_state_step), "gdn": (_gdn, gdn_state_step)}


def masked(rule, layers):
    """Every slot updated, written back under the mask: the parent. A
    program a layer, as `hybrid_decode` holds a layer's update between
    other work: one program over all the layers at once copies the
    stack between them (1.2 GB a layer at granite's shape), which the
    decode program does not."""

    def one_layer(at, stack, active, operands):
        old = stack[at]
        new, y = rule(old, *operands)
        return stack.at[at].set(
            jnp.where(active[:, None, None, None], new, old)
        ), y

    programs = [
        jax.jit(partial(one_layer, at), donate_argnums=0)
        for at in range(layers)
    ]

    def step(stack, active, operands):
        outs = []
        for program in programs:
            stack, y = program(stack, active, operands)
            outs.append(y)
        return stack, jnp.stack(outs)

    return step


def looped(rule, stack, active, operands):
    """An XLA loop over the live slots: slice one slot's state, update
    it, read out of the same value, write it back on the carried stack."""
    order, count = live_order(active)
    slots, heads = stack.shape[1:3]
    outs = []
    for at in range(stack.shape[0]):
        def one(i, carry, at=at):
            stack, y = carry
            slot = order[i]
            s = jax.lax.dynamic_slice(
                stack, (at, slot, 0, 0, 0), (1, 1, *stack.shape[2:])
            )[0, 0]
            new, row = rule(s, *(x[slot] for x in operands))
            stack = jax.lax.dynamic_update_slice(
                stack, new[None, None], (at, slot, 0, 0, 0)
            )
            return stack, jax.lax.dynamic_update_slice(
                y, row[None], (slot, 0, 0)
            )

        width = stack.shape[3] if rule is _mamba else stack.shape[4]
        stack, y = jax.lax.fori_loop(
            0, count[0], one,
            (stack, jnp.zeros((slots, heads, width), jnp.float32)),
        )
        outs.append(y)
    return stack, jnp.stack(outs)


def kernel(step, block_bytes, stack, active, operands):
    order, count = live_order(active)
    outs = []
    for at in range(stack.shape[0]):
        stack, y = step(
            stack, at, order, count, *operands, block_bytes=block_bytes
        )
        outs.append(y)
    return stack, jnp.stack(outs)


def operands_of(kind, shape, per_row, rng):
    _, slots, heads, a, b = shape

    def normal(*s):
        return jnp.asarray(rng.standard_normal(s), jnp.float32)

    keep = jnp.exp(-jnp.abs(normal(slots, heads)) * 0.1)
    if kind == "mamba":
        groups = heads // per_row
        return (keep, normal(slots, heads, a), normal(slots, groups, b),
                normal(slots, groups, b))
    hk = heads // per_row

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    return (keep, jax.nn.sigmoid(normal(slots, heads)),
            unit(normal(slots, hk, a)) * a**-0.5, unit(normal(slots, hk, a)),
            normal(slots, heads, b))


def timed(fn, stack, active, operands, calls=20, jit=True):
    if jit:
        fn = jax.jit(fn, donate_argnums=0)
    for _ in range(2):
        stack, y = fn(stack, active, operands)
    jax.block_until_ready((stack, y))
    began = time.perf_counter()
    for _ in range(calls):
        stack, y = fn(stack, active, operands)
    jax.block_until_ready((stack, y))
    return (time.perf_counter() - began) / calls, stack, y


def main():
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind,
                      "platform": device.platform}), flush=True)
    rng = np.random.default_rng(53)
    for name, (kind, shape, per_row) in SHAPES.items():
        rule, step = RULES[kind]
        layers, slots, heads, a, b = shape
        operands = operands_of(kind, shape, per_row, rng)
        start = np.asarray(rng.standard_normal(shape), np.float32)
        for live in (32, 15):
            mask = np.zeros(slots, bool)
            mask[rng.permutation(slots)[:live]] = True
            active = jnp.asarray(mask)
            whole = heads * a * b * 4
            # name -> (the step, heads at once, trips unrolled)
            variants = {
                "masked (parent), a program a layer": (
                    masked(rule, layers), 8, 2),
                "xla loop over live slots": (partial(looped, rule), 8, 2),
                "kernel, a head at once, 2048 KiB blocks": (
                    partial(kernel, step, 2 << 20), 1, 1),
                "kernel, 4 heads at once, 2048 KiB blocks": (
                    partial(kernel, step, 2 << 20), 4, 2),
                "kernel, 16 heads at once, 2048 KiB blocks": (
                    partial(kernel, step, 2 << 20), 16, 1),
            }
            for block in (whole, 2 << 20, 1 << 20, 512 << 10):
                if block <= whole:
                    variants[
                        f"kernel, 8 heads at once, {block >> 10} KiB blocks"
                    ] = (partial(kernel, step, block), 8, 2)
            want = None
            for variant, (fn, at_once, unroll) in variants.items():
                jax.clear_caches()
                state_step._HEADS_AT_ONCE = at_once
                state_step._UNROLL = unroll
                seconds, stack, y = timed(fn, jnp.asarray(start), active,
                                          operands,
                                          jit=not variant.startswith("masked"))
                got = (np.asarray(stack[:, mask]), np.asarray(y[:, mask]),
                       np.asarray(stack[:, ~mask]))
                del stack, y
                want = want or got
                moved = 2 * live * heads * a * b * 4
                print(json.dumps({
                    "shape": name, "live": live, "variant": variant,
                    "ms_a_layer": round(1e3 * seconds / layers, 4),
                    "live_GB_per_s": round(moved * layers / seconds / 1e9, 1),
                    "state_max_abs": float(np.abs(got[0]).max()),
                    "state_max_abs_diff": float(
                        np.abs(got[0] - want[0]).max()),
                    "y_max_abs_diff": float(np.abs(got[1] - want[1]).max()),
                    "dead_slots_changed": bool(
                        (got[2] != start[:, ~mask]).any()),
                }), flush=True)


if __name__ == "__main__":
    main()
