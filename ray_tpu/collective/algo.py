"""Topology-aware collective algorithm selection.

The Big Send-off (arXiv:2504.18658) shows algorithm choice by message
size and topology is worth integer factors at scale. Three levers here:

- **Ring vs tree by message size** (:func:`choose_algorithm`): a
  flat ring is bandwidth-optimal (moves ``2(n-1)/n * N`` per rank over
  ``2(n-1)`` latency-bound steps); a binomial tree moves the full
  message each of ``~2*log2(n)`` rounds but pays exponentially fewer
  latency terms — it wins below a per-world-size crossover message
  size. The crossover table is overridable via config
  ``COLLECTIVE_ALGO_CROSSOVER``.
- **Hierarchical two-level allreduce for multi-slice DCN meshes**
  (:func:`hierarchical_allreduce`): reduce-scatter inside the slice
  over ICI, allreduce the scattered shards across slice leaders over
  DCN (1/m of the bytes), all-gather back inside the slice. The slow
  inter-domain link carries ``2(s-1)/s * N/m`` instead of
  ``2(n-1)/n * N``.
- **Honest accounting** (:func:`wire_bytes_per_rank`): per-algorithm
  bytes-on-the-wire estimates feeding the flight recorder's wire
  counter and busbw gauge for ops whose transfers happen inside a
  compiled program.
"""

from __future__ import annotations

import math
from typing import Any, Sequence

import numpy as np

# Algorithm names accepted by the collective verbs' ``algo=`` kwarg.
HUB = "hub"            # cpu backend's default star reduce (rank 0 hub)
RING = "ring"          # flat ring: bandwidth-optimal, O(n) latency terms
TREE = "tree"          # binomial tree: O(log n) latency terms, full-N rounds
AUTO = "auto"          # pick ring/tree by message size (crossover table)
HIERARCHICAL = "hierarchical"  # two-level ICI/DCN (multi-slice meshes)

ALGOS = (HUB, RING, TREE, AUTO, HIERARCHICAL)

# Default tree→ring crossover (bytes) by world size: the ring's 2(n-1)
# latency terms take longer to amortize as the group grows, so the tree
# keeps winning to larger messages. Largest key <= world applies.
_DEFAULT_CROSSOVER = {
    2: 64 << 10,
    4: 128 << 10,
    8: 256 << 10,
    16: 512 << 10,
    32: 1 << 20,
}


def _crossover_table() -> dict[int, int]:
    """Config-overridable crossover table. ``COLLECTIVE_ALGO_CROSSOVER``
    accepts a single byte count ("65536" — every world size) or
    per-world entries ("2:65536,8:262144")."""
    from ray_tpu._private import config

    spec = str(config.get("COLLECTIVE_ALGO_CROSSOVER") or "").strip()
    if not spec:
        return dict(_DEFAULT_CROSSOVER)
    if ":" not in spec:
        return {2: int(spec)}
    table: dict[int, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        w, _, b = part.partition(":")
        table[int(w)] = int(b)
    return table or dict(_DEFAULT_CROSSOVER)


def crossover_bytes(world: int) -> int:
    """Message size (bytes) at which ring overtakes tree for ``world``."""
    table = _crossover_table()
    eligible = [w for w in table if w <= max(2, int(world))]
    return table[max(eligible)] if eligible else min(table.values())


def choose_algorithm(
    nbytes: int,
    world: int,
    n_slices: int = 1,
    override: str | None = None,
    verb: str = "allreduce",
) -> str:
    """Pick the data-plane algorithm for a payload of ``nbytes``/rank.

    ``override`` short-circuits (any explicit non-AUTO algo wins).
    Multi-slice topologies always take the hierarchical two-level path —
    keeping the DCN hop at 1/m of the bytes beats either flat algorithm
    whenever more than one ICI domain is involved. Otherwise: tree below
    the crossover size, ring above.

    ``verb`` extends the crossover routing to the reduce-scatter /
    all-gather hops of the ZeRO-sharded gradient path: the same
    size-vs-latency tradeoff applies (ring moves (n-1)/n of the bytes
    over n-1 latency-bound hops; the latency-optimal plane — hub star
    on the cpu backend, one-shot lowering on the compiled backends,
    both mapped from TREE — moves more bytes in O(1)/O(log n) rounds),
    minus the hierarchical route, which is an allreduce-only driver
    op."""
    if override is not None and override != AUTO:
        if override not in ALGOS:
            raise ValueError(
                f"unknown collective algo {override!r}; known: {ALGOS}"
            )
        return override
    if n_slices > 1 and verb == "allreduce":
        return HIERARCHICAL
    if world <= 2:
        # Two ranks: ring and tree degenerate to the same exchange; call
        # it tree (one round) so tiny groups never pay ring bookkeeping.
        return TREE
    return TREE if nbytes < crossover_bytes(world) else RING


def wire_bytes_per_rank(
    algo: str,
    nbytes: int,
    world: int,
    n_slices: int = 1,
    compressed_nbytes: int | None = None,
    verb: str = "allreduce",
) -> int:
    """Per-rank bytes ``verb`` moves on the wire under ``algo``.

    ``nbytes`` is the op's LOGICAL per-rank payload by the flight
    recorder's convention: the full flat payload for allreduce and
    reducescatter, this rank's contribution for allgather.
    ``compressed_nbytes`` substitutes the quantized payload size (int8
    data + scales) for the phases that ship compressed data. These are
    the analytic counts the flight recorder's wire counter uses for ops
    whose transfers run inside a compiled program (or through the hub,
    where the payload sizes are measured — this function is the
    estimator for the rest)."""
    n = max(1, int(world))
    payload = int(compressed_nbytes if compressed_nbytes is not None
                  else nbytes)
    if n == 1:
        return 0
    if verb == "reducescatter":
        if algo == RING:
            # n-1 hops, each shipping one 1/n chunk.
            return int((n - 1) / n * payload)
        if algo == HUB:
            # full contribution up, the 1/n chunk back down.
            return payload + payload // n
        if algo == TREE:
            # one-shot / reduce-then-slice: the reduce tree's bytes.
            return int(math.ceil(math.log2(n)) * payload)
        raise ValueError(
            f"unknown reducescatter algo {algo!r}; known: {ALGOS}"
        )
    if verb == "allgather":
        if algo == RING:
            # n-1 hops, each forwarding one rank's contribution.
            return (n - 1) * payload
        if algo == HUB:
            # contribution up, the n gathered chunks back down.
            return (n + 1) * payload
        if algo == TREE:
            # recursive-doubling broadcast of the growing gather.
            return int(math.ceil(math.log2(n)) * n * payload)
        raise ValueError(
            f"unknown allgather algo {algo!r}; known: {ALGOS}"
        )
    if algo == HUB:
        return 2 * payload  # one round trip: contribution up, result down
    if algo == RING:
        # reduce-scatter + all-gather, each (n-1)/n of the payload out.
        return int(2 * (n - 1) / n * payload)
    if algo == TREE:
        # binomial reduce up + broadcast down: log2(n) full-payload sends.
        return int(2 * math.ceil(math.log2(n)) * payload)
    if algo == HIERARCHICAL:
        s = max(1, int(n_slices))
        m = max(1, n // s)
        ici = int(2 * (m - 1) / m * payload) if m > 1 else 0
        dcn = int(2 * (s - 1) / s * (payload / m)) if s > 1 else 0
        return ici + dcn
    raise ValueError(f"unknown collective algo {algo!r}; known: {ALGOS}")


# ------------------------------------------------- hierarchical (jax)
_HIER_PROGRAMS: dict[tuple, Any] = {}

# Per-slice DCN skip bookkeeping for the hierarchical partial op: the
# analogue of the cpu hub's per-rank skip window, at slice granularity.
# A slice chronically skipped on the DCN hop escalates to the head
# (collective_slice_report) which drains the WHOLE slice — feeding the
# same drain-and-replace path the rank-level chronic-skip signal uses.
import threading as _threading

_slice_lock = _threading.Lock()
_slice_skips: dict[str, dict[int, int]] = {}         # group → slice → total
_slice_skip_events: dict[str, list] = {}             # group → [(ts, slice)]
_slice_reported: dict[str, set] = {}                 # group → reported slices


def slice_skip_stats(group: str = "hier") -> dict[int, int]:
    """Per-slice DCN-hop skip counts of the hierarchical partial
    allreduce for ``group`` (merged into
    ``collective.straggler_stats()`` as ``slice_skip_counts``)."""
    with _slice_lock:
        return dict(_slice_skips.get(group, {}))


def _note_slice_skips(group: str, skipped: Sequence[int]) -> None:
    """Count skips, slide the escalation window, and report a slice
    whose skip rate crossed the chronic threshold to the head (which
    drains the whole slice). Fire-and-forget: telemetry and escalation
    must never fail the op."""
    import time as _time

    from ray_tpu._private import config

    window = config.get("COLLECTIVE_SKIP_WINDOW_S")
    threshold = config.get("COLLECTIVE_SKIP_DRAIN_THRESHOLD")
    now = _time.monotonic()
    chronic: list[tuple[int, int]] = []
    with _slice_lock:
        counts = _slice_skips.setdefault(group, {})
        events = _slice_skip_events.setdefault(group, [])
        reported = _slice_reported.setdefault(group, set())
        for si in skipped:
            counts[si] = counts.get(si, 0) + 1
            events.append((now, si))
        cutoff = now - window
        events[:] = [e for e in events if e[0] >= cutoff]
        in_window: dict[int, int] = {}
        for _ts, si in events:
            in_window[si] = in_window.get(si, 0) + 1
        for si, cnt in in_window.items():
            if cnt >= threshold and si not in reported:
                reported.add(si)
                chronic.append((si, cnt))
    if not chronic:
        return
    try:
        import ray_tpu.api as _api

        rt = _api._runtime
        if not rt.ready:
            return
        for si, cnt in chronic:
            rt.run(
                rt.core.head.call(
                    "collective_slice_report",
                    group=group,
                    slice_id=str(si),
                    skips=int(cnt),
                    window_s=float(window),
                )
            )
    # tpulint: allow(broad-except reason=escalation is advisory; without a runtime or a new-enough head the skip metrics still carry the signal)
    except Exception:
        pass


def _slice_count(devices: Sequence) -> int:
    return len({getattr(d, "slice_index", 0) for d in devices})


def hier_dcn_wire_bytes(
    length: int,
    itemsize: int,
    world: int,
    n_slices: int,
    block: int | None = None,
) -> int:
    """Per-rank bytes the hierarchical allreduce's DCN hop moves.

    Uncompressed: the inter-slice allreduce of the 1/m shard,
    ``2(s-1)/s * length/m * itemsize``. With ``block`` (the int8 codec
    on the DCN hop only): int8 data + 1/block fp32 scales through the
    all_to_all + all_gather pair."""
    s = max(1, int(n_slices))
    n = max(1, int(world))
    m = max(1, n // s)
    if s <= 1:
        return 0
    shard_len = max(1, math.ceil(max(1, length) / m))
    if block is None:
        return int(2 * (s - 1) / s * shard_len * itemsize)
    from ray_tpu.collective import codec

    chunk_len = codec.padded_len(-(-shard_len // s), block)
    q_payload = s * (chunk_len + (chunk_len // block) * 4)
    return int(2 * (s - 1) / s * q_payload)


def hierarchical_allreduce(
    tensors: Sequence[Any],
    devices: Sequence | None = None,
    n_slices: int | None = None,
    group: str = "hier",
    min_slices: int | None = None,
    grace_s: float | None = None,
    skip_slices: Sequence[int] | None = None,
    compression: str | None = None,
):
    """Two-level allreduce over a multi-slice device set.

    ``tensors`` is one per-device tensor (single-controller semantics,
    like :class:`XlaMeshGroup`); ``devices`` default to ``jax.devices()``
    and are split into ``n_slices`` contiguous slices (inferred from
    ``device.slice_index`` when present — the fake-slice dryrun shim
    carries it too). The compiled program runs

        psum_scatter over "ici"  →  psum over "dcn"  →  all_gather over "ici"

    so the DCN hop moves ``1/m`` of the payload per rank. Single-slice
    inputs degenerate to a flat psum (same program shape, dcn axis of
    size 1). Returns the per-device reduced tensors, numerically equal
    to a flat allreduce up to fp32 reassociation.

    **DCN-partial mode** (``min_slices=`` / ``skip_slices=``): the
    slice is the failure unit — the intra-slice ICI reduce-scatter and
    all-gather stay EXACT, and the PR-6 masked-partial semantics apply
    only to the inter-slice DCN reduce: a dead or slow slice
    contributes weight 0 and the sum is rescaled by ``S/Σw`` so the
    mean over contributing slices is preserved. Returns a typed
    :class:`PartialResult` whose ``contributed``/``skipped`` lists name
    SLICE indices (``world`` = number of slices). ``skip_slices`` is
    the explicit dead set (drain notices, external health signals);
    the ``RAY_TPU_SLICE_FAIL`` chaos knob adds deterministic failures —
    a "kill"-failed slice is treated as dead, a delayed slice is
    skipped when its delay exceeds ``grace_s`` (config
    COLLECTIVE_PARTIAL_GRACE_S when None). Fewer than ``min_slices``
    surviving slices raises :class:`CollectiveTimeoutError`. Skips
    feed per-slice DCN metrics, ``slice_skip_stats()``, and — past the
    chronic threshold — a ``collective_slice_report`` to the head,
    which drains the whole slice.

    **Compressed DCN hop** (``compression="int8"``): the block-scaled
    int8 codec applies to the inter-slice exchange ONLY — the slow DCN
    link moves int8 + per-block scales (quantize → all_to_all →
    fp32 accumulate → S/Σw rescale → requantize → all_gather) while
    both ICI hops stay exact f32. Composes with partial mode inside
    the same compiled program."""
    import time

    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.collective import codec
    from ray_tpu.collective.flight_recorder import (
        record_dcn_slices,
        record_op,
        record_partial,
    )
    from ray_tpu.collective.types import (
        CollectiveTimeoutError,
        PartialResult,
    )

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    n = len(devices)
    if len(tensors) != n:
        raise ValueError(
            f"expected {n} per-device tensors, got {len(tensors)}"
        )
    s = int(n_slices) if n_slices is not None else _slice_count(devices)
    s = max(1, s)
    if n % s:
        raise ValueError(f"{n} devices do not split into {s} slices")
    m = n // s
    compression = codec.check_codec(compression)
    partial = min_slices is not None or skip_slices is not None

    # Dead/slow slice set: explicit skips, then the chaos knob. A
    # "kill"-failed slice is dead (the in-process analogue of GCE
    # reaping all its hosts); a delayed slice is skipped when its delay
    # exceeds the grace window in partial mode — otherwise the op pays
    # the stall, which is exactly what partial mode exists to avoid.
    skipped = sorted({int(si) for si in (skip_slices or ())})
    from ray_tpu._private import config as _config
    from ray_tpu._private.test_utils import slice_fail_action

    if _config.get("SLICE_FAIL"):
        grace = (
            float(grace_s) if grace_s is not None
            else _config.get("COLLECTIVE_PARTIAL_GRACE_S")
        )
        stall = 0.0
        for si in range(s):
            if si in skipped:
                continue
            action = slice_fail_action(si)
            if action is None:
                continue
            kind, val = action
            if kind == "kill" or (partial and val > grace):
                skipped = sorted(set(skipped) | {si})
                partial = True
            elif kind == "delay":
                stall = max(stall, val)
        if stall > 0:
            time.sleep(stall)
    if partial:
        contributed_slices = [si for si in range(s) if si not in skipped]
        if len(contributed_slices) < max(1, int(min_slices or 1)):
            raise CollectiveTimeoutError(
                group,
                "hier_allreduce",
                grace_s,
                missing_ranks=skipped,
                detail=f"only {len(contributed_slices)} of {s} slices "
                       f"contribute, below min_slices {min_slices}",
            )
    # Runtime devices (unwrap fake-slice shims so device_put accepts them).
    runtime = [getattr(d, "_raytpu_device", d) for d in devices]

    wall_start = time.time()
    t0 = time.perf_counter()
    arrs = [jnp.asarray(t)[None] for t in tensors]
    shape, dtype = arrs[0].shape[1:], arrs[0].dtype
    length = int(np.prod(shape)) if shape else 1
    pad_to = max(1, math.ceil(length / m)) * m
    mesh = Mesh(
        np.asarray(runtime, dtype=object).reshape(s, m), ("dcn", "ici")
    )
    sharding = NamedSharding(mesh, P(("dcn", "ici")))
    x = jax.make_array_from_single_device_arrays(
        (n, *shape), sharding,
        [jax.device_put(a, d) for a, d in zip(arrs, runtime)],
    )

    block = (
        int(_config.get("COLLECTIVE_COMPRESSION_BLOCK"))
        if compression is not None
        else None
    )
    key = (
        s, m, x.shape, str(dtype), tuple(d.id for d in runtime),
        partial, block,
    )
    prog = _HIER_PROGRAMS.get(key)
    if prog is None:
        if partial or compression is not None:
            prog = jax.jit(
                shard_map(
                    _hier_masked_fn(s, m, length, pad_to, block),
                    mesh=mesh,
                    in_specs=(P(("dcn", "ici")), P(("dcn", "ici"))),
                    out_specs=P(("dcn", "ici")),
                )
            )
        else:
            # Classic exact path: untouched program, byte-identical to
            # before partial/compression existed (int dtypes included).
            def fn(v):
                flat = v.reshape(-1)
                flat = jnp.pad(flat, (0, pad_to - length))
                shard = jax.lax.psum_scatter(
                    flat, "ici", scatter_dimension=0, tiled=True
                )
                shard = jax.lax.psum(shard, "dcn")
                full = jax.lax.all_gather(
                    shard, "ici", axis=0, tiled=True
                )
                return full[:length].reshape(v.shape)

            prog = jax.jit(
                shard_map(
                    fn,
                    mesh=mesh,
                    in_specs=P(("dcn", "ici")),
                    out_specs=P(("dcn", "ici")),
                )
            )
        _HIER_PROGRAMS[key] = prog
        if len(_HIER_PROGRAMS) > 64:
            _HIER_PROGRAMS.pop(next(iter(_HIER_PROGRAMS)))
    if partial or compression is not None:
        if not jnp.issubdtype(dtype, jnp.inexact):
            raise TypeError(
                f"partial/compressed hierarchical allreduce needs a "
                f"floating dtype, got {dtype}"
            )
        w = np.ones((n,), dtype=np.dtype(dtype).name)
        for si in skipped:
            w[si * m:(si + 1) * m] = 0
        wx = jax.make_array_from_single_device_arrays(
            (n,), sharding,
            [
                jax.device_put(jnp.asarray(w[i:i + 1]), d)
                for i, d in enumerate(runtime)
            ],
        )
        out = prog(x, wx)
    else:
        out = prog(x)
    # Order results by global row, not shard-iteration order.
    out_shards = sorted(
        out.addressable_shards, key=lambda sh: sh.index[0].start or 0
    )
    result = [shard.data[0] for shard in out_shards]
    dur = time.perf_counter() - t0
    nbytes = int(np.dtype(dtype).itemsize) * length
    itemsize = int(np.dtype(dtype).itemsize)
    ici_bytes = (
        int(2 * (m - 1) / m * nbytes) if m > 1 else 0
    )
    dcn_bytes = hier_dcn_wire_bytes(length, itemsize, n, s, block=block)
    record_op(
        group, "hier_allreduce", "xla_mesh", n, tensors[0],
        wall_start, dur, wire_bytes=ici_bytes + dcn_bytes,
    )
    if s > 1:
        record_dcn_slices(
            group,
            contributed=[si for si in range(s) if si not in skipped],
            skipped=skipped,
            dcn_bytes=dcn_bytes,
            dur=dur,
        )
    if not partial:
        return result
    if skipped:
        record_partial(group, "hier_allreduce", skipped)
        _note_slice_skips(group, skipped)
    return PartialResult(
        value=result,
        contributed=[si for si in range(s) if si not in skipped],
        skipped=skipped,
        world=s,
    )


def _hier_masked_fn(s: int, m: int, length: int, pad_to: int,
                    block: int | None):
    """shard_map body of the masked (and optionally DCN-compressed)
    hierarchical allreduce. ``w`` carries each device's SLICE weight
    (0 = skipped slice): the ICI reduce-scatter stays exact; the DCN
    reduce weights each slice's shard, rescales by ``S/Σw``, and — with
    ``block`` — moves int8 + per-block scales instead of f32 on the
    inter-slice hop (quantize → all_to_all → fp32 accumulate →
    requantize → all_gather), the EQuARX treatment applied to exactly
    the slow link."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.collective import codec

    shard_len = pad_to // m
    if block is not None:
        chunk_len = codec.padded_len(-(-shard_len // s), block)
        total2 = s * chunk_len
        nblk = chunk_len // block

    def fn(v, w):
        flat = v.reshape(-1)
        flat = jnp.pad(flat, (0, pad_to - length))
        shard = jax.lax.psum_scatter(
            flat, "ici", scatter_dimension=0, tiled=True
        )
        wv = w[0]
        cnt = jax.lax.psum(wv, "dcn")
        scale = s / jnp.maximum(cnt, 1.0)
        if block is None:
            red = jax.lax.psum(shard * wv, "dcn") * scale
        else:
            xq = (shard * wv).astype(jnp.float32)
            xq = jnp.pad(xq, (0, total2 - shard_len))
            q, scales = codec.quantize_blocked_jax(
                xq.reshape(s, nblk, block)
            )
            q_t = jax.lax.all_to_all(
                q, "dcn", split_axis=0, concat_axis=0, tiled=True
            )
            s_t = jax.lax.all_to_all(
                scales, "dcn", split_axis=0, concat_axis=0, tiled=True
            )
            deq = q_t.astype(jnp.float32) * s_t[..., None]
            acc = jnp.sum(deq, axis=0) * scale  # fp32 accumulate
            q2, sc2 = codec.quantize_blocked_jax(acc)
            qg = jax.lax.all_gather(q2, "dcn", axis=0, tiled=False)
            sg = jax.lax.all_gather(sc2, "dcn", axis=0, tiled=False)
            red = (
                (qg.astype(jnp.float32) * sg[..., None])
                .reshape(-1)[:shard_len]
                .astype(v.dtype)
            )
        full = jax.lax.all_gather(red, "ici", axis=0, tiled=True)
        return full[:length].reshape(v.shape)

    return fn
