"""The contract between `LLMEngine` and a model: `Serving`, and the
record a program's expert blocks leave, from the program that makes it
to the counters `stats()` carries.

The bottom of ``llm/``, importing none of its siblings: `paged_kv.py`
(the page pool, and the program pieces every family shares) stands on
it, `hybrid_kv.py` and `latent_kv.py` side by side on that, `engine.py`
on top. A new family subclasses `Serving` in its own programs file; a
counter it needs goes into its own `counters()` and nowhere else.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ray_tpu._private import chip

_FOLD_AT = 512  # programs whose `counts` rows may wait on the device


def leaf_bytes(cache, leaves) -> int:
    """Bytes of those of ``leaves`` that ``cache`` holds."""
    return sum(int(cache[leaf].nbytes) for leaf in leaves if leaf in cache)


def _new_record():
    """What a program's expert blocks leave: `_note` fills, `_record`
    sums."""
    return {"routes": [], "pairs_here": [], "experts_touched": [],
            "sorted_rows": [], "selected": [], "zero_pairs": [],
            "real_max": []}


def _note(record, aux):
    """An expert block's counters, from `moe_ffn`'s ``aux``."""
    record["routes"].append(aux["routes"])
    record["pairs_here"].append(aux["expert_load"].sum())
    record["experts_touched"].append((aux["expert_load"] > 0).sum())
    record["sorted_rows"].append(aux["sorted_rows"])
    if "zero_pairs" in aux:  # a router with identity outputs
        record["zero_pairs"].append(aux["zero_pairs"])
        record["real_max"].append(aux["real_max"])


def _record(record):
    """Per program: ``routes`` [L_expert, T, k] (each token's experts, of
    all the model's) and ``counts`` int32[4]: the pairs of live rows
    whose expert is held, the held experts that got a row, the rows the
    sorted form ran its grouped matmuls over and the pairs it was given
    (padding's and absent experts' among them), each summed over the
    expert blocks; int32[6] where the router has identity outputs: the
    live rows' routes to one, summed, and the most real experts a live
    row chose in any block."""
    zero = (
        [jnp.stack([sum(record["zero_pairs"]),
                    jnp.stack(record["real_max"]).max()])]
        if record["zero_pairs"] else []
    )
    selected = (
        # [L_latent, T, index_blocks]: the blocks each query attended
        # beside its own (-1: fewer candidates), where the model selects.
        {"selected": jnp.stack(record["selected"])} if record["selected"]
        else {}
    )
    return {
        **selected,
        "routes": jnp.stack(record["routes"]),
        "counts": jnp.concatenate([
            jnp.stack(
                [sum(record["pairs_here"]), sum(record["experts_touched"])]
            ),
            sum(record["sorted_rows"]),
            *zero,
        ]).astype(jnp.int32),
    }


class Serving:
    """What `LLMEngine` may ask of a model, and all of it
    (`tests/test_serving_contract.py`); a config's ``serving()`` returns
    one. The cache is ONE tree that every program takes donated and
    returns: pages (physical page 0 is the allocator's dump page) and
    whatever the model keeps per decode slot beside them. Host arrays go
    into a program as they are. ``slot`` is the decode slot a prompt is
    for, ``length`` the context's TRUE length (tokens from it on are
    padding), ``use_kernel`` the attention path, which the engine
    chooses once (Pallas on a bare TPU, XLA's gather under a mesh or on
    a CPU; a program without a kernel takes no notice). A program with
    expert blocks returns its record last (`_record`, on the device):
    the engine hands it to ``on_logits`` and to `note`. `note`, `fold`
    and `counters` are called under the engine's lock and take none."""

    # Why `speculate > 0` is refused; None where `decode` judges drafts.
    no_speculation = (
        "the decode program takes one token a slot: draft acceptance is "
        "`paged_verify`'s own and is not shared"
    )
    # Prefill returns the last real token's logits alone, [1, 1, V].
    logits_last_only = True
    # A prompt's last chunk is padded to the chunk's length (one compiled
    # shape); else it is as long as the context's own pages.
    fixed_chunks = True
    _paged: tuple = ()  # the cache's leaves that are pages

    def __init__(self, cfg, init_weights, expert_blocks: int = 0):
        """``init_weights(key, cfg)`` makes the tree as it is held;
        ``expert_blocks`` route a token to ``cfg.top_k`` experts each."""
        self.cfg = cfg
        self._init_weights = init_weights
        self._pairs_per_token = cfg.top_k * expert_blocks if expert_blocks else 0
        self._backlog: list = []  # (phase, device int32[4 or 6]), unfolded
        # The pairs the live tokens were routed to, and `_record`'s
        # ``counts`` summed over the programs (the held experts that got
        # a row over the decode programs alone; the most real experts a
        # token chose is a maximum). Zeros without expert blocks.
        self._experts = dict.fromkeys(
            ("moe_pairs_routed", "moe_pairs_here", "experts_touched",
             "moe_rows_computed", "moe_rows_sorted", "moe_zero_pairs"), 0
        )
        self._real_experts_max = 0

    def init_weights(self, key):
        return self._init_weights(key, self.cfg)

    def held_weights(self, params):
        """A caller's tree as the programs multiply by it."""
        return params

    def logical_axes(self):  # each leaf's axes, for a mesh
        raise NotImplementedError(
            f"a mesh: {type(self).__name__}'s programs are written for one "
            "chip's share (experts across chips and their exchange are not)"
        )

    def init_cache(self, num_pages, page_size, max_batch, shardings=None):
        raise NotImplementedError

    def cache_bytes(self, cache) -> tuple[int, int]:
        """(bytes of the cache's `_paged` leaves, bytes of whatever else
        it holds: per-slot state)."""
        pool = leaf_bytes(cache, self._paged)
        return pool, sum(int(v.nbytes) for v in cache.values()) - pool

    def prefill(self, params, tokens, cache, pages, *, n_write_pages, slot,
                length, use_kernel=False):
        """A whole prompt, padded to its bucket: the chunk at 0."""
        return self.prefill_chunk(
            params, tokens, cache, pages, np.int32(0),
            n_write_pages=n_write_pages, chunk_pages=n_write_pages, slot=slot,
            length=length, use_kernel=use_kernel,
        )

    def prefill_chunk(self, params, tokens, cache, pages, start, *,
                      n_write_pages, chunk_pages, slot, length,
                      use_kernel=False):
        """``tokens [1, chunk_pages * page_size]`` at the page-aligned
        ``start`` of the context whose whole table is ``pages
        [n_write_pages]``, from what the chunk before it left in the
        cache. Returns ``(logits [1, T, V], cache[, record])``."""
        raise NotImplementedError

    def decode(self, params, tokens, cache, block_tables, positions,
               temperature, rng_key, *, use_kernel, stochastic, active):
        """Every slot at once: ``tokens [B, K]``, K = 1 + ``speculate``,
        from ``positions [B]`` on through ``block_tables [B, max_pages]``
        (-1: unused), sampled on the device. ``active [B]``: the slots
        that decode; another computes like them (static shapes) and
        changes nothing that lasts. ``stochastic`` (static): whether any
        slot's drafts are judged by rejection sampling. Returns
        ``(sampled [B, K] int32, logits [B, V] float32 of position 0,
        cache, accept [B, K-1] bool, rej [B, K-1] int32[, record])``.
        Here by a program without drafts, `_decode_one`: ``[B, 0]``."""
        sampled, logits, cache, record = self._decode_one(
            params, tokens, cache, block_tables, positions, active,
            temperature, rng_key, cfg=self.cfg, use_kernel=use_kernel,
        )
        none = np.zeros((len(tokens), 0), np.int32)
        return sampled, logits, cache, none.astype(bool), none, record

    def note(self, phase: str, record: dict, tokens: int) -> int:
        """Keep the record of a "prefill", "prefill_chunk" or "decode"
        program that ``tokens`` live tokens went through, on the device.
        Returns how many wait where a `fold` is due, else 0."""
        self._experts["moe_pairs_routed"] += tokens * self._pairs_per_token
        self._backlog.append((phase, record["counts"]))
        return len(self._backlog) if len(self._backlog) >= _FOLD_AT else 0

    def fold(self) -> None:
        """Read the waiting rows back and add them up."""
        rows, self._backlog = self._backlog, []
        sums = self._experts
        for phase, row in rows:
            here, touched, computed, given, *zero = np.asarray(row).tolist()
            if zero:
                sums["moe_zero_pairs"] += zero[0]
                self._real_experts_max = max(self._real_experts_max, zero[1])
            sums["moe_pairs_here"] += here
            sums["moe_rows_computed"] += computed
            sums["moe_rows_sorted"] += given
            if phase == "decode":
                sums["experts_touched"] += touched

    def counters(self) -> dict:
        """What `stats()` says that the engine did not count: the expert
        blocks' counters, the kernels the programs were compiled with
        where the platform alone decides, and what a family adds."""
        self.fold()
        out = dict(self._experts)
        # How tight the sorted expert form's row bound is: beside
        # moe_pairs_here / moe_pairs_routed, the share it must do.
        out["moe_sorted_rows_pct"] = (
            100.0 * out["moe_rows_computed"] / out["moe_rows_sorted"]
            if out["moe_rows_sorted"] else 0.0
        )
        if out["moe_pairs_routed"] and self.cfg.zero_experts:
            # How many of a token's routes cost nothing, and how many
            # experts it has left: per live token and expert block.
            share = out["moe_zero_pairs"] / out["moe_pairs_routed"]
            out["zero_expert_pairs_pct"] = 100.0 * share
            out["real_experts_per_token_mean"] = self.cfg.top_k * (1.0 - share)
            out["real_experts_per_token_max"] = self._real_experts_max
        if self._pairs_per_token:
            # The sorted expert form's combine (models/moe.py): the
            # kernel on a TPU, XLA's scatter-add elsewhere.
            out["moe_combine_kernel"] = chip.platform() == "tpu"
        return out
