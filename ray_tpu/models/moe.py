"""Mixture-of-Experts decoder: dropless top-k routing with a grouped
expert matmul.

Every (token, expert) pair the router picks is computed: the pairs are
sorted by expert (one stable argsort), the rows gathered in that order,
the three expert matmuls run as grouped matmuls whose ``group_sizes``
are the tokens each expert received (``jax.lax.ragged_dot``), the
results weighted by the gates and added back per token. There is no
per-expert slot limit and no one-hot dispatch tensor, so the work is
tokens x top_k whatever the skew. Experts carry the "expert" logical
axis, sharded over the mesh's ep axis. A serving program that holds a
share of the experts does that work on the pairs whose expert it holds,
gathered a block of the sorted order at a time, and sums the rows onto
their tokens once a layer; on a TPU the grouped matmuls and the sum are
kernels of their own (`_experts_on_pairs_here`,
``ops/pallas/grouped_rows.py``, ``ops/pallas/expert_combine.py``).

The attention sublayer, scan scaffolding, and non-expert parameters are
the flagship Llama's (ray_tpu.models.llama — this module only swaps the
FFN hook). The shape fields are those of OLMoE-1B-7B-0125-Instruct
(Muennighoff et al. 2024, arXiv:2409.02060: 64 experts of width 1024,
8 per token, gates not renormalised, QK-norm), the public model the
benchmark trains through this file.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu._private import chip
from ray_tpu.models.llama import (
    LlamaConfig,
    Params,
    forward_with_aux,
    init_params,
    param_logical_axes,
)
from ray_tpu.ops.pallas.expert_combine import combine_rows
from ray_tpu.ops.pallas.expert_rows import experts_on_rows, poly_norm_of
from ray_tpu.ops.pallas.grouped_rows import grouped_rows


@dataclasses.dataclass(frozen=True)
class MoEConfig(LlamaConfig):
    """``d_ff`` is the width of one expert."""

    num_experts: int = 8
    top_k: int = 2
    # Whether the top-k gates are renormalised to sum to 1: a field of
    # the model's shape, read from its published config
    # (``norm_topk_prob``), like n_kv_heads.
    norm_topk_prob: bool = False
    # Weights of the router's two auxiliary losses (OLMoE section 3):
    # load balance (Switch section 2.2) and the z-loss on its logits.
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 0.001
    # The rest of what `moe_ffn` reads of a config, at OLMoE's values;
    # `models/nemotron_h.py`'s config carries the same names at its own.
    # "softmax": gates are the k largest probabilities over all experts.
    # "sigmoid": each expert's score is its own sigmoid, the k largest of
    # score + p["router_bias"] are chosen and the scores are the gates.
    router_kind: str = "softmax"
    routed_scaling_factor: float = 1.0
    # "swiglu": silu(h W_gate) * (h W_up) W_down. "relu2": relu(h W_up)^2
    # W_down, no gate matrix. "polynorm": PolyNorm(h W_gate) * (h W_up)
    # W_down (`poly_norm`: three norms over the expert's whole width,
    # with ``polynorm_scale`` and ``polynorm_clamp`` of the config and
    # ``poly_w`` / ``poly_b`` an expert in the tree; `models/motif.py`).
    expert_kind: str = "swiglu"
    # A gated expert's two products clamped before they are multiplied
    # (``swiglu_limit`` of a published config): ``silu(min(h W_gate, l))
    # * clip(h W_up, -l, l)``. None, here and in every family but
    # `models/glm5_next.py`: no clamp, and nothing of it in a program.
    swiglu_limit: float | None = None
    # Router outputs behind the ``num_experts`` real ones that are
    # identity experts (``zero_expert_num`` of a published config,
    # ``zero_expert_type: identity``): a route to one adds ``gate * x``
    # and costs no matmul. 0, here and in every family but
    # `models/longcat_flash.py`: nothing of them in a program.
    zero_experts: int = 0
    # (first, count) of the experts whose weights are held here, where
    # that is a share of `num_experts` (expert parallelism: the router
    # stays `num_experts` wide); None where all are.
    experts_held: tuple | None = None
    # Up to this many rows (tokens of a call), every held expert is
    # applied to every row (`_experts_on_every_row`); above it the pairs
    # are sorted and each expert applied to its own rows
    # (`_experts_on_sorted_pairs`). 0: always sorted, which is right for
    # a train step (OLMoE's has 8,192 rows, 1,024 pairs an expert).
    dense_expert_rows: int = 0

    def _matmul_params(self, experts: int) -> int:
        """Parameters of the matrices a token meets when each layer
        applies ``experts`` experts: projections, router, experts, head."""
        d, hd = self.d_model, self.head_dim
        attn = 2 * d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
        per_layer = attn + d * self.num_experts + experts * 3 * d * self.d_ff
        return self.n_layers * per_layer + d * self.vocab_size

    def num_params(self) -> int:
        """All parameters held: what memory and the optimizer pay for."""
        d = self.d_model
        norms = 2 * d
        if self.qk_norm:
            norms += (self.n_heads + self.n_kv_heads) * self.head_dim
        return (
            self._matmul_params(self.num_experts)
            + self.n_layers * norms + d + self.vocab_size * d
        )

    def flops_per_token(self, seq: int) -> float:
        """Training (fwd+bwd) FLOPs per token: 6 x the matmul parameters
        a token passes through (``top_k`` experts a layer, not all) plus
        causal attention (a query at position t reads t + 1 keys)."""
        attention = 12 * self.n_layers * self.n_heads * self.head_dim * (
            seq + 1
        ) / 2
        return 6.0 * self._matmul_params(self.top_k) + attention


MOE_PRESETS: dict[str, MoEConfig] = {
    # CPU-test scale, with OLMoE's switches on (as many key as query
    # heads, QK-norm, gates as they are).
    "moe_tiny": MoEConfig(
        vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=128, max_seq=256, dtype=jnp.float32, remat="none",
        num_experts=4, top_k=2, qk_norm=True,
    ),
}


def moe_param_logical_axes(cfg: MoEConfig) -> Params:
    axes = param_logical_axes(cfg)
    axes["blocks"].update(
        router=("layers", "embed", "expert"),
        w_gate=("layers", "expert", "embed", "mlp"),
        w_up=("layers", "expert", "embed", "mlp"),
        w_down=("layers", "expert", "mlp", "embed"),
    )
    return axes


def init_moe_params(key: jax.Array, cfg: MoEConfig) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    L = cfg.n_layers
    base_key, *keys = jax.random.split(key, 5)

    def w(k, shape, fan_in):
        return (
            jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
            * fan_in**-0.5
        )

    params = init_params(base_key, cfg)
    params["blocks"].update(
        router=w(keys[0], (L, d, e), d),
        w_gate=w(keys[1], (L, e, d, f), d),
        w_up=w(keys[2], (L, e, d, f), d),
        w_down=w(keys[3], (L, e, f, d), f),
    )
    return params


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(x, index, inverse, fan):
    """``x[index // fan]`` where ``index`` is a permutation of
    ``range(fan * len(x))`` and ``inverse`` its inverse: each row is read
    ``fan`` times. The cotangent is a gather by ``inverse`` and a sum of
    each row's ``fan`` copies, where the gather's own transpose would be
    a scatter-add of the same rows."""
    return x[index // fan]


def _take_rows_fwd(x, index, inverse, fan):
    return x[index // fan], inverse


def _take_rows_bwd(fan, inverse, g):
    rows = g[inverse]
    return rows.reshape(-1, fan, g.shape[-1]).sum(1), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _expert_act(cfg, rows, w_gate, w_up, matmul, poly=None):
    """The width-``d_ff`` activations of an expert of ``cfg.expert_kind``;
    ``matmul(rows, w)`` is the grouped or the plain product. ``poly``:
    `poly_terms` of each row's expert, where the kind is "polynorm"."""
    if cfg.expert_kind == "relu2":
        return jnp.square(jax.nn.relu(matmul(rows, w_up)))
    if cfg.expert_kind == "polynorm":
        return poly_glu(rows, w_gate, w_up, poly, cfg.norm_eps, matmul)
    return clamped_swiglu(rows, w_gate, w_up, cfg.swiglu_limit, matmul)


def poly_terms(cfg, w, b):
    """PolyNorm's four numbers an FFN as the arithmetic takes them, [..,
    4] float32: ``polynorm_scale`` times the three weights ``w`` [.., 3]
    (of the cube, the square and the first power) and the bias ``b``
    [.., 1] held within ``+-polynorm_clamp``."""
    c = cfg.polynorm_clamp
    return jnp.concatenate(
        [cfg.polynorm_scale * w, jnp.clip(b, -c, c)], axis=-1
    ).astype(jnp.float32)


def poly_norm(z, poly, eps: float):
    """``s (w_0 N(z^3) + w_1 N(z^2) + w_2 N(z)) + clip(b)`` with ``N(a) =
    a / sqrt(mean(a^2) + eps)`` over z's WHOLE last axis (PolyNorm,
    arXiv:2411.03884): z [.., f] float32, ``poly`` `poly_terms`'s [.., 4]
    (leading axes that broadcast against z's)."""
    return poly_norm_of(z, *jnp.split(poly, 4, axis=-1), eps)


def poly_glu(rows, w_gate, w_up, poly, eps: float, matmul=jnp.matmul):
    """``PolyNorm(rows W_gate) * (rows W_up)`` in the rows' dtype, the
    norms in float32."""
    with jax.named_scope("ffn:polynorm"):
        gate = poly_norm(matmul(rows, w_gate).astype(jnp.float32), poly, eps)
        up = matmul(rows, w_up)
        return (gate * up.astype(jnp.float32)).astype(up.dtype)


def clamped_swiglu(rows, w_gate, w_up, limit: float | None, matmul=jnp.matmul):
    """``silu(rows W_gate) * (rows W_up)`` and, where the model has a
    ``limit``, the gate held under it and the linear part within it on
    both sides first. (In the order a model without one has always made
    them: its programs' text stays as it was.)"""
    gate = matmul(rows, w_gate)
    if limit is not None:
        gate = jnp.minimum(gate, limit)
    act = jax.nn.silu(gate)
    up = matmul(rows, w_up)
    if limit is not None:
        up = jnp.clip(up, -limit, limit)
    return act * up


def every_row_gates(cfg, routes, gates, here):
    """The every-row form's dispatch: ``weight`` float32 [n, held], a
    row's gate for each held expert and zero where it did not choose it
    (or the pair is not computed ``here``), and ``load`` int32 [held],
    the pairs each held expert got."""
    first, e_here = cfg.experts_held or (0, cfg.num_experts)
    chosen = routes - first  # [n, k]; outside [0, held) where absent
    if here is not None:
        chosen = jnp.where(here, chosen, e_here)
    onehot = chosen[:, :, None] == jnp.arange(e_here)  # [n, k, held]
    weight = (onehot * gates[:, :, None]).sum(1)
    return weight, onehot.sum((0, 1)).astype(jnp.int32)


def touched_first(load):
    """The work list of ``ops/pallas/expert_rows.py`` from the pairs each
    held expert got: the experts with any, packed to the front in
    order with the last of them repeated behind, and their count."""
    slot = jnp.arange(load.shape[0])
    touched = load > 0
    count = touched.sum().astype(jnp.int32)
    place = jnp.cumsum(touched) - 1  # where a touched expert goes
    ids = ((touched & (place == slot[:, None])) * slot).sum(1)
    return jnp.where(slot < count, ids, ids.max()).astype(jnp.int32), count


def _expert_poly(cfg, p, each_row: bool = False):
    """`poly_terms` of the held experts [held, 4] (``each_row``: [held,
    1, 4], against activations [held, n, f]); None for another kind."""
    if cfg.expert_kind != "polynorm":
        return None
    poly = poly_terms(cfg, p["poly_w"], p["poly_b"])
    return poly[:, None] if each_row else poly


def _poly_operands(cfg, p) -> dict:
    """What the expert kernels take of a PolyNorm model beside the
    stacks: the held experts' numbers and the norms' eps; nothing for
    another kind (whose config need not have a ``norm_eps``)."""
    if cfg.expert_kind != "polynorm":
        return {}
    return {"poly": _expert_poly(cfg, p), "eps": cfg.norm_eps}


def every_row_einsum(cfg, rows, p, weight):
    """Every held expert on every row as one batched matmul over the
    experts, and each row's sum over them by ``weight``: every expert's
    weights are read whatever the routes. What runs off the TPU, and
    the oracle of the kernel that runs on it."""
    dt = cfg.dtype
    with jax.named_scope("moe:experts"):
        batched = lambda a, w: jnp.einsum(  # noqa: E731
            "...nd,edf->enf", a, w.astype(dt)
        )
        per_expert = lambda a, w: jnp.einsum(  # noqa: E731
            "enf,efd->end", a, w.astype(dt)
        )
        act = _expert_act(cfg, rows.astype(dt), p.get("w_gate"), p["w_up"],
                          batched, _expert_poly(cfg, p, each_row=True))
        outs = per_expert(act, p["w_down"])  # [held, n, d]
    with jax.named_scope("moe:combine"):
        return jnp.einsum(
            "end,ne->nd", outs.astype(jnp.float32), weight
        ).astype(dt)


def _experts_on_every_row(tokens, p, cfg, routes, gates, here):
    """Every held expert on every row, and each row's sum over ITS
    experts by a [n, held] matrix of gates that is zero elsewhere. For
    few rows: nothing is sorted. On a TPU one kernel does all of it and
    reads the weights of the experts that got a row, no others
    (``ops/pallas/expert_rows.py``: forward only, as is every caller of
    this form); elsewhere `every_row_einsum`."""
    dt = cfg.dtype
    with jax.named_scope("moe:dispatch"):
        weight, load = every_row_gates(cfg, routes, gates, here)
    if chip.platform() != "tpu":
        return every_row_einsum(cfg, tokens, p, weight), load
    with jax.named_scope("moe:dispatch"):
        ids, count = touched_first(load)
    with jax.named_scope("moe:experts"):
        gated = cfg.expert_kind != "relu2"
        out = experts_on_rows(
            tokens.astype(dt), p["w_gate"].astype(dt) if gated else None,
            p["w_up"].astype(dt), p["w_down"].astype(dt), weight, ids, count,
            limit=cfg.swiglu_limit, **_poly_operands(cfg, p),
        )
    return out, load


def _experts_on_sorted_pairs(tokens, p, cfg, routes, gates):
    """Pairs sorted by expert, the rows gathered in that order, the
    experts applied as grouped matmuls whose groups are each expert's
    rows, the results gathered back and summed per token. Every pair
    is computed (every expert held, every row a token): the train
    step's form, with a backward pass."""
    n, k = routes.shape
    d = tokens.shape[-1]
    dt = cfg.dtype
    with jax.named_scope("moe:dispatch"):
        # Pairs in expert order; a stable sort keeps each expert's rows
        # in token order.
        pair_expert = routes.reshape(n * k)
        order = jnp.argsort(pair_expert, stable=True)
        inverse = jnp.argsort(order)
        load = jnp.bincount(
            pair_expert, length=cfg.num_experts
        ).astype(jnp.int32)
        rows = _take_rows(tokens, order, inverse, k)  # [n * k, d]
        poly = _expert_poly(cfg, p)
        if poly is not None:  # each row's expert's
            poly = poly[pair_expert[order]]

    with jax.named_scope("moe:experts"):
        grouped = lambda a, w: jax.lax.ragged_dot(a, w.astype(dt), load)  # noqa: E731
        rows_out = grouped(
            _expert_act(cfg, rows, p.get("w_gate"), p["w_up"], grouped, poly),
            p["w_down"],
        )

    with jax.named_scope("moe:combine"):
        # Back to token order: pair j of token t sits at row t * k + j.
        pairs = _take_rows(rows_out, inverse, order, 1).reshape(n, k, d)
        weighted = pairs.astype(jnp.float32) * gates[..., None]
        out = weighted.sum(1).astype(dt)
    return out, load


# Rows of the sorted order that one step of `_experts_on_pairs_here`
# takes: the work is the pairs computed here rounded up to this.
_PAIR_BLOCK = 1024


def _experts_on_pairs_here(tokens, p, cfg, routes, gates, here):
    """The sorted form where only the pairs under ``here`` are computed
    (a share of the experts held, rows that carry no token): a serving
    program's, forward only. Those pairs sort to the front, ``m`` of
    them, and the work is ``m`` rounded up to `_PAIR_BLOCK`, not the
    ``n * k`` pairs routed: a loop over blocks of the sorted order, as
    many as hold a pair, each gathering its rows; the experts applied to
    the rows gathered (each expert to its group); and ONE sum of the
    ``m`` live rows onto their tokens, each weighted by its float32 gate
    where it lies. On a TPU the experts are two calls a layer of a
    kernel that reads each expert's weights once however its rows fall
    into tiles (``ops/pallas/grouped_rows.py``) and the sum is a kernel
    too (``ops/pallas/expert_combine.py``), over the rows as the first
    left them; elsewhere the loop applies ``jax.lax.ragged_dot`` to each
    block's part of the groups and XLA's scatter-add sums, which are
    also the kernels' oracles. Any routing gives the same sums as every
    pair computed and the dead ones masked, all ``n * k`` live included.
    Returns the rows the grouped matmuls ran over beside the output and
    the load."""
    n, k = routes.shape
    d = tokens.shape[-1]
    first, e_here = cfg.experts_held or (0, cfg.num_experts)
    dt = cfg.dtype
    block = min(_PAIR_BLOCK, n * k)
    on_tpu = chip.platform() == "tpu"
    with jax.named_scope("moe:dispatch"):
        # Held experts are numbered from 0; a pair that is not computed
        # here gets the number after the last and sorts behind every
        # group. (bincount drops that number.)
        pair_expert = jnp.where(
            here.reshape(n * k), routes.reshape(n * k) - first, e_here
        )
        order = jnp.argsort(pair_expert, stable=True)
        load = jnp.bincount(pair_expert, length=e_here).astype(jnp.int32)
        ends = jnp.cumsum(load)  # where each expert's rows end
        m = ends[-1]
        blocks = (m + block - 1) // block
        # The last block may reach past the pairs.
        order = jnp.pad(order, (0, -(n * k) % block))
        token = order // k  # of each row, as the rows lie
        gate = gates.reshape(n * k)[order]
        poly = None if on_tpu else _expert_poly(cfg, p)
        if poly is not None:
            # Each row's expert's (a row past the pairs: any expert's).
            poly = poly[jnp.minimum(pair_expert[order], e_here - 1)]

    def step(i, staged):
        lo = i * block
        with jax.named_scope("moe:dispatch"):
            rows = tokens[jax.lax.dynamic_slice(token, (lo,), (block,))]
            if on_tpu:  # the experts come after the loop
                return jax.lax.dynamic_update_index_in_dim(
                    staged, rows.astype(dt), i, 0
                )
            # Each expert's rows that lie in this block.
            sizes = (
                jnp.clip(ends - lo, 0, block)
                - jnp.clip(ends - load - lo, 0, block)
            )
        with jax.named_scope("moe:experts"):
            grouped = lambda a, w: jax.lax.ragged_dot(  # noqa: E731
                a, w.astype(dt), sizes
            )
            out = grouped(
                _expert_act(
                    cfg, rows, p.get("w_gate"), p["w_up"], grouped,
                    poly if poly is None else jax.lax.dynamic_slice_in_dim(
                        poly, lo, block
                    ),
                ),
                p["w_down"],
            )
        with jax.named_scope("moe:combine"):
            return jax.lax.dynamic_update_index_in_dim(staged, out, i, 0)

    # (The loop's own time goes under the experts' scope; a step's parts
    # are named inside it.) The buffer starts as it is found (on a TPU
    # nothing is written to make it) and the blocks never reached stay
    # so: rows at and past `m` are no expert's output and are never read.
    with jax.named_scope("moe:experts"):
        rows_out = jax.lax.fori_loop(
            0, blocks, step,
            jax.lax.empty((token.shape[0] // block, block, d), dt),
        )
        if on_tpu:
            # The tile follows the rows an expert gets if the router
            # spreads the pairs evenly over its outputs.
            mean = n * k // (cfg.num_experts + cfg.zero_experts)
            gated = cfg.expert_kind != "relu2"
            hidden = grouped_rows(
                rows_out.reshape(-1, d),
                [p[w].astype(dt) for w in (["w_gate"] * gated + ["w_up"])],
                load, cfg.expert_kind, mean, limit=cfg.swiglu_limit,
                **_poly_operands(cfg, p),
            )
            rows_out = grouped_rows(
                hidden, [p["w_down"].astype(dt)], load, None, mean
            ).reshape(rows_out.shape)
    with jax.named_scope("moe:combine"):
        if on_tpu:
            out = combine_rows(rows_out, token, gate, m, n)
        else:
            # Rows at and past `m` go to no token, whatever they hold.
            at = jnp.where(jnp.arange(token.shape[0]) < m, token, n)
            weighted = rows_out.reshape(-1, d).astype(jnp.float32)
            out = jnp.zeros((n, d), jnp.float32).at[at].add(
                weighted * gate[:, None], mode="drop"
            ).astype(dt)
    return out, load, blocks * block


def moe_ffn(x: jnp.ndarray, p: Params, cfg, rows_live=None):
    """FFN hook for llama._block: x [B, S, d] -> (out, aux).

    ``cfg`` is a ``MoEConfig`` or any config with its expert-layer
    fields. Routing is over all ``cfg.num_experts`` and, behind them,
    ``cfg.zero_experts`` identity outputs: a route to one of those is
    never a pair (it is in no group, no load and no kernel's work
    list); a row's gates for them are summed and multiply the row
    itself (``moe:zero``, inside ``moe:combine``). A softmax router
    whose tree has a ``router_bias`` chooses by ``probs + bias`` and
    gates by ``probs``, as the sigmoid kind always does. Where
    ``cfg.experts_held`` names a share, only pairs whose expert is held
    are computed: the others are left out of the sort's groups and add
    nothing, so the result is this share's part of the layer (plus the
    shared expert, where ``p`` has one, times its own sigmoid gate,
    where ``p`` has that). ``rows_live`` (bool [B * S])
    leaves out the pairs of rows that carry no token (a padded tail, a
    free decode slot) in the same way.

    ``aux`` is the layer's router record: ``balance_loss`` and
    ``z_loss`` (unweighted; softmax routers), ``expert_load`` (pairs each
    held expert computed, int32[held]: with every expert held and every
    row live their sum is tokens x top_k, nothing is dropped),
    ``routes`` (the experts of each token, int32[T, k]) and, where only
    some pairs are computed here, ``sorted_rows`` (int32[2]: the rows
    the sorted form ran its grouped matmuls over and the ``T x k`` pairs
    it was given; zeros where the every-row form ran) and, where the
    router has identity outputs, ``zero_pairs`` (the routes of live rows
    that went to one, int32[]) and ``real_max`` (the most real experts
    any live row chose, int32[])."""
    b, s, d = x.shape
    e, k, zero = cfg.num_experts, cfg.top_k, cfg.zero_experts
    n = b * s
    dt = cfg.dtype
    tokens = x.reshape(n, d)

    with jax.named_scope("moe:route"):
        # Matmul, scores and top-k in float32: the k-th and (k+1)-th
        # scores of a token are often closer than bf16 rounding.
        logits = jnp.dot(
            tokens.astype(jnp.float32), p["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        if cfg.router_kind == "softmax":
            probs = jax.nn.softmax(logits, axis=-1)  # [n, e + zero]
        else:
            probs = jax.nn.sigmoid(logits)
        if "router_bias" in p:  # for the choice alone
            _, routes = jax.lax.top_k(probs + p["router_bias"], k)
            gates = jnp.take_along_axis(probs, routes, axis=-1)
        else:
            gates, routes = jax.lax.top_k(probs, k)  # [n, k]
        if cfg.norm_topk_prob:
            gates = gates / gates.sum(-1, keepdims=True)
        if cfg.routed_scaling_factor != 1.0:
            gates = gates * cfg.routed_scaling_factor

    # Which experts are computed here, and for which rows: an identity
    # output (a route at or past `e`) is no expert's and is held nowhere.
    first, e_here = cfg.experts_held or (0, e)
    here = None
    if cfg.experts_held is not None or rows_live is not None or zero:
        local = routes - first
        here = (local >= 0) & (local < e_here)  # [n, k]
        if rows_live is not None:
            here &= rows_live[:, None]

    sorted_rows = (0, 0)
    if n <= cfg.dense_expert_rows:
        out, load = _experts_on_every_row(tokens, p, cfg, routes, gates, here)
    elif here is None:
        out, load = _experts_on_sorted_pairs(tokens, p, cfg, routes, gates)
    else:
        out, load, computed = _experts_on_pairs_here(
            tokens, p, cfg, routes, gates, here
        )
        sorted_rows = (computed, n * k)

    if "shared_up" in p:
        with jax.named_scope("moe:shared"):
            plain = lambda a, w: a @ w.astype(dt)  # noqa: E731
            shared = plain(
                _expert_act(
                    cfg, tokens.astype(dt), p.get("shared_gate"),
                    p["shared_up"], plain,
                    poly_terms(cfg, p["shared_poly_w"], p["shared_poly_b"])
                    if cfg.expert_kind == "polynorm" else None,
                ),
                p["shared_down"],
            )
            if "shared_expert_gate" in p:
                # A sigmoid scalar a token on the shared expert's output
                # (`models/qwen3_next.py`: ``w_s: d -> 1``).
                scalar = jnp.dot(
                    tokens.astype(dt), p["shared_expert_gate"].astype(dt),
                    preferred_element_type=jnp.float32,
                )
                shared = (shared * jax.nn.sigmoid(scalar)).astype(dt)
            out = out + shared

    aux = {"expert_load": load, "routes": routes}
    if zero:
        with jax.named_scope("moe:combine"), jax.named_scope("moe:zero"):
            # What every chip computes for its own rows, whichever
            # experts it holds: nothing is sent anywhere.
            to_zero = routes >= e  # [n, k]
            gate = jnp.where(to_zero, gates, 0.0).sum(-1, keepdims=True)
            out = out + (tokens.astype(jnp.float32) * gate).astype(dt)
            real = k - to_zero.sum(-1)  # [n]: the experts a row chose
            if rows_live is not None:
                to_zero &= rows_live[:, None]
                real = jnp.where(rows_live, real, 0)
            aux["zero_pairs"] = to_zero.sum().astype(jnp.int32)
            aux["real_max"] = real.max().astype(jnp.int32)
    if here is not None:
        aux["sorted_rows"] = jnp.stack(sorted_rows).astype(jnp.int32)
    if cfg.router_kind == "softmax":
        with jax.named_scope("moe:route"):
            # Load balance: e * sum_e (share of pairs routed to e) *
            # (mean probability of e) (Switch section 2.2); z-loss: the
            # mean squared logsumexp of the router's logits.
            # (Under a share: the held experts' part of that sum.)
            mean_prob = probs.mean(0)
            if cfg.experts_held is not None or zero:
                mean_prob = mean_prob[first: first + e_here]
            aux["balance_loss"] = e * (mean_prob * (load / n)).sum()
            aux["z_loss"] = jnp.square(
                jax.nn.logsumexp(logits, axis=-1)
            ).mean()
    return out.reshape(b, s, d), aux


def moe_forward(
    params: Params,
    tokens: jnp.ndarray,
    cfg: MoEConfig,
    attn_fn=None,
    return_hidden: bool = False,
) -> tuple[jnp.ndarray, dict]:
    """tokens [B, S] -> (logits [B, S, V] fp32 — or final hidden states
    with ``return_hidden`` — and every layer's router record, stacked
    on a leading layer dimension: see :func:`moe_ffn`)."""
    # The experts are cast to the compute dtype as whole stacks, before
    # the scan over layers, so that the scan's backward pass stacks their
    # gradients in that dtype too (they are the grouped matmul's outputs,
    # upcast) and the float32 gradient exists only inside the fusions
    # that consume it: at OLMoE's widths 1.6 GB less at the step's peak.
    blocks = dict(params["blocks"])
    for name in ("w_gate", "w_up", "w_down"):
        blocks[name] = blocks[name].astype(cfg.dtype)
    return forward_with_aux(
        {**params, "blocks": blocks}, tokens, cfg, attn_fn=attn_fn,
        ffn_fn=moe_ffn, return_hidden=return_hidden,
    )


def router_losses(aux: dict, cfg: MoEConfig) -> dict[str, jnp.ndarray]:
    """The weighted auxiliary losses and the step's router counters,
    from ``moe_forward``'s record."""
    load = aux["expert_load"].astype(jnp.float32)  # [L, e]
    return {
        "aux_loss": cfg.aux_loss_weight * aux["balance_loss"].mean(),
        "router_z_loss": cfg.z_loss_weight * aux["z_loss"].mean(),
        # Largest expert's pairs over the mean, in the worst layer.
        "expert_load_max_over_mean": (
            load.max(-1) / load.mean(-1)
        ).max(),
        # Pairs computed this step: tokens x top_k x layers, always.
        "moe_pairs": aux["expert_load"].sum(),
    }
