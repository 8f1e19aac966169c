"""LLMEngine: continuous-batching inference over the static-shape
programs of `paged_kv.py`.

Plays the role of vLLM's engine in the reference stack (SURVEY.md §2.4:
ray.llm passes TP/PP sizes to vLLM and gang-schedules its workers).
TPU-native shape: tensor parallelism is not worker processes — it is the
same XLA programs pjit-sharded over a mesh's 'tp' axis, so adding
chips changes a sharding annotation, not the orchestration.

One cache and three programs over it, which the MODEL supplies
(`self.serving`, a `serving.Serving`, whose docstring is the contract:
`paged_kv.LlamaServing` for a `LlamaConfig`, or what a config's own
`serving()` returns): a whole prompt's prefill (bucketed to power-of-two
lengths to bound compile count), one chunk's of a long prompt, and the
decode program (K = 1 + `speculate` tokens a slot). add_request() parks
requests in a FIFO; step() admits queued requests into free slots while
their pages fit the pool, runs at most one prefill chunk, and then
advances all `max_batch` slots with one decode program.

One decode step is in flight while the host works. step() dispatches
step t+1 with step t's sampled ids as they lie on the device, and only
then reads step t back, emits it and returns: the device runs t+1 under
the host's read-back, the pump's delivery and the preparation of t+2.
A slot's position, its pages and whether its next token is its last by
`max_tokens` or `max_seq` are known without the token's value; only a
stop token is learnt a step late, and that slot's one extra slot-step
is computed and thrown away. Where the next step needs token VALUES on
the host (drafts, a slot that samples on the host, a preemption, the
first token after a prefill) the step in flight is read back first:
`stats()["pipeline_drains"]` counts those by cause.

Weights: the engine holds `self.params` as its programs multiply, the
matmul weights and the embedding in `cfg.dtype` (`paged_kv.matmul_weights`,
once, in `__init__`), the norm scales as given; `stats()["param_bytes"]`
is that tree's size. An fp32 tree handed in as `params=` stays the
caller's, untouched.

Host phases are `jax.profiler.TraceAnnotation` spans (`engine:step` and
its children, `engine:add_request`, `engine:abort_request`): with a
profiler session open they land in the trace's host plane, on the
device events' clock; with none each is one flag test. Every call that
puts a program on the device is a span of its own, `launch:*`, around
the call and nothing else: programs run in the order they were launched,
so the k-th launch of a trace is its k-th program. The phases of
`step()` also book their own seconds (a phase's duration less its
children's) into `stats()`, profiler or none (`_Phase`). README "Serve
observability" lists them.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from functools import cache, partial
from typing import Any

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from ray_tpu.llm.paged_kv import (
    LlamaServing,
    PageAllocator,
    prefix_hashes,
    propose_ngram_draft,
)
from ray_tpu.models.llama import LlamaConfig, PRESETS


@dataclass(frozen=True)
class SamplingParams:
    max_tokens: int = 64
    temperature: float = 0.0  # 0 = greedy
    top_k: int = 0  # 0 = full vocab
    stop_token_ids: tuple = ()
    seed: int = 0


@dataclass
class _Request:
    request_id: str
    prompt: list[int]
    sampling: SamplingParams
    out_tokens: list = field(default_factory=list)
    slot: int = -1
    position: int = 0  # index the NEXT token will be written at
    last_token: int = 0
    done: bool = False
    pages: list = field(default_factory=list)  # block table
    # Request-path timing (wall clock), the feed for the serve:prefill /
    # serve:decode spans and TTFT/TPOT histograms. First-write-wins so a
    # preemption's recompute re-admission never resets TTFT.
    submit_ts: float = 0.0
    prefill_start_ts: float = 0.0
    first_token_ts: float = 0.0
    finish_ts: float = 0.0


@dataclass
class _DecodeStep:
    """A decode step that was dispatched and not yet read back: what its
    program returned, on the device, and what the host needs to emit
    it."""

    slots: dict  # slot -> request, as of the dispatch
    sampled: Any  # [B, K] int32: the next step's `tokens` at K = 1
    logits: Any  # [B, V] fp32, position 0
    accept: Any  # [B, K-1] bool
    rej: Any  # [B, K-1] int32
    drafts: np.ndarray  # [B, K-1]: the host's proposals
    draft_len: np.ndarray  # [B]


# Links of the chain of keys made by one program.
_KEY_BLOCK = 64


@jax.jit
def _key_block(key):
    """The next `_KEY_BLOCK` links of ``key, sub = split(key)``: the
    chain's new head and every `sub`, each an array of its own, so that
    no program and no unpacking stands between two decode programs."""

    def link(key, _):
        key, sub = jax.random.split(key)
        return key, sub

    key, subs = jax.lax.scan(link, key, length=_KEY_BLOCK)
    return key, [subs[i] for i in range(_KEY_BLOCK)]


@jax.jit
def _logits_row(logits, idx):
    """Row `idx` of a prefill's `[1, T, V]` logits as ONE program: the
    eager `logits[0, idx]` is two (a slice, a squeeze), and a launch is
    one program."""
    return logits[0, idx]


# The phases of `step()` whose own seconds `stats()` carries as
# `host_s_sum.<phase>`: the `engine:*` children of a step by their own
# name, every `launch:*` under `launch`, every `readback:*` under
# `readback`, and the step's remainder under `step`.
_HOST_PHASES = (
    "step", "admit", "prefill_chunk", "first_token", "grow_tables",
    "decode_dispatch", "decode_sync", "emit", "launch", "readback",
)


@cache
def _host_key(name: str) -> str:
    """A span's name -> the `stats()` key of its phase."""
    prefix, _, rest = name.partition(":")
    return f"host_s_sum.{rest if prefix == 'engine' else prefix}"


class _Phase:
    """One host phase of `step()`: its `TraceAnnotation`, entered and
    left with it, and its OWN seconds (its duration less that of the
    phases opened inside it) added to `stats()["host_s_sum.<phase>"]`,
    so that the phases of a step add up to the step with the profiler
    off. Only `step()`'s thread opens one; `with` gives the annotation,
    for `set_metadata`."""

    __slots__ = ("open", "stats", "key", "span", "began", "inner")

    def __init__(self, engine: "LLMEngine", name: str, attrs: dict):
        self.open = engine._open_phases
        self.stats = engine._stats
        self.key = _host_key(name)
        self.span = TraceAnnotation(name, **attrs)
        self.inner = 0.0

    def __enter__(self):
        self.span.__enter__()
        self.open.append(self)
        self.began = time.perf_counter()
        return self.span

    def __exit__(self, *exc):
        spent = time.perf_counter() - self.began
        self.open.pop()
        if self.open:
            self.open[-1].inner += spent
        self.stats[self.key] += spent - self.inner
        return self.span.__exit__(*exc)


def _bucket(n: int, lo: int = 16) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


class LLMEngine:
    def __init__(
        self,
        model: str | LlamaConfig = "tiny",
        *,
        max_batch: int = 4,
        max_seq: int | None = None,
        mesh=None,
        params=None,
        seed: int = 0,
        kv: str = "paged",  # the one cache; kept for callers that name it
        page_size: int = 64,
        num_pages: int | None = None,
        speculate: int = 0,  # draft tokens per step (prompt lookup)
        prefill_chunk: int | None = None,  # tokens per prefill chunk
        prefill_delay_s: float = 0.0,  # chaos: injected TTFT (tests)
    ):
        from ray_tpu._private import chip

        if kv != "paged":
            raise ValueError(
                f"kv={kv!r}: the dense slab cache was removed; the page "
                "pool ('paged') is the engine's only cache"
            )
        init_began = time.perf_counter()
        # Where this engine runs: the platform the worker's lease fixed
        # (raises if a chip was promised and cannot be opened).
        self.platform = chip.platform()
        cfg = PRESETS[model] if isinstance(model, str) else model
        self.cfg = cfg
        # What the model supplies: its weights as held, its cache and
        # its three programs.
        serving = cfg.serving() if hasattr(cfg, "serving") else LlamaServing(cfg)
        self.serving = serving
        if speculate and serving.no_speculation:
            raise ValueError(f"speculate > 0 {serving.no_speculation}")
        self.max_batch = max_batch
        self.max_seq = max_seq or cfg.max_seq
        self.mesh = mesh
        if params is None:
            params = serving.init_weights(jax.random.key(seed))
        if mesh is not None:
            from ray_tpu.parallel.sharding import shard_pytree

            params = shard_pytree(params, mesh, serving.logical_axes())
        # One program casts what every program would otherwise cast on
        # every call; each leaf stays sharded as it came, and the
        # caller's tree is neither donated nor kept. Where nothing is to
        # be cast (a float32 config, a tree already held) the arrays
        # stay the caller's own.
        self.params = serving.held_weights(params)
        self.page_size = page_size
        self.prefill_delay_s = float(prefill_delay_s)
        self.speculate = int(speculate)

        # Default token budget: every slot can grow to max_seq. Serving
        # deployments pass a smaller num_pages to run memory-bound
        # admission (the point: many variable-length requests share one
        # budget).
        if num_pages is None:
            num_pages = max(
                (max_batch * self.max_seq) // page_size, max_batch
            )
        self.alloc = PageAllocator(num_pages, page_size)
        # +1: physical page 0 is the allocator's dump page.
        if (
            mesh is not None
            and mesh.shape.get("tp", 1) > 1
            and cfg.n_kv_heads % mesh.shape["tp"] == 0
        ):
            # Shard the pool on the KV-head dim over tp (the
            # head-major layout's natural TP split): each chip
            # holds 1/tp of the KV bytes — the reference's
            # tensor_parallel_size KV split — and the attention
            # einsums contract per-head, so SPMD needs no
            # resharding on the hot path. Allocated DIRECTLY
            # sharded (out_shardings on the zeros program): pools
            # are sized toward per-chip HBM x tp, so a transient
            # unsharded replica would OOM at init.
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            ns = NamedSharding(mesh, P(None, None, "tp", None, None))
            self.cache = serving.init_cache(
                num_pages + 1, page_size, max_batch,
                shardings={"k": ns, "v": ns},
            )
        else:
            self.cache = serving.init_cache(num_pages + 1, page_size, max_batch)
        self.max_pages_per_seq = -(-self.max_seq // page_size)
        # Pallas paged-attention kernel on a bare TPU backend (under a
        # mesh XLA's SPMD partitioner stays in charge).
        # RAY_TPU_PAGED_ATTN=0/1 overrides — =1 on CPU runs the kernel
        # interpreted (parity tests).
        # tpulint: allow(TPU703 reason=emergency kernel off-switch read in library code that must work without a live runtime or config registry)
        env_flag = os.environ.get("RAY_TPU_PAGED_ATTN", "").strip()
        if env_flag in ("0", "1"):
            use_kernel = env_flag == "1"
        else:
            use_kernel = mesh is None and self.platform == "tpu"
        self.paged_attn_kernel = use_kernel
        # Chunked prefill: a prompt longer than the chunk is
        # prefilled one page-aligned chunk per step(), interleaved
        # with decode — one long admission no longer stalls every
        # in-flight request for its full dense pass (reference
        # capability: vLLM chunked prefill behind ray.llm).
        if prefill_chunk is not None:
            prefill_chunk = max(
                -(-prefill_chunk // page_size) * page_size, page_size
            )
        self.prefill_chunk = prefill_chunk
        self._prefilling: dict | None = None
        self._prefill_chunk_fn = partial(
            serving.prefill_chunk, use_kernel=use_kernel
        )
        self._prefill_paged = partial(serving.prefill, use_kernel=use_kernel)
        # The one decode program, K = 1 + speculate tokens a slot.
        self._decode_paged = partial(serving.decode, use_kernel=use_kernel)
        # Called, where set, with every program's logits as the program
        # returned them (on the device) and its record, if it makes one:
        # `on_logits(phase, logits, record)`, phase "prefill",
        # "prefill_chunk" or "decode". For checks of the timed programs'
        # own output against a reference.
        self.on_logits = None
        # The chain of keys, ``key, sub = split(key)`` a decode step:
        # its head, and the links made ahead (`_key_block`).
        self._step_key = jax.random.key(seed)
        self._keys: list = []
        # The decode step dispatched and not read back, if any; and why
        # this step() read one back before its own dispatch, if it did.
        self._in_flight: _DecodeStep | None = None
        self._drained_for: str | None = None
        # The `_Phase`s `step()` has open, innermost last; when the last
        # `step()` returned (`between_s_sum`); whether this `step()` has
        # launched a prefill program (`decode_steps_alone`).
        self._open_phases: list[_Phase] = []
        self._left_step_at: float | None = None
        self._launched_prefill = False
        self._temps = np.zeros((max_batch,), np.float32)
        self._queue: list[_Request] = []
        self._active: dict[int, _Request] = {}  # slot → request
        self._free = list(range(max_batch))
        self._ids = itertools.count()
        self._rng = np.random.default_rng(seed)
        # Host mirrors of the decode inputs, one entry per slot: the
        # newest token the host has read back, and the position the
        # slot's next dispatch writes at (it advances at dispatch).
        self._tokens = np.zeros((max_batch, 1), np.int32)
        self._positions = np.zeros((max_batch,), np.int32)
        # add_request may run on a different thread than step() (the serve
        # pump runs step in an executor); guard the queue/slot state.
        # step() lets go of it while it waits for a decode program.
        self._lock = threading.Lock()
        # Per-request tokens emitted since the last drain_deltas() call —
        # the feed for streaming responses (reference shape: vLLM's
        # per-step RequestOutput deltas). Only requests added with
        # stream=True record deltas, so batch callers don't accumulate
        # tokens nobody drains.
        self._deltas: dict[str, list[int]] = {}
        self._stream_ids: set[str] = set()
        # Ids of the requests that have not ended (queued, prefilling,
        # decoding, preempted): what `abort_request` looks at before it
        # waits for `_lock`.
        self._live: set[str] = set()
        # The cache's two kinds of per-sequence state, as the model
        # counts them: pages, and what it keeps per slot beside them.
        pool_bytes, state_bytes = serving.cache_bytes(self.cache)
        # Serving observability counters (reference: the vLLM stats
        # ray.llm surfaces — requests, tokens, acceptance, preemption).
        self._stats = {
            "requests_submitted": 0,
            "requests_finished": 0,
            "tokens_generated": 0,
            "draft_tokens_proposed": 0,
            "draft_tokens_accepted": 0,
            "requests_aborted": 0,
            "preemptions": 0,
            "prefill_chunks": 0,
            # The engine loop's own account (PERF.md's span-and-counter
            # table): occupancy = slot_steps / (decode_steps * max_batch);
            # the two sums are seconds requests waited for a slot and
            # add/abort callers waited for `_lock`; attn_pages_live /
            # attn_pages_table is how much of the decode steps' block
            # tables was pages to attend (the attention kernel's work
            # list: a bucket's growth pages past the position are held,
            # not attended) and not width.
            "steps": 0,
            "decode_steps": 0,
            # Decode steps dispatched before the step before them was
            # read back; reads of the step in flight that had to come
            # before the next dispatch, by cause; slot-steps computed
            # for a request that a stop token had already ended.
            "decode_steps_in_flight": 0,
            "pipeline_drains": {
                "admit": 0, "host_sampled": 0, "speculate": 0, "preempt": 0,
            },
            "overrun_slot_steps": 0,
            # Who sets the pace (PERF.md's table again): of the decode
            # steps dispatched with a step in flight by a `step()` that
            # launched no prefill program first (`alone`), those that
            # found that step already complete (`starved`: the device
            # had finished t before the host launched t+1).
            "decode_steps_alone": 0,
            "decode_steps_starved": 0,
            # Seconds in `step()`, on its thread's CPU, and waiting for
            # `_lock` at its entry; each phase's own seconds
            # (`_HOST_PHASES`: with the lock wait they add up to
            # `step_s_sum`); and the seconds between one step's return
            # and the next one's entry where the later step found work
            # in hand (the executor hop and the pump's delivery).
            "step_s_sum": 0.0,
            "step_cpu_s_sum": 0.0,
            "step_lock_wait_s_sum": 0.0,
            **{f"host_s_sum.{phase}": 0.0 for phase in _HOST_PHASES},
            "between_s_sum": 0.0,
            "slot_steps": 0,
            "attn_pages_live": 0,
            "attn_pages_table": 0,
            "admitted": 0,
            "queue_wait_s_sum": 0.0,
            "lock_wait_s_sum": 0.0,
            "init_s": time.perf_counter() - init_began,
            # Bytes of the held tree: half of an fp32 tree's where
            # cfg.dtype is bfloat16.
            "param_bytes": sum(
                x.nbytes for x in jax.tree.leaves(self.params)
            ),
            "pool_bytes": pool_bytes,
            "state_bytes": state_bytes,
        }

    # ------------------------------------------------------ request API
    @contextmanager
    def _locked(self, span, request_id: str):
        """`_lock` for a caller on the replica's event loop. step() holds
        it for its host work (and for a prefill's wait), not while it
        waits for a decode program; what the caller waited goes on its
        span and into `lock_wait_s_sum`."""
        began = time.perf_counter()
        with self._lock:
            waited = time.perf_counter() - began
            self._stats["lock_wait_s_sum"] += waited
            span.set_metadata(
                rid=request_id, lock_wait_ms=round(waited * 1e3, 3)
            )
            yield

    def _phase(self, name: str, **attrs) -> _Phase:
        """A host phase of `step()`, `engine:*`, `launch:*` or
        `readback:*`: the span, and its own seconds in `stats()`."""
        return _Phase(self, name, attrs)

    def add_request(
        self,
        prompt: list[int],
        sampling: SamplingParams | None = None,
        request_id: str | None = None,
        stream: bool = False,
    ) -> str:
        with TraceAnnotation(
            "engine:add_request", prompt_len=len(prompt)
        ) as span:
            if len(prompt) >= self.max_seq:
                raise ValueError(
                    f"prompt length {len(prompt)} >= max_seq {self.max_seq}"
                )
            sampling = sampling or SamplingParams()
            # Reject requests the pool could NEVER hold (prompt plus
            # its full max_tokens growth) at submission — admitting
            # one and crashing mid-decode would take every in-flight
            # request down with it.
            P = self.page_size
            worst = min(len(prompt) + sampling.max_tokens, self.max_seq)
            pad = min(max(_bucket(worst), P), self.max_pages_per_seq * P)
            if pad // P > self.alloc.num_pages:
                raise ValueError(
                    f"prompt+max_tokens needs {pad // P} pages but the "
                    f"pool holds {self.alloc.num_pages}; raise "
                    "num_pages or lower max_tokens"
                )
            rid = request_id or f"req-{next(self._ids)}"
            # Stamped before the wait for `_lock`, so that queue_s and
            # ttft_s count it.
            req = _Request(
                rid, list(prompt), sampling, submit_ts=time.time()
            )
            with self._locked(span, rid):
                self._stats["requests_submitted"] += 1
                if stream:
                    self._stream_ids.add(rid)
                self._live.add(rid)
                self._queue.append(req)
        return rid

    def _begin_prefill(self, req: _Request, span) -> None:
        """Mark prefill start (first-write-wins, so a preemption's
        re-admission counts neither as admitted nor as queue wait again)
        and apply the injected prefill delay (the ``prefill_delay_s``
        engine kwarg) — a deterministic TTFT injection the serve-tracing
        tests bound spans against. ``span`` is the admission's
        `engine:admit`."""
        if req.prefill_start_ts == 0.0:
            req.prefill_start_ts = time.time()
            waited = max(0.0, req.prefill_start_ts - req.submit_ts)
            self._stats["admitted"] += 1
            self._stats["queue_wait_s_sum"] += waited
            span.set_metadata(queue_ms=round(waited * 1e3, 3))
        if self.prefill_delay_s > 0:
            time.sleep(self.prefill_delay_s)

    def has_unfinished(self) -> bool:
        return bool(
            self._queue
            or self._active
            or self._prefilling is not None
            or self._in_flight is not None
        )

    def _sample(self, logits: np.ndarray, s: SamplingParams) -> int:
        if s.temperature <= 0.0:
            return int(logits.argmax())
        logits = logits / s.temperature
        if s.top_k:
            kth = np.partition(logits, -s.top_k)[-s.top_k]
            logits = np.where(logits < kth, -np.inf, logits)
        logits = logits - logits.max()
        probs = np.exp(logits)
        probs /= probs.sum()
        return int(self._rng.choice(len(probs), p=probs))

    def _finish_if_done(self, req: _Request, finished: list[dict]) -> bool:
        """Evaluate stop conditions on req's latest token (shared by the
        prefill-sampled token and decode-sampled tokens)."""
        s = req.sampling
        tok = req.out_tokens[-1]
        if not (
            tok in s.stop_token_ids
            or len(req.out_tokens) >= s.max_tokens
            or req.position >= self.max_seq - 1
        ):
            return False
        if tok in s.stop_token_ids:
            req.out_tokens.pop()  # don't return the stop token
            d = self._deltas.get(req.request_id)
            if d and d[-1] == tok:
                d.pop()
        req.done = True
        req.finish_ts = time.time()
        self._stats["requests_finished"] += 1
        self._stream_ids.discard(req.request_id)
        self._live.discard(req.request_id)
        finished.append(
            {
                "request_id": req.request_id,
                "prompt": req.prompt,
                "tokens": req.out_tokens,
                "timing": self._request_timing(req),
            }
        )
        if req.slot in self._active:
            self._vacate(req.slot)
        self._release_pages(req)
        return True

    @staticmethod
    def _request_timing(req: _Request) -> dict:
        """Wall-clock phase breakdown of one finished request: queue
        (submit→prefill start), prefill (prefill start→first token),
        decode (first token→finish), plus TTFT — the serve telemetry
        span/histogram feed."""
        t = {
            "submit_ts": req.submit_ts,
            "prefill_start_ts": req.prefill_start_ts,
            "first_token_ts": req.first_token_ts,
            "finish_ts": req.finish_ts,
        }
        if req.submit_ts and req.prefill_start_ts:
            t["queue_s"] = max(0.0, req.prefill_start_ts - req.submit_ts)
        if req.prefill_start_ts and req.first_token_ts:
            t["prefill_s"] = max(
                0.0, req.first_token_ts - req.prefill_start_ts
            )
        if req.submit_ts and req.first_token_ts:
            t["ttft_s"] = max(0.0, req.first_token_ts - req.submit_ts)
        if req.first_token_ts and req.finish_ts:
            t["decode_s"] = max(0.0, req.finish_ts - req.first_token_ts)
        return t

    def _vacate(self, slot: int) -> None:
        """Free a decode slot, its position back to 0. The decode
        program attends every slot up to its position, and a free
        slot's table is all -1 (the dump page): at position 0 that is
        one page, at a finished 8k request's position 130 of them."""
        del self._active[slot]
        self._free.append(slot)
        self._positions[slot] = 0

    def _release_pages(self, req: _Request) -> None:
        for pg in req.pages:
            self.alloc.release(pg)
        req.pages = []

    def _admit(self, finished: list[dict]) -> None:
        while self._queue and self._free:
            if not self._admit_one(finished):
                return

    def _post_prefill(
        self, req, slot, logits, ctx_len, finished, logit_idx=None
    ) -> None:
        """The tail of admission, whole or chunked: sample the next token
        from the context's last logits, activate, run stop checks.
        ctx_len is the true (unpadded) prefilled length — prompt plus
        any tokens generated before a preemption. logit_idx overrides
        the row to sample from (chunked prefill: the last token's index
        LOCAL to the final chunk); a model whose prefill returns the
        last real token's logits alone has them in row 0."""
        with self._phase("engine:first_token", rid=req.request_id):
            if self.serving.logits_last_only:
                logit_idx = 0
            if logit_idx is None:
                logit_idx = ctx_len - 1
            # The first token is sampled on the host: the decode step in
            # flight, which the device ran before this prefill, is read
            # back first (its tokens are due), and the next step is
            # dispatched from the host's tokens. Under `_lock`: this
            # request is in no list an abort would find it in.
            self._drain(finished, "admit", unlock=False)
            with self._phase("launch:first_token_row", rid=req.request_id):
                row = _logits_row(logits, np.int32(logit_idx))
            # The host waits here for the prefill program.
            last = np.asarray(row)
            req.slot = slot
            req.position = ctx_len
            if req.first_token_ts == 0.0:
                req.first_token_ts = time.time()
            req.last_token = self._sample(last, req.sampling)
            self._stats["tokens_generated"] += 1  # the prefill-sampled token
            req.out_tokens.append(req.last_token)
            if req.request_id in self._stream_ids:
                self._deltas.setdefault(req.request_id, []).append(
                    req.last_token
                )
            self._active[slot] = req
            # The prefill-sampled token can already hit max_tokens=1 or a
            # stop token; finishing here frees the slot for this _admit
            # loop itself.
            if not self._finish_if_done(req, finished):
                self._tokens[slot, 0] = req.last_token
                self._temps[slot] = req.sampling.temperature

    def _admit_one(self, finished: list[dict]) -> bool:
        """Admit the head of the queue if its pages fit the pool:
        admission is MEMORY-bound, not slot-bound. Returns False when
        the pool cannot hold the next request yet."""
        if self._prefilling is not None:
            # One chunked prefill at a time: its pages are committed and
            # its chunks are the per-step prefill budget already.
            return False
        P = self.page_size
        req = self._queue[0]
        # An attempt the pool turns away is a span too (the prefix
        # lookup is host time), without the attributes set below.
        with self._phase(
            "engine:admit", rid=req.request_id, prompt_len=len(req.prompt)
        ) as span:
            # Full context: the prompt plus anything generated before a
            # preemption (recompute-style resume). req.prompt stays
            # pristine.
            context = list(req.prompt) + list(req.out_tokens)
            pad = min(
                max(_bucket(len(context)), P),
                self.max_pages_per_seq * P,
            )
            need_pages = pad // P
            # Prefix sharing: leading FULL pages whose token prefix
            # matches a live page are reused (refcounted), not
            # re-allocated.
            hashes = prefix_hashes(context, P)
            shared: list[int] = []
            for h in hashes:
                pg = self.alloc.lookup_prefix(h)
                if pg is None:
                    break
                shared.append(pg)
            if need_pages > self.alloc.num_pages:
                # Would never fit even with the pool empty — a config
                # error, not backpressure; failing loud beats spinning
                # forever.
                self._queue.pop(0)
                raise RuntimeError(
                    f"prompt needs {need_pages} pages but the pool holds "
                    f"{self.alloc.num_pages}; raise num_pages or page_size"
                )
            if need_pages - len(shared) > self.alloc.free_pages:
                return False
            self._queue.pop(0)
            slot = self._free.pop(0)
            span.set_metadata(pages=need_pages, pages_shared=len(shared))
            self._begin_prefill(req, span)
            pages = [self.alloc.share(pg) for pg in shared]
            for i in range(len(shared), need_pages):
                pg = self.alloc.alloc()
                if i < len(hashes):
                    self.alloc.register_prefix(hashes[i], pg)
                pages.append(pg)
            req.pages = pages
            if (
                self.prefill_chunk is not None
                and len(context) > self.prefill_chunk
            ):
                # Long prompt: hold the slot and prefill one chunk per
                # step(), interleaved with decode. Chunks cover only the
                # context's own pages (ceil(ctx/P)); the bucket's growth
                # pages stay unwritten until decode reaches them.
                self._prefilling = {
                    "req": req,
                    "slot": slot,
                    "context": context,
                    "pages": np.asarray(pages, np.int32),
                    "next_start": 0,
                    "ctx_pad": -(-len(context) // P) * P,
                    "need_pages": need_pages,
                }
                self._prefill_step(finished)
                return True
            tokens = np.zeros((1, pad), np.int32)
            tokens[0, : len(context)] = context
            table = np.asarray(pages, np.int32)
            # Prefill rewrites shared pages with byte-identical values
            # (K/V at position i depend only on tokens <= i) —
            # idempotent, so no write mask is needed.
            # Host arrays go in as they are: the call transfers them.
            self._launched_prefill = True
            with self._phase("launch:prefill", rid=req.request_id):
                logits, self.cache, *record = self._prefill_paged(
                    self.params,
                    tokens,
                    self.cache,
                    table,
                    n_write_pages=need_pages,
                    slot=slot,
                    length=len(context),
                )
            self._account("prefill", logits, record, len(context))
            self._post_prefill(req, slot, logits, len(context), finished)
            return True

    def _prefill_step(self, finished: list[dict]) -> None:
        """Advance the in-flight chunked prefill by ONE chunk; on the
        final chunk, sample the first token and activate the slot."""
        st = self._prefilling
        assert st is not None
        P = self.page_size
        context = st["context"]
        start = st["next_start"]
        end = start + self.prefill_chunk
        if not self.serving.fixed_chunks or end > st["need_pages"] * P:
            # As long as the context's own pages; a model whose programs
            # take the true length pads its last chunk to the chunk's
            # own length instead (one compiled shape), where the
            # bucket's pages reach that far.
            end = min(end, st["ctx_pad"])
        rid = st["req"].request_id
        with self._phase(
            "engine:prefill_chunk", rid=rid, start=start, tokens=end - start
        ):
            tokens = np.zeros((1, end - start), np.int32)
            valid = context[start: min(end, len(context))]
            tokens[0, : len(valid)] = valid
            self._launched_prefill = True
            with self._phase("launch:prefill_chunk", rid=rid):
                logits, self.cache, *record = self._prefill_chunk_fn(
                    self.params,
                    tokens,
                    self.cache,
                    st["pages"],
                    np.int32(start),
                    n_write_pages=st["need_pages"],
                    chunk_pages=(end - start) // P,
                    slot=st["slot"],
                    length=len(context),
                )
            self._account("prefill_chunk", logits, record, len(valid))
            st["next_start"] = end
            self._stats["prefill_chunks"] += 1
            if end >= st["ctx_pad"]:
                self._prefilling = None
                # ctx_len-1 always falls in the final chunk: ctx_pad is
                # page-aligned, so ctx_pad - len(context) < P <= chunk.
                self._post_prefill(
                    st["req"], st["slot"], logits, len(context), finished,
                    logit_idx=len(context) - 1 - start,
                )

    def _account(self, phase: str, logits, record: list, tokens: int) -> None:
        """After every program: hand its logits to `on_logits`, and its
        record, where it makes one, to the model (`tokens` live tokens
        went through the program). A record stays on the device until
        `stats()` asks or the model says a fold is due: only then is a
        program waited for here (`readback:moe_counts`)."""
        record = record[0] if record else None
        if self.on_logits is not None:
            self.on_logits(phase, logits, record)
        if record is not None:
            if due := self.serving.note(phase, record, tokens):
                with self._phase("readback:moe_counts", programs=due):
                    self.serving.fold()

    def step(self) -> list[dict]:
        """Admit + one decode step dispatched + the step before it read
        back. Returns the request dicts that read-back finished."""
        finished: list[dict] = []
        stats = self._stats
        entered, cpu_at = time.perf_counter(), time.thread_time()
        # What lay between the step before and this one was the pump's
        # (the executor hop, the delivery) if this one finds work in
        # hand; with none the engine had drained, and nobody's.
        in_hand = len(self._active), int(self._prefilling is not None)
        if self._left_step_at is not None and any(in_hand):
            stats["between_s_sum"] += entered - self._left_step_at
        # The span begins before the wait for `_lock`; its attributes are
        # the engine's state as the step found it, and at its end how
        # long that wait was and how long its thread ran: a step of
        # seconds with neither a wait nor CPU time to show stood still.
        with self._phase(
            "engine:step",
            step=stats["steps"],
            active=in_hand[0],
            queued=len(self._queue),
            prefilling=in_hand[1],
            max_batch=self.max_batch,
        ) as span:
            with self._lock:
                waited = time.perf_counter() - entered
                stats["steps"] += 1
                self._drained_for = None
                self._launched_prefill = False
                if self._prefilling is not None:
                    # Continue the in-flight chunked prefill: one chunk
                    # per step bounds the stall it adds to this step's
                    # decodes.
                    self._prefill_step(finished)
                self._admit(finished)
                if self._active or self._in_flight is not None:
                    self._step_paged(finished)
            cpu = time.thread_time() - cpu_at
            span.set_metadata(
                lock_wait_ms=round(waited * 1e3, 3),
                cpu_ms=round(cpu * 1e3, 3),
            )
        self._left_step_at = time.perf_counter()
        stats["step_s_sum"] += self._left_step_at - entered
        stats["step_cpu_s_sum"] += cpu
        stats["step_lock_wait_s_sum"] += waited
        stats["host_s_sum.step"] -= waited
        return finished

    def _record_token(self, req, tok: int, finished: list[dict]) -> None:
        req.position += 1
        self._stats["tokens_generated"] += 1
        req.out_tokens.append(tok)
        if req.request_id in self._stream_ids:
            self._deltas.setdefault(req.request_id, []).append(tok)
        req.last_token = tok
        self._tokens[req.slot, 0] = tok
        self._finish_if_done(req, finished)

    def _preempt(self, req: _Request) -> None:
        """vLLM-style recompute preemption: free the pages + slot and
        requeue at the FRONT; re-admission prefills the request's full
        context (prompt + generated so far), so generation resumes
        exactly where it stopped. req.prompt itself is never mutated —
        finished dicts must echo the prompt the caller submitted."""
        self._stats["preemptions"] += 1
        self._release_pages(req)
        if req.slot in self._active:
            self._vacate(req.slot)
        req.slot = -1
        self._queue.insert(0, req)

    @staticmethod
    def _host_sampled(s: SamplingParams) -> bool:
        """top-k needs host logic on the [B, V] logits. (top_k with
        temperature 0 IS greedy — the on-device argmax already answered
        it; don't ship the logits for it.)"""
        return bool(s.top_k) and s.temperature > 0

    def _needs_values(self) -> str | None:
        """Why the next decode step cannot be dispatched before the one
        in flight is read back, where it cannot: its inputs are then
        token values that the host has to hold."""
        if self.speculate:
            return "speculate"  # drafts come from `req.out_tokens`
        if any(self._host_sampled(r.sampling) for r in self._active.values()):
            return "host_sampled"  # the token is chosen on the host
        return None

    def _last_in_flight(self, req: _Request) -> bool:
        """Whether the token `req` has in flight is its last by
        `max_tokens` or `max_seq` (`_finish_if_done`'s own tests, one
        token on): known before the token's value is."""
        return (
            len(req.out_tokens) + 1 >= req.sampling.max_tokens
            or req.position + 1 >= self.max_seq - 1
        )

    def _next_key(self):
        """The decode step's link of the chain of keys. A block of links
        is one program, dispatched behind what the device is running."""
        if not self._keys:
            with self._phase("launch:key_block"):
                self._step_key, self._keys = _key_block(self._step_key)
            self._keys.reverse()
        return self._keys.pop()

    def _step_paged(self, finished: list[dict]) -> None:
        """One decode step for every slot that decodes: K = 1 +
        speculate positions a slot in one dispatch of the one decode
        program, and then the read-back of the step dispatched before
        it. Whether that order holds (lag 1) or the step in flight is
        read back first (lag 0) is chosen here, from what this step's
        inputs are.

        With speculation (reference capability: vLLM speculative
        decoding behind ray.llm) columns 1.. of the token matrix are
        prompt-lookup drafts and the longest draft prefix the model
        agrees with is accepted. Greedy slots accept on argmax equality
        (bit-identical to plain decode); stochastic slots use exact
        rejection sampling computed on device (see
        paged_kv.paged_verify) so their emitted stream is distributed
        exactly as plain temperature sampling. At K = 1 there is no
        draft, nothing is accepted and a slot emits its one sampled
        token."""
        P = self.page_size
        K = 1 + self.speculate
        cause = self._needs_values()
        if cause:
            self._drain(finished, cause)

        def attended(position: int) -> int:
            """Pages a step at `position` writes into or attends: up to
            position + K - 1, clamped to the table width. Near max_seq a
            K-wide step may reach past capacity — the kernel routes
            those writes to the dump page and _finish_if_done stops the
            request at max_seq before any overflow token is kept."""
            return min((position + K - 1) // P + 1, self.max_pages_per_seq)

        def grow(decoding: dict, ahead: int) -> bool:
            """Grow block tables to cover every position this step may
            write ([position, position + K - 1] with speculation);
            exhausted pool → preempt the youngest active request until
            pages fit. False, with nobody preempted, where that is due
            and a step is in flight: a victim's context has to hold its
            token in flight before it is requeued."""
            for req in list(decoding.values()):
                needed = attended(req.position + ahead)
                while len(req.pages) < needed and req.slot != -1:
                    if self.alloc.free_pages:
                        req.pages.append(self.alloc.alloc())
                    elif ahead:
                        return False
                    else:
                        victims = [
                            r for r in self._active.values() if r is not req
                        ]
                        self._preempt(victims[-1] if victims else req)
            return True

        with self._phase("engine:grow_tables") as span:
            preempted = self._stats["preemptions"]
            while True:
                # A slot with a token in flight (K = 1 then, and every
                # active slot has one: an admission reads the step back)
                # is one position past what the host has read, and is
                # not in this dispatch where that token is its last.
                ahead = int(self._in_flight is not None)
                decoding = {
                    slot: req for slot, req in self._active.items()
                    if not (ahead and self._last_in_flight(req))
                }
                if grow(decoding, ahead):
                    break
                self._drain(finished, "preempt")
            span.set_metadata(
                preempted=self._stats["preemptions"] - preempted
            )
            decoding = {
                slot: req for slot, req in decoding.items() if req.slot == slot
            }
            if not decoding:
                self._drain(finished)
                return

            tables = np.full(
                (self.max_batch, self.max_pages_per_seq), -1, np.int32
            )
            # An active slot that sits this step out is as a free one.
            self._positions[list(self._active)] = 0
            for slot, req in decoding.items():
                tables[slot, : len(req.pages)] = req.pages
                self._positions[slot] = req.position + ahead
            toks, draft_len = self._propose_drafts()
        self._stats["decode_steps"] += 1
        self._stats["decode_steps_in_flight"] += ahead
        if self._drained_for:
            self._stats["pipeline_drains"][self._drained_for] += 1
        self._stats["slot_steps"] += len(decoding)
        self._stats["attn_pages_live"] += sum(
            attended(req.position + ahead) for req in decoding.values()
        )
        self._stats["attn_pages_table"] += tables.size
        # Static flag: an all-greedy batch (the common speculative
        # configuration) skips the rejection-sampling tensors entirely
        # — at most two compiled variants, like use_kernel. At K = 1
        # there are none to skip, so one variant whatever the
        # temperatures.
        stochastic = K > 1 and any(
            r.sampling.temperature > 0 and not r.sampling.top_k
            for r in decoding.values()
        )
        # The slots that decode: a recurrent model's program leaves the
        # state of the others (free, ended, or mid-prefill) as it is.
        mask = np.zeros((self.max_batch,), bool)
        mask[list(decoding)] = True
        with self._phase("engine:decode_dispatch"):
            # The step in flight's ids as they lie on the device: the
            # same [B, 1] int32 the host's matrix is.
            tokens = self._in_flight.sampled if ahead else toks
            # A copy: the mirror changes while the program, which may
            # read the host's buffer in place, is in flight.
            positions = self._positions.copy()
            key = self._next_key()
            # Whether the device had already finished the step in flight
            # and stood idle for this launch: counted where no prefill
            # program of this step() was keeping it busy meanwhile.
            starved = int(bool(ahead) and tokens.is_ready())
            if not self._launched_prefill:
                self._stats["decode_steps_alone"] += ahead
                self._stats["decode_steps_starved"] += starved
            with self._phase("launch:decode", ahead=ahead, starved=starved):
                (
                    sampled, logits, self.cache, accept, rej, *record
                ) = self._decode_paged(
                    self.params,
                    tokens,
                    self.cache,
                    tables,
                    positions,
                    self._temps,
                    key,
                    stochastic=stochastic,
                    active=mask,
                )
            self._account("decode", logits, record, len(decoding))
            sampled.copy_to_host_async()
        before, self._in_flight = self._in_flight, _DecodeStep(
            decoding, sampled, logits, accept, rej, toks[:, 1:], draft_len
        )
        if before is not None:
            self._read_back(before, finished)

    def _drain(
        self, finished: list[dict], cause: str | None = None,
        unlock: bool = True,
    ) -> None:
        """Read the step in flight back, if there is one, BEFORE the
        next dispatch; `cause` says what needed its token values, and
        is counted where a dispatch follows in this step()."""
        step, self._in_flight = self._in_flight, None
        if step is None:
            return
        self._drained_for = self._drained_for or cause
        self._read_back(step, finished, unlock)

    def _fetch(self, step: _DecodeStep) -> tuple:
        """The host's wait for a decode program, and its results as
        numpy: (sampled [B, K], n_acc [B], rej, position 0's logits or
        None). Touches none of the engine's state: `_read_back` calls it
        with `_lock` let go."""
        sampled = np.asarray(step.sampled)  # [B, K] ints
        K = sampled.shape[1]
        n_acc, rej = step.draft_len, None  # no draft, none accepted
        if K > 1:
            # Speculation's two, [B, K-1] each ([B, 0] at K = 1: not
            # read back), and the acceptance — one mismatch-argmax
            # over [B, K-1], not a per-slot interpreted loop on the
            # serial dispatch path: n_acc[b] = index of the first
            # rejected (or absent) draft position.
            rej = np.asarray(step.rej)
            stop = ~np.asarray(step.accept)
            stop |= np.arange(K - 1)[None, :] >= step.draft_len[:, None]
            n_acc = np.where(stop.any(axis=1), stop.argmax(axis=1), K - 1)
        # The [B, V] logits of position 0, only if a slot samples on
        # the host.
        host_logits = None
        if any(self._host_sampled(r.sampling) for r in step.slots.values()):
            host_logits = np.asarray(step.logits)
        return sampled, n_acc, rej, host_logits

    def _read_back(
        self, step: _DecodeStep, finished: list[dict], unlock: bool = True
    ) -> None:
        """Wait for `step`, emit its tokens and run the stop checks, by
        the slot -> request map of its dispatch: a request that ended or
        was aborted since is skipped."""
        with self._phase("engine:decode_sync"):
            # add_request and abort_request, on the replica's event
            # loop, do not wait out a device step.
            if unlock:
                self._lock.release()
            try:
                sampled, n_acc, rej, host_logits = self._fetch(step)
            finally:
                if unlock:
                    self._lock.acquire()
        with self._phase("engine:emit") as span:
            n_tokens, n_done = self._stats["tokens_generated"], len(finished)
            for slot, req in step.slots.items():
                if req.done:
                    # Ended by a stop token that the host learnt after
                    # this dispatch (an abort has no `finish_ts`): its
                    # slot-step was computed for nobody.
                    self._stats["overrun_slot_steps"] += bool(req.finish_ts)
                    continue
                if self._host_sampled(req.sampling):
                    tok = self._sample(host_logits[slot], req.sampling)
                    self._record_token(req, tok, finished)
                    continue
                na = int(n_acc[slot])
                # Accepted drafts verbatim, then the boundary token: the
                # residual sample if a draft was REJECTED there, the
                # full-p sample if the draft simply ran out (or none
                # existed).
                emit = list(step.drafts[slot, :na])
                if na < step.draft_len[slot]:
                    emit.append(int(rej[slot, na]))
                else:
                    emit.append(int(sampled[slot, na]))
                for idx, tok in enumerate(emit):
                    self._record_token(req, int(tok), finished)
                    if idx < na:
                        # Count acceptance by tokens actually EMITTED —
                        # verified drafts discarded when the request
                        # finishes mid-emit must not inflate the rate.
                        self._stats["draft_tokens_accepted"] += 1
                    if req.done:
                        break
            span.set_metadata(
                tokens=self._stats["tokens_generated"] - n_tokens,
                finished=len(finished) - n_done,
            )

    def _propose_drafts(self) -> tuple[np.ndarray, np.ndarray]:
        """The step's token matrix ``toks [B, K]`` (column 0 the last
        token, then a prompt-lookup draft) and ``draft_len [B]``. top_k
        slots run with an empty draft (their position-0 output is a
        normal decode step); without speculation every slot does, and
        the matrix is ``self._tokens``."""
        draft_len = np.zeros((self.max_batch,), np.int32)
        if not self.speculate:
            return self._tokens, draft_len
        K = 1 + self.speculate
        toks = np.zeros((self.max_batch, K), np.int32)
        toks[:, 0] = self._tokens[:, 0]
        for slot, req in self._active.items():
            if self._host_sampled(req.sampling):
                continue  # host-sampled: no draft
            draft = propose_ngram_draft(
                req.prompt + req.out_tokens, K - 1
            )
            if draft:
                draft_len[slot] = len(draft)
                self._stats["draft_tokens_proposed"] += len(draft)
                toks[slot, 1: 1 + len(draft)] = draft
        return toks, draft_len

    def abort_request(self, request_id: str) -> bool:
        """Drop a request (queued or active), freeing its slot — the
        client-disconnect path for streaming (reference: vLLM engine
        abort_request). Safe to call after completion (returns False),
        and then without a wait for `_lock`: every stream ends with this
        call on the replica's event loop, and a step that ends a prefill
        holds `_lock` for the prefill's length."""
        with TraceAnnotation("engine:abort_request") as span:
            if request_id not in self._live:
                span.set_metadata(rid=request_id, lock_wait_ms=0.0)
                return False
            with self._locked(span, request_id):
                self._live.discard(request_id)
                self._stream_ids.discard(request_id)
                self._deltas.pop(request_id, None)
                st = self._prefilling
                if st is not None and st["req"].request_id == request_id:
                    # Mid-chunked-prefill abort: free the held slot + pages
                    # and drop the chunk state.
                    self._prefilling = None
                    self._free.append(st["slot"])
                    self._release_pages(st["req"])
                    self._stats["requests_aborted"] += 1
                    return True
                for i, r in enumerate(self._queue):
                    if r.request_id == request_id:
                        del self._queue[i]
                        self._stats["requests_aborted"] += 1
                        return True
                for slot, r in list(self._active.items()):
                    if r.request_id == request_id:
                        r.done = True
                        self._vacate(slot)
                        self._release_pages(r)
                        self._stats["requests_aborted"] += 1
                        return True
            return False

    def slot_of(self, request_id: str) -> int | None:
        """The decode slot a request holds (decoding, or mid-prefill), or
        None: which row of a program's per-slot output is this
        request's."""
        with self._lock:
            st = self._prefilling
            if st is not None and st["req"].request_id == request_id:
                return st["slot"]
            for slot, req in self._active.items():
                if req.request_id == request_id:
                    return slot
        return None

    def occupancy(self) -> dict:
        """Decode slots and pool pages in use, for the serve gauges.
        Takes no lock: the pump asks on the event loop between steps."""
        return {
            "active": len(self._active),
            "max_batch": self.max_batch,
            "pages_free": self.alloc.free_pages,
            "pages_total": self.alloc.num_pages,
        }

    def stats(self) -> dict:
        """Serving counters + live occupancy (reference shape: the
        vLLM engine stats ray.llm's deployments surface): request and
        token totals, speculative proposal/acceptance, preemptions,
        chunked-prefill progress, the engine loop's own account (steps,
        slot-steps, attended and table pages, queue and lock waits,
        `init_s`; decode steps dispatched with the step before them
        still in flight, as a count and as `decode_in_flight_pct`, the
        reads that had to come first by cause, `pipeline_drains`, and
        `overrun_slot_steps`; who sets the pace: `decode_starved_pct`,
        the seconds in `step()` and of its thread's CPU, each host
        phase's own seconds `host_s_sum.<phase>`, `between_s_sum`: all
        sums over the engine's life, a first call's compile among a
        `launch`'s, so take rates from two polls), `param_bytes` (the
        held weights, matmul leaves in `cfg.dtype`), which attention and
        which K/V cell write the decode program was compiled with
        (`paged_attn_kernel`, `kv_write_kernel`), the pool/slot
        occupancy, and what the model counts (`Serving.counters`)."""
        with self._lock:
            # The model's own counters beside the engine's.
            out = {**self._stats, **self.serving.counters()}
            out["pipeline_drains"] = dict(out["pipeline_drains"])
            # How often a decode step ran under the host's work on the
            # step before it.
            out["decode_in_flight_pct"] = (
                100.0 * out["decode_steps_in_flight"] / out["decode_steps"]
                if out["decode_steps"] else 0.0
            )
            # How often such a step, alone in its step(), found the device
            # done with the step before it: the host sets the pace.
            out["decode_starved_pct"] = (
                100.0 * out["decode_steps_starved"] / out["decode_steps_alone"]
                if out["decode_steps_alone"] else 0.0
            )
            out["platform"] = self.platform
            out["device_kind"] = jax.devices()[0].device_kind
            out["paged_attn_kernel"] = self.paged_attn_kernel
            # The decode cell write follows the attention's path
            # (paged_kv.paged_verify): Pallas and in place beside the
            # kernel, XLA's scatter beside the gather.
            out["kv_write_kernel"] = out["paged_attn_kernel"]
            out["active_requests"] = len(self._active)
            out["queued_requests"] = len(self._queue)
            out["prefilling"] = self._prefilling is not None
            out["pages_total"] = self.alloc.num_pages
            out["pages_free"] = self.alloc.free_pages
            if out["draft_tokens_proposed"]:
                out["draft_acceptance_rate"] = round(
                    out["draft_tokens_accepted"]
                    / out["draft_tokens_proposed"],
                    4,
                )
        return out

    def drain_deltas(self) -> dict[str, list[int]]:
        """Return and clear per-request tokens emitted since the last
        call — the streaming feed (callers pair it with step()'s finished
        list to know when a request's stream ends)."""
        with self._lock:
            out, self._deltas = self._deltas, {}
        return out

    def generate(
        self,
        prompts: list[list[int]],
        sampling: SamplingParams | None = None,
    ) -> list[list[int]]:
        """Synchronous convenience: run all prompts to completion."""
        order = {}
        for i, p in enumerate(prompts):
            order[self.add_request(p, sampling)] = i
        results: list = [None] * len(prompts)
        while self.has_unfinished():
            for fin in self.step():
                results[order[fin["request_id"]]] = fin["tokens"]
        return results
