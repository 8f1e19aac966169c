"""State API: inspect live cluster state (reference:
python/ray/util/state/api.py — `ray list tasks/actors/nodes/...` backed by
GCS task events and tables).

All calls query the head service through the driver's core worker.
"""

from __future__ import annotations

import json
from typing import Any

from ray_tpu import api as core_api


def _call_head(method: str, _timeout: float | None = None, **kw) -> dict:
    rt = core_api._runtime
    if rt.core is None:
        raise RuntimeError("ray_tpu.init() has not been called")

    async def go():
        return await rt.core.head.call(method, **kw)

    return rt.run(go(), _timeout)


def list_worker_logs() -> list[dict]:
    """Every captured worker log across the cluster (reference:
    `ray logs` listing the session log dir via the per-node agents)."""
    rt = core_api._runtime

    async def fetch():
        from ray_tpu._private import rpc as _rpc

        table = await rt.core.head.call("node_table")
        out = []
        for nid, n in table.items():
            # Per-node failures (dead host mid-listing, dial timeout)
            # skip that node — one unreachable node must not break the
            # cluster-wide listing.
            try:
                conn = await _rpc.connect(n["addr"])
                try:
                    reply = await conn.call("list_logs")
                finally:
                    await conn.close()
            except (_rpc.RpcError, OSError):
                continue
            for rec in reply.get("logs", []):
                out.append({**rec, "node_id": nid})
        return out

    return rt.run(fetch())


def read_worker_log(worker_prefix: str, tail_bytes: int = 0) -> str | None:
    """Log content of the first worker matching the prefix — dead
    workers included. None when no node has a matching log."""
    rt = core_api._runtime

    async def fetch():
        from ray_tpu._private import rpc as _rpc

        table = await rt.core.head.call("node_table")
        for n in table.values():
            try:
                conn = await _rpc.connect(n["addr"])
                try:
                    reply = await conn.call(
                        "read_log",
                        worker_id=worker_prefix,
                        offset=-tail_bytes if tail_bytes else 0,
                    )
                finally:
                    await conn.close()
            except (_rpc.RpcError, OSError):
                continue
            if reply.get("ok"):
                data = reply["data"]
                return (
                    data.decode("utf-8", "replace")
                    if isinstance(data, bytes)
                    else data
                )
        return None

    return rt.run(fetch())


def list_nodes() -> list[dict]:
    table = _call_head("node_table")
    return [
        {
            "node_id": nid,
            "addr": n["addr"],
            "resources": n["resources"],
            "available": n["available"],
            "labels": n.get("labels", {}),
            "agent_addr": n.get("agent_addr"),
        }
        for nid, n in table.items()
    ]


def list_actors(state: str | None = None) -> list[dict]:
    actors = _call_head("list_actors")["actors"]
    out = [
        {"actor_id": aid, **info}
        for aid, info in actors.items()
        if state is None or info["state"] == state
    ]
    return out


def list_tasks(limit: int = 1000, state: str | None = None) -> list[dict]:
    # The state filter runs on the head BEFORE limit, so filtered kinds
    # aren't evicted from the newest-N window by other traffic.
    events = _call_head("list_task_events", limit=limit, state=state)[
        "events"
    ]
    if state is not None:
        events = [e for e in events if e.get("state") == state]
    return events


def list_placement_groups() -> list[dict]:
    pgs = _call_head("list_placement_groups")["placement_groups"]
    return [{"pg_id": pid, **pg} for pid, pg in pgs.items()]


def list_objects() -> list[dict]:
    """Objects in this node's shared-memory store."""
    rt = core_api._runtime
    store = rt.core.store
    out = []
    for oid_hex, size in store.list_objects():
        out.append({"object_id": oid_hex, "size_bytes": size})
    return out


def summarize_tasks() -> dict:
    counts: dict[str, int] = {}
    for ev in list_tasks(limit=20000):
        counts[ev.get("state", "?")] = counts.get(ev.get("state", "?"), 0) + 1
    return counts


def cluster_metrics() -> dict:
    """Merged user metrics across all workers."""
    from ray_tpu.util import metrics as m

    workers = _call_head("cluster_metrics")["workers"]
    # Refresh this process's entry from the live registry (its periodic
    # flusher may lag); same key as the flusher uses so the local
    # snapshot replaces — never double-counts — the reported one.
    local = m.snapshot()
    if local:
        workers = {**workers, core_api._runtime.core.addr: local}
    return m.merge_snapshots(workers)


def prometheus_metrics() -> str:
    from ray_tpu.util import metrics as m

    return m.prometheus_text(cluster_metrics())


def train_stats() -> dict:
    """Per-train-job goodput accounting from the head: productive step
    time vs. stalls (inter-step gaps, data wait, checkpointing) and
    elastic restart loss, plus MFU and phase breakdowns. Backs the
    dashboard's /api/train and the `ray_tpu goodput` CLI."""
    return _call_head("train_stats")


def sweep_stats(sweep_id: str | None = None) -> dict:
    """Sweep-engine ledger from the head's journaled ``sweeps`` table:
    per-sweep trial states (gang admission → running → rung-stopped /
    forked / migrated), fork and preemption counters, and each trial's
    live train-job ledger row joined in. Backs the dashboard's
    /api/tune and the `ray_tpu tune` CLI; survives head restart via
    journal replay."""
    return _call_head("sweep_stats", sweep_id=sweep_id)


def serve_stats() -> dict:
    """Per-deployment serve SLO ledger from the head: request/error
    counts, sliding-window TTFT/latency p50/p99, SLO attainment, and
    the burn-rate alert state. Backs the dashboard's /api/serve and the
    `ray_tpu slo` CLI."""
    return _call_head("serve_stats")


def mem_stats() -> dict:
    """Device-memory ledger from the head: per-node current/peak used
    bytes, capacity, headroom alert state, and per-subsystem byte
    attribution, plus per-job peaks. Backs the dashboard's /api/memory
    and the `ray_tpu mem` CLI."""
    return _call_head("mem_stats")


def profile_stats() -> dict:
    """Per-job compiled-program profile from the head: the latest MFU
    decomposition (category shares + dominant gap) and the journaled
    per-signature fingerprints the regression sentinel compares new
    captures against. Backs the dashboard's /api/profile and the
    `ray_tpu profile` CLI."""
    return _call_head("profile_stats")


def profile_capture(steps: int | None = None) -> dict:
    """Ask the head to fan a compiled-program capture request out to
    every rank (collective-channel riders arm their per-step profiler
    hook; reports land in profile_stats after the next
    PROFILE_CAPTURE_STEPS steps)."""
    return _call_head("profile_capture", steps=steps)


def head_stats() -> dict:
    """Head control-plane load stats: telemetry fold-queue depth, shed
    counter, overload alert state, pubsub coalescing counters, and
    journal size/compaction. Backs the dashboard's /api/head and the
    `ray_tpu head` CLI."""
    return _call_head("head_stats")


def list_checkpoints(run: str | None = None) -> dict:
    """In-cluster shard-store checkpoints per run (step, world,
    completeness, bytes, chunk count, min replica count). Backs the
    dashboard's /api/checkpoints and `ray_tpu ckpt ls`."""
    return _call_head("ckpt_list", run=run)


def verify_checkpoints(run: str | None = None) -> dict:
    """Probe every retained checkpoint chunk on its recorded holders;
    reports under-replicated and lost chunks (`ray_tpu ckpt verify`)."""
    return _call_head("ckpt_verify", run=run)


# What an event is made of; everything else on a SPAN event is one of
# the span's attributes.
_EVENT_FIELDS = frozenset(
    ("task_id", "name", "state", "ts", "dur", "worker")
)


def _span_attrs(ev: dict) -> dict:
    return {k: v for k, v in ev.items() if k not in _EVENT_FIELDS}


# ------------------------------------------------- start-up by phase
_STARTUP_COLUMNS = (
    "lease", "chip_free_wait", "spawn", "boot", "first_task", "chip_open",
    "replica_init",
)
_last_startup_report: dict | None = None


def _span_row(ev: dict) -> dict:
    row = _span_attrs(ev)
    for k in ("trace_id", "span_id", "parent_id"):
        row.pop(k, None)
    return {"ts": ev["ts"], "dur": ev["dur"], **row}


def _host(addr: str) -> str:
    return str(addr).rsplit(":", 1)[0]


def startup_report(timeout: float | None = None) -> dict:
    """How every process became useful, from the head's table of
    ``startup:*`` and ``compile:*`` spans: ``driver`` holds this
    driver's own phases (``startup:init`` and its parts, each
    ``startup:entry``, ``startup:http``); ``workers`` one row a worker
    process, oldest first, with its node, pid, platform and lease, each
    phase's seconds (``phases``), the spans themselves with both ends on
    ``time.time()`` (``spans``), and its compiles: requests, cache
    hits and seconds by part (``compiles``), and the newest of them as
    spans (``compile_spans``). A worker that died and was replaced
    keeps its row beside its successor's, so the rows are also the
    record of how long a recovery took. ``same_host`` is False on a row
    whose host is not this driver's: the time BETWEEN spans of two
    processes means something only on one host's clock."""
    processes = _call_head("startup_table", _timeout=timeout)["processes"]
    own_addr = core_api._runtime.core.addr
    report: dict = {"driver": None, "workers": []}
    for key, proc in processes.items():
        spans = proc["spans"]  # by name, or name/entry for an entry
        if key == own_addr:
            report["driver"] = {
                "addr": key,
                "spans": {k: _span_row(ev) for k, ev in spans.items()},
            }
        if ":" in key:  # a driver or a daemon, by address
            continue
        any_ev = next(iter(spans.values()), None) or proc["compiles"][0]
        said = {}  # what the spans say of the process itself
        for ev in spans.values():
            for k in ("node_id", "pid", "platform", "lease_id", "tpu"):
                if k in ev:
                    said[k] = ev[k]
        host = _host(any_ev["worker"])
        report["workers"].append({
            "worker_id": key,
            **said,
            "host": host,
            "same_host": host == _host(own_addr),
            "phases": {
                name.split(":", 1)[1]: ev["dur"]
                for name, ev in spans.items()
            },
            "spans": {k: _span_row(ev) for k, ev in spans.items()},
            "compiles": proc["compile_totals"],
            "compile_spans": [
                {"name": ev["name"], **_span_row(ev)}
                for ev in proc["compiles"]
            ],
        })
    return report


def keep_startup_report() -> None:
    """Take one last :func:`startup_report` while the head can still be
    asked (``ray_tpu.shutdown()`` calls this first) and keep it for
    :func:`last_startup_report`. Best effort: a cluster that is
    already half gone keeps the report before it."""
    global _last_startup_report
    rt = core_api._runtime
    try:
        rt.run(rt.core.flush_observability(), timeout=5)
        if rt.node is not None:
            rt.run(rt.node.flush_spans(), timeout=5)
        _last_startup_report = startup_report(timeout=5)
    # tpulint: allow(broad-except reason=shutdown is best-effort by contract and this is telemetry: a dead head or a stopped loop must not keep the process from exiting)
    except Exception:  # noqa: BLE001
        pass


def last_startup_report() -> dict | None:
    """The :func:`startup_report` taken as this process's last cluster
    shut down; None before any has. For code that runs after the
    cluster is gone (the benchmark's reducers)."""
    return _last_startup_report


def startup_table(report: dict | None = None) -> str:
    """:func:`startup_report` as text: one line for the driver, one a
    worker, seconds by phase; ``*`` marks a row from another host."""

    def secs(v):
        return "-" if v is None else f"{v:.2f}"

    report = startup_report() if report is None else report
    lines = []
    driver = report.get("driver")
    if driver:
        lines.append("driver " + driver["addr"] + "  " + "  ".join(
            f"{name.split(':', 1)[1]} {secs(sp['dur'])}"
            for name, sp in driver["spans"].items()
        ))
    header = ("worker", "node", "pid", "platform", "tpu", "lease_id",
              *_STARTUP_COLUMNS, "compiles", "hits", "trace+lower",
              "backend")
    rows = [header]
    for w in report["workers"]:
        c = w["compiles"]
        rows.append((
            w["worker_id"][:8] + ("" if w["same_host"] else "*"),
            str(w.get("node_id", "?"))[:8], str(w.get("pid", "?")),
            w.get("platform", "?"), f"{w.get('tpu', 0):g}",
            w.get("lease_id", "-"),
            *(secs(w["phases"].get(col)) for col in _STARTUP_COLUMNS),
            str(c["requests"]), str(c["cache_hits"]),
            secs(c["trace_s"] + c["lower_s"]), secs(c["backend_s"]),
        ))
    widths = [max(len(r[i]) for r in rows) for i in range(len(header))]
    lines += ["  ".join(v.ljust(n) for v, n in zip(r, widths)).rstrip()
              for r in rows]
    return "\n".join(lines)


def timeline(path: str | None = None) -> list[dict] | str:
    """Chrome-trace export of task execution spans plus SPAN events —
    collective ops and train step phases render as slices alongside the
    tasks that issued them (reference: `ray timeline`, powered by
    GcsTaskManager events)."""
    events = _call_head("list_task_events", limit=20000, raw=True)["events"]
    trace = []
    for ev in events:
        if ev.get("state") == "SPAN" and "dur" in ev:
            trace.append(
                {
                    "ph": "X",
                    "name": ev.get("name") or "span",
                    "ts": ev["ts"] * 1e6,
                    "dur": ev["dur"] * 1e6,
                    "pid": ev.get("worker", "?"),
                    # Separate track per worker so span slices don't
                    # overlap the task slices they ran inside.
                    "tid": "spans",
                    "args": _span_attrs(ev),
                }
            )
            continue
        if ev.get("state") != "RUNNING" or "dur" not in ev:
            continue
        trace.append(
            {
                "ph": "X",
                "name": ev.get("name") or ev.get("task_id", "")[:8],
                "ts": ev["ts"] * 1e6,
                "dur": ev["dur"] * 1e6,
                "pid": ev.get("worker", "?"),
                "tid": 0,
                "args": {"task_id": ev.get("task_id")},
            }
        )
    if path is None:
        return trace
    with open(path, "w") as f:
        json.dump(trace, f)
    return path
