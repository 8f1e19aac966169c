"""State API, task events, metrics, timeline, and job submission tests.

Reference test models: python/ray/tests/test_state_api.py (list
nodes/actors/tasks), test_metrics_agent.py, dashboard/modules/job tests.
"""

import json
import time

import pytest

import ray_tpu
from ray_tpu.util import metrics, state


@pytest.fixture(scope="module")
def cluster():
    info = ray_tpu.init(num_cpus=4)
    yield info
    ray_tpu.shutdown()


def test_list_nodes(cluster):
    nodes = state.list_nodes()
    assert len(nodes) >= 1
    assert all("CPU" in n["resources"] for n in nodes)


def test_list_actors_and_tasks(cluster):
    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def bump(self):
            self.n += 1
            return self.n

    c = Counter.remote()
    assert ray_tpu.get(c.bump.remote()) == 1

    actors = state.list_actors(state="ALIVE")
    assert any(a["class_name"] == "Counter" for a in actors)

    @ray_tpu.remote
    def named_task():
        return 42

    ray_tpu.get([named_task.remote() for _ in range(3)])
    time.sleep(1.5)  # event flush period
    tasks = state.list_tasks(limit=5000)
    names = [t.get("name") for t in tasks]
    assert "named_task" in names
    finished = [
        t for t in tasks
        if t.get("name") == "named_task" and t.get("state") == "FINISHED"
    ]
    assert len(finished) >= 3

    summary = state.summarize_tasks()
    assert summary.get("FINISHED", 0) >= 3


def test_task_events_record_failures(cluster):
    @ray_tpu.remote(max_retries=0)
    def boom():
        raise ValueError("intentional")

    with pytest.raises(Exception):
        ray_tpu.get(boom.remote())
    time.sleep(1.5)
    failed = state.list_tasks(state="FAILED")
    assert any(t.get("name") == "boom" for t in failed)


def test_timeline_export(cluster, tmp_path):
    @ray_tpu.remote
    def sleepy():
        time.sleep(0.05)
        return 1

    ray_tpu.get([sleepy.remote() for _ in range(2)])
    time.sleep(1.5)
    path = state.timeline(str(tmp_path / "trace.json"))
    trace = json.load(open(path))
    spans = [e for e in trace if e["name"] == "sleepy"]
    assert len(spans) >= 2
    assert all(e["ph"] == "X" and e["dur"] > 0 for e in spans)


def test_metrics_local_and_prometheus(cluster):
    metrics.clear_registry()
    c = metrics.Counter("test_requests_total", "reqs", tag_keys=("route",))
    c.inc(2, tags={"route": "/a"})
    c.inc(1, tags={"route": "/b"})
    g = metrics.Gauge("test_queue_depth", "depth")
    g.set(7)
    h = metrics.Histogram(
        "test_latency_s", "lat", boundaries=(0.1, 1.0), tag_keys=()
    )
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)

    merged = state.cluster_metrics()
    assert merged["test_requests_total"]["series"]['route="/a"'] == 2
    text = state.prometheus_metrics()
    assert "# TYPE test_requests_total counter" in text
    assert 'test_latency_s_bucket{le="0.1"} 1' in text
    assert "test_latency_s_count 3" in text
    assert "test_queue_depth" in text


def test_metrics_from_workers(cluster):
    @ray_tpu.remote
    def work(i):
        from ray_tpu.util import metrics as wm

        counter = wm.Counter("test_worker_units", "units")
        counter.inc(10)
        time.sleep(1.5)  # survive until the flush loop runs
        return i

    ray_tpu.get([work.remote(i) for i in range(2)])
    merged = state.cluster_metrics()
    rec = merged.get("test_worker_units")
    assert rec is not None
    assert sum(rec["series"].values()) >= 20


def test_job_submission_roundtrip(cluster):
    from ray_tpu.job import JobSubmissionClient

    client = JobSubmissionClient()
    job_id = client.submit_job(
        entrypoint="python -c \"print('job ran ok')\"",
    )
    status = client.wait_until_finish(job_id, timeout=60)
    assert status == "SUCCEEDED"
    assert "job ran ok" in client.get_job_logs(job_id)
    jobs = client.list_jobs()
    assert any(j["job_id"] == job_id for j in jobs)


def test_job_failure_and_stop(cluster):
    from ray_tpu.job import JobSubmissionClient

    client = JobSubmissionClient()
    bad = client.submit_job(entrypoint="python -c 'raise SystemExit(3)'")
    assert client.wait_until_finish(bad, timeout=60) == "FAILED"

    slow = client.submit_job(entrypoint="sleep 60")
    time.sleep(0.5)
    assert client.stop_job(slow) is True
    assert client.get_job_status(slow) in ("STOPPED", "FAILED")


def test_metrics_registry_reregistration():
    """Re-registering a name with an identical shape returns the live
    instance (series preserved); any mismatch raises instead of
    silently clobbering the first metric's series."""
    c1 = metrics.Counter("rereg_total", "d", tag_keys=("a",))
    c1.inc(3, tags={"a": "x"})
    c2 = metrics.Counter("rereg_total", "d", tag_keys=("a",))
    assert c2 is c1
    assert c2.value(tags={"a": "x"}) == 3
    with pytest.raises(ValueError):
        metrics.Counter("rereg_total", "d", tag_keys=("b",))
    with pytest.raises(ValueError):  # same name, different kind
        metrics.Gauge("rereg_total", "d", tag_keys=("a",))
    h1 = metrics.Histogram("rereg_hist", "d", boundaries=(1.0, 2.0))
    h1.observe(1.5)
    assert metrics.Histogram("rereg_hist", "d", boundaries=(2.0, 1.0)) is h1
    with pytest.raises(ValueError):
        metrics.Histogram("rereg_hist", "d", boundaries=(1.0, 3.0))


def test_prometheus_exposition_hygiene():
    """Hostile label values and HELP text cannot corrupt the scrape:
    quotes/backslashes/newlines are escaped, HELP stays one line."""
    g = metrics.Gauge("escape_gauge", "line1\nline2", tag_keys=("k",))
    g.set(1.0, tags={"k": 'a"b\\c\nd'})
    text = metrics.prometheus_text(
        metrics.merge_snapshots({"w\n1": metrics.snapshot()})
    )
    lines = text.splitlines()
    series = [l for l in lines if l.startswith("escape_gauge{")]
    assert len(series) == 1
    assert '\\"' in series[0] and "\\\\" in series[0]
    assert "\\n" in series[0]
    help_line = next(l for l in lines if l.startswith("# HELP escape_gauge"))
    assert "line1 line2" in help_line
    # round-trip: the escaped tag string parses back to the raw value
    tags = metrics.parse_tag_str('k="a\\"b\\\\c\\nd"')
    assert tags["k"] == 'a"b\\c\nd'


def test_collective_flight_recorder(cluster):
    """Every collective verb records latency/bytes/bus-bandwidth and a
    timeline SPAN (driver-side world-1 CPU group: no flush wait)."""
    import numpy as np

    from ray_tpu import collective as col
    from ray_tpu.collective import flight_recorder as fr
    from ray_tpu.util import tracing

    col.init_collective_group(1, 0, backend="cpu", group_name="fr1")
    try:
        col.allreduce(np.ones(1024, np.float32), group_name="fr1")
        lat = fr.OP_LATENCY.value(
            tags={"group": "fr1", "verb": "allreduce", "backend": "cpu"}
        )
        assert lat is not None and lat[2] >= 1  # observation count
        assert (
            fr.OP_BYTES.value(
                tags={"group": "fr1", "verb": "allreduce",
                      "dtype": "float32"}
            )
            >= 4096
        )
        # The driver's snapshot rides the 1 Hz flush to the head; push
        # it eagerly so the cluster-wide scrape is deterministic here.
        rt = ray_tpu.api._runtime
        rt.run(rt.core.flush_observability())
        text = state.prometheus_metrics()
        assert (
            "# TYPE ray_tpu_collective_op_latency_seconds histogram"
            in text
        )
        assert "ray_tpu_collective_bus_bandwidth_bytes_per_s" in text
        assert "ray_tpu_collective_bytes_total" in text
        deadline = time.time() + 20
        while time.time() < deadline:
            spans = tracing.get_trace_events()
            hits = [
                s for s in spans
                if s.get("name") == "collective:allreduce"
                and s.get("group") == "fr1"
            ]
            if hits:
                break
            time.sleep(0.3)
        assert hits, "no collective SPAN reached the head"
        assert hits[0]["bytes"] == 4096
    finally:
        col.destroy_collective_group("fr1")


def test_trace_context_through_collective_in_actor(cluster):
    """A collective op issued inside a traced actor task parents its
    span under the task's execution span (same trace, linked parent)."""
    from ray_tpu.util import tracing

    tracing.enable_tracing()
    try:
        @ray_tpu.remote
        class ColActor:
            def run_op(self):
                import numpy as np

                from ray_tpu import collective as col

                col.init_collective_group(
                    1, 0, backend="cpu", group_name="trace_g"
                )
                try:
                    col.allreduce(
                        np.ones(8, np.float32), group_name="trace_g"
                    )
                finally:
                    col.destroy_collective_group("trace_g")
                return True

        a = ColActor.remote()
        assert ray_tpu.get(a.run_op.remote(), timeout=60)
        task_span = col_span = None
        deadline = time.time() + 20
        while time.time() < deadline:
            spans = tracing.get_trace_events()
            task_span = next(
                (s for s in spans
                 if str(s.get("name", "")).endswith("run_op")), None
            )
            col_span = next(
                (s for s in spans
                 if s.get("name") == "collective:allreduce"
                 and s.get("group") == "trace_g"), None
            )
            if task_span and col_span:
                break
            time.sleep(0.3)
        assert task_span and col_span, "spans did not reach the head"
        assert col_span["trace_id"] == task_span["trace_id"]
        assert col_span["parent_id"] == task_span["span_id"]
        ray_tpu.kill(a)  # free its CPU for the trainer tests below
    finally:
        tracing.disable_tracing()


def test_goodput_accounting_across_elastic_restart(cluster):
    """Attempt 0 dies mid-step, attempt 1 finishes: the head's per-job
    ledger shows goodput < 1 and restart-lost time > 0, and the train
    metrics reach the Prometheus surface."""
    import os

    from ray_tpu._private import config as _config
    from ray_tpu.train import (
        FailureConfig,
        JaxTrainer,
        RunConfig,
        ScalingConfig,
    )
    import ray_tpu.train as train

    def loop(config):
        import time as t

        import ray_tpu.train as train

        ctx = train.get_context()
        for i in range(3):
            with train.step_span(flops=1e9) as s:
                with s.phase("data_wait"):
                    t.sleep(0.01)
                with s.phase("compute"):
                    t.sleep(0.05)
            train.report({"i": i})
            if ctx.attempt == 0 and i == 1:
                t.sleep(0.03)
                raise RuntimeError("attempt 0 dies mid-step")

    # Short settle window so the retry doesn't wait the default 30s
    # node-death ageout (same knob test_elastic_train uses).
    _config.set_system_config({"HEALTH_TIMEOUT_S": 4.0})
    try:
        trainer = JaxTrainer(
            loop,
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(
                name="goodput_exp",
                storage_path="/tmp/ray_tpu_test_goodput",
                failure_config=FailureConfig(max_failures=1),
            ),
        )
        result = trainer.fit()
        assert result.error is None
    finally:
        _config.clear_system_config("HEALTH_TIMEOUT_S")
    job = None
    deadline = time.time() + 20
    while time.time() < deadline:
        job = state.train_stats().get("jobs", {}).get("goodput_exp")
        if job and job["attempts"] >= 2 and job["steps"] >= 5:
            break
        time.sleep(0.4)
    assert job, "head never saw the train job"
    assert job["attempts"] == 2
    assert job["steps"] >= 5
    assert job["restart_lost_s"] > 0
    assert 0 < job["goodput"] < 1
    assert job["mfu"] and job["mfu"] > 0
    assert job["phase_s"].get("compute", 0) > 0
    text = state.prometheus_metrics()
    assert 'ray_tpu_train_goodput_ratio{job="goodput_exp"' in text
    assert "ray_tpu_train_mfu" in text
    assert "ray_tpu_train_restart_lost_seconds" in text
    # the dashboard route serves the same ledger over HTTP
    import json as _json
    import urllib.request

    from ray_tpu.dashboard import start_dashboard

    dash = start_dashboard()
    try:
        with urllib.request.urlopen(dash.url + "/api/train") as r:
            body = _json.loads(r.read())
    finally:
        dash.stop()
    assert body["jobs"]["goodput_exp"]["restart_lost_s"] > 0


def test_trainer_timeline_has_collective_and_phase_slices(cluster):
    """`ray_tpu timeline` from a real JaxTrainer run renders collective
    ops and train step phases as slices alongside tasks."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    import ray_tpu.train as train

    def loop(config):
        import numpy as np

        import ray_tpu.train as train
        from ray_tpu import collective as col

        ctx = train.get_context()
        gname = f"tl{ctx.attempt}"
        col.init_collective_group(
            2, ctx.get_world_rank(), backend="cpu", group_name=gname
        )
        try:
            for i in range(2):
                with train.step_span(tokens=128, flops_per_token=1e6) as s:
                    with s.phase("data_wait"):
                        x = np.ones(64, np.float32)
                    with s.phase("collective"):
                        col.allreduce(x, group_name=gname)
                train.report({"i": i})
        finally:
            col.destroy_collective_group(gname)

    trainer = JaxTrainer(
        loop,
        # Fractional CPUs: earlier tests in this module leak actors, so
        # don't require 2 whole free cores for the gang.
        scaling_config=ScalingConfig(
            num_workers=2, resources_per_worker={"CPU": 0.5}
        ),
        run_config=RunConfig(
            name="tl_exp", storage_path="/tmp/ray_tpu_test_timeline"
        ),
    )
    result = trainer.fit()
    assert result.error is None
    names: set = set()
    deadline = time.time() + 20
    while time.time() < deadline:
        names = {e["name"] for e in state.timeline()}
        if "collective:allreduce" in names and "train:step" in names:
            break
        time.sleep(0.4)
    assert "collective:allreduce" in names
    assert "train:step" in names
    assert "train:collective" in names
    assert "train:attempt" in names
    # collective slices carry their bandwidth accounting as args
    slc = next(
        e for e in state.timeline()
        if e["name"] == "collective:allreduce"
        and e["args"].get("group") == "tl0"
    )
    assert slc["args"].get("bytes") == 64 * 4


def test_chronic_straggler_surfaces_to_autoscaler(cluster):
    """collective_straggler_total resolves rank→node on the head, and
    the autoscaler flags a node past the threshold (log + metric)."""
    rt = ray_tpu.api._runtime
    nodes = state.list_nodes()
    nid, node_addr = nodes[0]["node_id"], nodes[0]["addr"]
    rt.run(
        rt.core.head.call(
            "collective_register",
            group="sg", rank=0, epoch=0, addr="fake",
            node_addr=node_addr, worker_id="w_straggle",
        )
    )
    snap = {
        "collective_straggler_total": {
            "kind": "counter",
            "description": "",
            "series": {'group="sg",rank="0"': 25.0},
            "boundaries": None,
        }
    }
    rt.run(
        rt.core.head.call(
            "report_metrics", worker="fake_hub", metrics=snap
        )
    )
    try:
        stats = rt.run(rt.core.head.call("collective_straggler_stats"))
        assert stats["nodes"].get(nid) == 25.0
        assert stats["groups"]["sg"]["0"] == 25.0

        from ray_tpu.autoscaler.autoscaler import (
            _CHRONIC_STRAGGLER,
            Autoscaler,
        )

        asc = Autoscaler.__new__(Autoscaler)  # flagging logic only
        asc.straggler_threshold = 20
        asc._flagged_stragglers = set()
        chronic = asc._check_stragglers(asc._straggler_node_counts())
        assert chronic.get(nid) == 25.0
        assert nid in asc._flagged_stragglers
        assert _CHRONIC_STRAGGLER.value(tags={"node": nid}) == 25.0
    finally:
        rt.run(rt.core.head.call("collective_deregister", group="sg"))


# ---------------------------------------------------------------------
# Serve request-path observability (PR 9): end-to-end trace trees, the
# head SLO ledger, comm-exposure attribution, and the disabled-path
# perf floor.
# ---------------------------------------------------------------------


def test_hier_busbw_derives_from_wire_bytes_only():
    """hier_allreduce busbw must come from MEASURED wire bytes; without
    them the gauge falls back to algbw (bytes/dur), never the flat
    2(n-1)/n factor that over-reports under int8-DCN compression."""
    import numpy as np

    from ray_tpu.collective import flight_recorder as fr

    arr = np.ones(1024, np.float32)  # 4096 logical bytes
    fr.record_op(
        "bw_hier1", "hier_allreduce", "xla_mesh", 8, arr,
        time.time(), 0.001, wire_bytes=2048,
    )
    tags = {"group": "bw_hier1", "verb": "hier_allreduce",
            "dtype": "float32"}
    assert fr.BUS_BANDWIDTH.value(tags=tags) == pytest.approx(
        2048 / 0.001
    )
    fr.record_op(
        "bw_hier2", "hier_allreduce", "xla_mesh", 8, arr,
        time.time(), 0.001,
    )
    tags2 = {"group": "bw_hier2", "verb": "hier_allreduce",
             "dtype": "float32"}
    assert fr.BUS_BANDWIDTH.value(tags=tags2) == pytest.approx(
        4096 / 0.001
    )
    # The factor table no longer speaks for the hierarchical op at all.
    assert "hier_allreduce" not in fr._BUS_FACTORS


def test_comm_exposed_attribution(cluster):
    """A collective op inside a step but OUTSIDE the compute phase is
    exposed; interval math handles overlap; the gauge and head ledger
    both report it."""
    import numpy as np

    import ray_tpu.train as train
    from ray_tpu import collective as col
    from ray_tpu.collective import flight_recorder as fr
    from ray_tpu.train import session, telemetry
    from ray_tpu.train.session import TrainContext

    # Interval units.
    assert telemetry._merge_intervals([(0, 2), (1, 3), (5, 6)]) == [
        (0, 3), (5, 6)
    ]
    assert telemetry._overlap_seconds([(0, 3), (5, 6)], [(1, 2), (5.5, 8)]) \
        == pytest.approx(1.5)
    exposed, overlapped = 0.0, 0.0

    fr.take_op_intervals()  # drain earlier tests' ops
    col.init_collective_group(1, 0, backend="cpu", group_name="ce1")
    session._set_context(TrainContext(experiment_name="comm_exp"))
    try:
        with train.step_span(flops=1e6) as s:
            with s.phase("compute"):
                time.sleep(0.02)
            with s.phase("collective"):
                col.allreduce(np.ones(256, np.float32), group_name="ce1")
    finally:
        session._set_context(None)
        col.destroy_collective_group("ce1")
    ratio = telemetry.COMM_EXPOSED_RATIO.value(tags={"job": "comm_exp"})
    assert ratio is not None and ratio > 0
    rt = ray_tpu.api._runtime
    rt.run(rt.core.flush_observability())
    job = None
    deadline = time.time() + 20
    while time.time() < deadline:
        job = state.train_stats().get("jobs", {}).get("comm_exp")
        if job and job.get("comm_exposed_s", 0) > 0:
            break
        time.sleep(0.3)
    assert job, "head never saw the comm_exp job"
    assert job["comm_exposed_s"] > 0
    assert job["comm_overlapped_s"] == pytest.approx(0.0)
    assert 0 < job["comm_exposed_ratio"] <= 1


def _sse_request(port, path, body, headers=None, timeout=60):
    """Minimal raw-socket SSE client: returns the data-frame payloads."""
    import socket

    payload = json.dumps(body).encode()
    req = (
        f"POST {path} HTTP/1.1\r\n"
        f"Host: 127.0.0.1\r\n"
        f"Accept: text/event-stream\r\n"
        f"Content-Length: {len(payload)}\r\n"
    )
    for k, v in (headers or {}).items():
        req += f"{k}: {v}\r\n"
    req += "\r\n"
    raw = b""
    with socket.create_connection(("127.0.0.1", port), timeout=timeout) as s:
        s.sendall(req.encode() + payload)
        while b"data: [DONE]" not in raw and b"event: error" not in raw:
            chunk = s.recv(65536)
            if not chunk:
                break
            raw += chunk
    assert b"200 OK" in raw, raw[:200]
    return [
        ln[len("data: "):]
        for ln in raw.decode("utf-8", "replace").splitlines()
        if ln.startswith("data: ")
    ]


def test_serve_request_tracing_end_to_end(cluster):
    """A streamed LLM request through proxy → replica → engine yields
    ONE connected trace (shared trace_id, correct parentage) whose
    prefill span and TTFT are bounded below by the injected prefill
    delay, with per-deployment TTFT percentiles visible via the
    serve_stats RPC."""
    from ray_tpu import serve
    from ray_tpu._private import config as _config
    from ray_tpu.llm.serve_integration import build_llm_deployment
    from ray_tpu.util import tracing

    delay = 0.6
    try:
        app = build_llm_deployment(
            "tiny",
            # prefill_delay_s: deterministic TTFT injection (the engine
            # kwarg reaches the replica regardless of worker reuse).
            engine_kwargs={"max_batch": 2, "prefill_delay_s": delay},
            ray_actor_options={"num_cpus": 0.1},
        )
        serve.run(app, name="llm_obs", route_prefix="/llmobs",
                  timeout_s=180)
        port = serve.start_http()
        # Warmup pays the first-compile cost so the timed request's
        # TTFT is delay-dominated, not compile-dominated.
        _sse_request(
            port, "/llmobs",
            {"prompt": "warm", "max_tokens": 4, "stream": True},
        )
        rid = "e2e-trace-0001"
        frames = _sse_request(
            port, "/llmobs",
            {"prompt": "hello", "max_tokens": 8, "stream": True},
            headers={"X-Request-Id": rid},
        )
        assert frames[-1] == "[DONE]"

        wanted = {"serve:ingress", "serve:queue", "serve:replica",
                  "serve:prefill", "serve:decode"}
        tree = {}
        deadline = time.time() + 25
        while time.time() < deadline:
            spans = tracing.get_trace_events(limit=5000)
            ingress = next(
                (s for s in spans
                 if s.get("name") == "serve:ingress"
                 and s.get("request_id") == rid), None,
            )
            if ingress is not None:
                same = [
                    s for s in spans
                    if s.get("trace_id") == ingress["trace_id"]
                ]
                if wanted <= {s.get("name") for s in same}:
                    tree = {s["name"]: s for s in same}
                    break
            time.sleep(0.4)
        assert tree, "connected request span tree never reached the head"

        ingress = tree["serve:ingress"]
        assert ingress["parent_id"] == ""
        assert ingress["deployment"] == "LLMServer"
        assert ingress["app"] == "llm_obs"
        assert ingress["status"] == 200 and ingress["streamed"]
        # Parentage: queue + replica under ingress; engine phases under
        # the replica span.
        assert tree["serve:queue"]["parent_id"] == ingress["span_id"]
        replica = tree["serve:replica"]
        assert replica["parent_id"] == ingress["span_id"]
        assert tree["serve:prefill"]["parent_id"] == replica["span_id"]
        assert tree["serve:decode"]["parent_id"] == replica["span_id"]
        # TTFT bounded by the injected prefill delay (tolerance covers
        # a warm prefill + routing, never a cold compile).
        assert ingress["ttft_s"] >= delay
        assert ingress["ttft_s"] < delay + 5.0
        assert tree["serve:prefill"]["dur"] >= delay
        assert tree["serve:decode"]["tokens"] == 8

        # timeline() renders the request tree (span args included).
        tl = next(
            e for e in state.timeline()
            if e["name"] == "serve:ingress"
            and e["args"].get("request_id") == rid
        )
        assert tl["args"]["trace_id"] == ingress["trace_id"]

        # Per-deployment ledger via the serve_stats RPC.
        dep = state.serve_stats()["deployments"].get("llm_obs/LLMServer")
        assert dep is not None and dep["requests"] >= 2
        assert dep["streamed"] >= 2
        assert dep["ttft_p50_s"] is not None
        assert dep["ttft_p99_s"] >= delay
    finally:
        serve.delete("llm_obs")


def test_serve_slo_alert_transitions(cluster):
    """The head SLO ledger flips ray_tpu_serve_slo_alert OFF→ON under
    sustained SLO misses (injected backlog) and clears once the window
    drains to attaining traffic."""
    from ray_tpu._private import config as _config

    rt = ray_tpu.api._runtime

    def feed(n, ts, ttft, status=200):
        events = [
            {
                "task_id": f"span:slo{ts}-{i}",
                "name": "serve:ingress",
                "state": "SPAN",
                "ts": ts + i * 0.01,
                "dur": ttft,
                "deployment": "dep1",
                "app": "slo_app",
                "status": status,
                "ttft_s": ttft,
                "streamed": True,
                "items": 1,
            }
            for i in range(n)
        ]
        rt.run(rt.core.head.call("add_task_events", events=events))

    def dep_stats():
        return rt.run(rt.core.head.call("serve_stats"))["deployments"][
            "slo_app/dep1"
        ]

    _config.set_system_config({
        "SERVE_SLO_TTFT_S": 0.1,
        "SERVE_SLO_TARGET": 0.9,
        "SERVE_SLO_WINDOW_S": 10.0,
    })
    try:
        base = time.time()
        feed(10, base, ttft=0.01)  # healthy traffic
        st = dep_stats()
        assert st["alert"] is False and st["attainment"] == 1.0
        # Sustained backlog: TTFT blows through the target → ON.
        feed(10, base + 1, ttft=2.0)
        st = dep_stats()
        assert st["alert"] is True
        assert st["attainment"] == pytest.approx(0.5)
        assert st["ttft_p99_s"] >= 2.0
        # The alert gauge reaches the Prometheus surface from the head.
        text = state.prometheus_metrics()
        line = next(
            ln for ln in text.splitlines()
            if ln.startswith("ray_tpu_serve_slo_alert")
            and 'deployment="slo_app/dep1"' in ln
        )
        assert line.endswith(" 1.0")
        # Backlog drains: a window of attaining requests past the
        # cutoff evicts the misses → OFF.
        feed(20, base + 30, ttft=0.01)
        st = dep_stats()
        assert st["alert"] is False and st["attainment"] == 1.0
    finally:
        _config.clear_system_config(
            "SERVE_SLO_TTFT_S", "SERVE_SLO_TARGET", "SERVE_SLO_WINDOW_S"
        )


# Disabled-path budget for serve request telemetry: begin_request +
# scope enter/exit + first_byte + finish with RAY_TPU_SERVE_TELEMETRY=0
# — the exact hooks the proxy runs per request. 50µs is <5% of even a
# 1ms echo round trip (the proxy's floor is ~2ms), mirroring PR 2's
# step-telemetry budget.
SERVE_TELEMETRY_DISABLED_CEILING_S = 50e-6


def test_serve_telemetry_disabled_perf_floor():
    from ray_tpu._private import config as _config
    from ray_tpu.serve import telemetry as stel

    headers = {"accept": "text/event-stream", "x-request-id": "perf"}
    _config.set_system_config({"SERVE_TELEMETRY": False})
    try:
        for _ in range(100):  # warmup (lazy imports, bytecode)
            tel = stel.begin_request(headers)
            with tel:
                pass
            tel.first_byte()
            tel.finish("a", "d", "/r", 200)
        assert stel.begin_request(headers) is stel.NOOP_REQUEST
        n = 2000
        t0 = time.perf_counter()
        for _ in range(n):
            tel = stel.begin_request(headers)
            with tel:
                pass
            tel.first_byte()
            tel.finish("a", "d", "/r", 200)
        per_req = (time.perf_counter() - t0) / n
    finally:
        _config.clear_system_config("SERVE_TELEMETRY")
    assert per_req < SERVE_TELEMETRY_DISABLED_CEILING_S, (
        f"disabled-path serve telemetry costs {per_req * 1e6:.1f}µs/req "
        f"(budget {SERVE_TELEMETRY_DISABLED_CEILING_S * 1e6:.0f}µs) — "
        "instrumentation is taxing the request path"
    )


def test_serve_api_and_slo_cli_smoke(cluster, capsys, monkeypatch):
    """Tier-1 smoke: dashboard /api/serve returns schema-complete JSON
    and `ray_tpu slo` renders the same ledger (both fed by the SLO
    test's synthetic traffic earlier in this module)."""
    import urllib.request

    from ray_tpu import scripts
    from ray_tpu.dashboard import start_dashboard

    dash = start_dashboard()
    try:
        with urllib.request.urlopen(dash.url + "/api/serve") as r:
            body = json.loads(r.read())
    finally:
        dash.stop()
    assert "deployments" in body and body["deployments"]
    required = {
        "requests", "errors", "streamed", "items", "window_requests",
        "ttft_p50_s", "ttft_p99_s", "latency_p50_s", "latency_p99_s",
        "attainment", "alert", "first_ts", "last_ts",
    }
    for name, dep in body["deployments"].items():
        assert required <= set(dep), (name, sorted(dep))
    assert "slo_app/dep1" in body["deployments"]

    # CLI wiring: `ray_tpu slo` end to end minus the daemon connect.
    monkeypatch.setattr(scripts, "_connect", lambda *a, **k: None)
    rc = scripts.main(["slo"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "slo_app/dep1" in out
    assert "attainment=" in out and "ttft p50=" in out
    rc = scripts.main(["slo", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and "slo_app/dep1" in out


def test_job_driver_connects_to_cluster(cluster, tmp_path):
    """A submitted driver can init against the running cluster via env."""
    from ray_tpu.job import JobSubmissionClient

    script = tmp_path / "driver.py"
    script.write_text(
        "import ray_tpu\n"
        "ray_tpu.init()\n"  # picks up RAY_TPU_ADDRESS from env
        "@ray_tpu.remote\n"
        "def f(x):\n"
        "    return x * 2\n"
        "print('driver result', ray_tpu.get(f.remote(21)))\n"
        "ray_tpu.shutdown()\n"
    )
    client = JobSubmissionClient()
    job_id = client.submit_job(entrypoint=f"python {script}")
    status = client.wait_until_finish(job_id, timeout=120)
    logs = client.get_job_logs(job_id)
    assert status == "SUCCEEDED", logs
    assert "driver result 42" in logs
