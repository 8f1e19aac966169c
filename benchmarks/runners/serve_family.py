"""Runner for serving cells of any model family (``"runner":
"serve_family"``, ``"model": <module of benchmarks/models>``):
``runners/serve_model.py``'s run around ``server_family.BenchFamilyServer``,
which asks the family's module for its check and its programs. The
replica's lease, warm-up, load offering, tracing, summary and the
decision of ``correct`` are ``serve_model``'s own, imported; folding the
two runners is a benchmark PR's (ROADMAP D20). This process never opens a
JAX backend."""

from __future__ import annotations

import contextlib
import time
from unittest import mock

from benchmarks.runners import serve_model


@contextlib.contextmanager
def replica(cell: dict, conf: dict, seed: int, rehearsal: bool):
    """``serve_model.replica`` of ``BenchFamilyServer``."""
    import ray_tpu
    from benchmarks.runners import common
    from benchmarks.server_family import BenchFamilyServer
    from ray_tpu import serve

    ray_tpu.init()
    try:
        common.require_chips(cell["chips"], rehearsal)
        called_at = time.time()
        deployment = serve.deployment(
            BenchFamilyServer,
            num_replicas=1,
            ray_actor_options={"num_tpus": cell["chips"]},
            max_ongoing_requests=conf["engine"]["max_batch"],
        )
        try:
            handle = serve.run(deployment.bind(conf, seed), timeout_s=1100)
            port = serve.start_http()
            yield handle, port, called_at, time.time()
        finally:
            serve.shutdown()
        common.wait_chip_free()
    finally:
        ray_tpu.shutdown()


def run(cell: dict, conf: dict, traffic: dict, args, t_start: float) -> dict:
    # `serve_model.run` looks its module's `replica` up when it is
    # called: the one thing of it that names a server class.
    with mock.patch.object(serve_model, "replica", replica):
        return serve_model.run(cell, conf, traffic, args, t_start)
