"""Worker process entry point (reference: the default_worker.py the raylet
execs, python/ray/_private/workers/default_worker.py + worker_pool.h:280).

Spawned by the node manager with connection info in env vars; registers
back, then serves tasks until told to exit or the node dies.
"""

from __future__ import annotations

import asyncio
import os
import sys


async def main() -> None:
    # tpulint: allow(TPU703 reason=worker bootstrap vars are passed by the spawner via env before any config exists)
    if os.environ.get("JAX_PLATFORMS") == "tpu":
        # Started for a lease of real chips (node._get_chip_worker):
        # fix the platform before any code here can create a backend.
        from ray_tpu._private import chip

        chip.hold_chip()
    from ray_tpu.runtime.core_worker import CoreWorker
    import ray_tpu.api as api

    # Process bootstrap: env is the only channel the spawning node
    # agent has into a fresh worker — no config registry exists yet.
    # tpulint: allow(TPU703 reason=worker bootstrap vars are passed by the spawner via env before any config exists)
    head_addr = os.environ["RAY_TPU_HEAD_ADDR"]
    # tpulint: allow(TPU703 reason=worker bootstrap vars are passed by the spawner via env before any config exists)
    node_addr = os.environ["RAY_TPU_NODE_ADDR"]
    # tpulint: allow(TPU703 reason=worker bootstrap vars are passed by the spawner via env before any config exists)
    store_dir = os.environ["RAY_TPU_STORE_DIR"]
    # tpulint: allow(TPU703 reason=worker bootstrap vars are passed by the spawner via env before any config exists)
    worker_id = os.environ["RAY_TPU_WORKER_ID"]

    core = CoreWorker(
        mode="worker",
        head_addr=head_addr,
        node_addr=node_addr,
        store_dir=store_dir,
        worker_id=worker_id,
    )
    addr = await core.start()
    api._attach_worker(core, asyncio.get_running_loop())
    await core.node.call(
        "register_worker", worker_id=worker_id, addr=addr, pid=os.getpid()
    )
    # Serve until the node connection drops (node death ⇒ worker exit).
    while not core.node._closed:
        await asyncio.sleep(0.5)
    sys.exit(0)


if __name__ == "__main__":
    asyncio.run(main())
