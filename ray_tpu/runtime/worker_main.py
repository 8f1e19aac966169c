"""Worker process entry point (reference: the default_worker.py the raylet
execs, python/ray/_private/workers/default_worker.py + worker_pool.h:280).

Spawned by the node manager with connection info in env vars; registers
back, then serves tasks until told to exit or the node dies.
"""

from __future__ import annotations

import asyncio
import os
import sys
import time

# argv[1] of a worker started for one lease that holds TPU > 0
# (node._get_chip_worker), real chips or fake.
CHIP_LEASE_ARG = "--chip-lease"


def _seconds_since_exec() -> float | None:
    """How long ago the OS started this process (the interpreter's own
    start and the import of this package lie in between); None where
    /proc does not say."""
    try:
        with open("/proc/self/stat") as f:
            started_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        since_boot = time.clock_gettime(time.CLOCK_BOOTTIME)
        return since_boot - started_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return None


async def main() -> None:
    began = time.time()
    exec_s = _seconds_since_exec()
    # tpulint: allow(TPU703 reason=worker bootstrap vars are passed by the spawner via env before any config exists)
    if os.environ.get("JAX_PLATFORMS") == "tpu":
        # Started for a lease of real chips (node._get_chip_worker):
        # fix the platform before any code here can create a backend.
        from ray_tpu._private import chip

        chip.hold_chip()
    elif CHIP_LEASE_ARG in sys.argv[1:]:
        # The same lease of fake chips: the backend that opens is the
        # CPU's, and it is watched like the chip's.
        from ray_tpu._private import chip

        chip.watch_startup()
    from ray_tpu.runtime.core_worker import CoreWorker
    from ray_tpu.util import tracing
    import ray_tpu.api as api

    imported = time.time()

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
    started = time.time()
    await core.node.call(
        "register_worker", worker_id=worker_id, addr=addr, pid=os.getpid()
    )
    core.registered_at = time.time()
    attrs = {} if exec_s is None else {"exec_s": exec_s}
    tracing.emit_worker_span(
        "startup:boot", began, core.registered_at - began, pid=os.getpid(),
        imports_s=imported - began, core_start_s=started - imported,
        **attrs,
    )
    # Serve until the node connection drops (node death ⇒ worker exit).
    while not core.node._closed:
        await asyncio.sleep(0.5)
    sys.exit(0)


if __name__ == "__main__":
    asyncio.run(main())
