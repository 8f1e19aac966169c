"""Node manager: per-host daemon — worker pool + lease scheduling.

Mirrors the reference raylet's local responsibilities (reference:
src/ray/raylet/node_manager.h:140 `HandleRequestWorkerLease`,
worker_pool.h:280): it spawns/caches Python worker processes, grants
worker leases against local resource accounting, queues infeasible
requests, reaps dead workers, and owns the node's shared-memory object
store directory. TPU twist: TPU chips are first-class resources — the
node detects them from the JAX runtime / environment and registers
"TPU" alongside "CPU" (reference handles TPU via a Python plugin,
python/ray/_private/accelerators/tpu.py).
"""

from __future__ import annotations

import asyncio
import collections
import hashlib
import json
import logging
import os
import subprocess
import sys
import tempfile
import time
import uuid
from pathlib import Path
from typing import Any

from ray_tpu._private import rpc
from ray_tpu._private.ids import NodeID, WorkerID

logger = logging.getLogger(__name__)

IDLE_WORKER_CAP = 4  # idle processes kept warm per node
SPAWN_TIMEOUT_S = 30.0
# How long a chip lease, and stop() after its SIGKILLs, wait for a chip
# that is being let go: the kernel goes on closing a dead holder's
# device nodes for up to 23.5 s after its pid is gone (PERF.md section 7).
CHIP_FREE_TIMEOUT_S = 60.0
# stop(): what all children together get between SIGTERM and SIGKILL.
STOP_TERM_S = 2.0
PENDING_SPILL_S = 2.0  # queued lease age before bouncing to spillback


def _wait_all(
    procs: "list[subprocess.Popen]", deadline: float
) -> "list[subprocess.Popen]":
    """Reap ``procs`` until ``deadline`` (``time.monotonic()``); those
    still alive then. Blocks: call it off the event loop."""
    alive = []
    for proc in procs:
        try:
            proc.wait(timeout=max(0.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            alive.append(proc)
    return alive


_mem_frac_cache: "tuple[float, float]" = (-1.0, 0.0)  # (ts, value)


def system_memory_fraction() -> float:
    """Fraction of system memory in use, cgroup-aware like the
    reference's MemoryMonitor (reference: memory_monitor.h:52 reads
    cgroup limits before /proc/meminfo). Test override:
    RAY_TPU_FAKE_MEMORY_FRAC_FILE names a file holding a float.

    Cached process-wide for 200 ms: parsing /proc/meminfo costs ~1 ms
    and every node-manager loop (memory monitor, spill) polls it — at
    scale-simulation density (hundreds of NodeManagers per process)
    the uncached reads alone ate ~7% of the core (PROFILE_r05.md)."""
    import time as _time

    from ray_tpu._private import config

    fake = config.get("FAKE_MEMORY_FRAC_FILE")
    if fake:
        try:
            with open(fake) as f:
                return float(f.read().strip())
        except (OSError, ValueError):
            return 0.0
    global _mem_frac_cache
    ts, cached = _mem_frac_cache
    now = _time.monotonic()
    if now - ts < 0.2:
        return cached
    value = _read_memory_fraction()
    _mem_frac_cache = (now, value)
    return value


def _read_memory_fraction() -> float:
    # cgroup v2 (container limits beat host totals)
    try:
        with open("/sys/fs/cgroup/memory.max") as f:
            limit = f.read().strip()
        if limit != "max":
            with open("/sys/fs/cgroup/memory.current") as f:
                current = float(f.read().strip())
            return current / float(limit)
    except (OSError, ValueError):
        pass
    try:
        info = {}
        with open("/proc/meminfo") as f:
            for line in f:
                parts = line.split()
                info[parts[0].rstrip(":")] = float(parts[1])
        total = info["MemTotal"]
        avail = info.get("MemAvailable", info.get("MemFree", total))
        return 1.0 - avail / total
    except (OSError, KeyError, ValueError):
        return 0.0


def worker_rss_bytes(pid: int) -> int:
    try:
        with open(f"/proc/{pid}/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return 0


def _spill_watermarks() -> tuple[float, float]:
    """Object-spilling watermarks (fractions of store capacity): above
    HIGH the daemon moves cold objects to disk until usage drops below
    LOW (reference: LocalObjectManager triggers spilling at
    object_spilling_threshold, local_object_manager.h:44). Read per
    tick so per-process overrides apply."""
    from ray_tpu._private import config

    return (config.get("SPILL_HIGH"), config.get("SPILL_LOW"))


# path → (monotonic ts, fingerprint). Short TTL: env_hash runs per
# lease, a full tree walk every time would tax hot paths, but an edited
# working_dir must be picked up within seconds.
_fp_cache: dict[str, tuple[float, str]] = {}


def _dir_fingerprint(path: str, ttl: float = 5.0) -> str:
    """Content fingerprint of a directory tree (names, sizes, mtimes) —
    the reference content-hashes working_dir packages so edited trees
    re-stage instead of silently serving stale copies."""
    now = time.monotonic()
    hit = _fp_cache.get(path)
    if hit and now - hit[0] < ttl:
        return hit[1]
    h = hashlib.sha1()
    for dirpath, dirnames, filenames in sorted(os.walk(path)):
        dirnames.sort()
        for fn in sorted(filenames):
            p = os.path.join(dirpath, fn)
            try:
                st = os.stat(p)
            except OSError:
                continue
            h.update(
                f"{os.path.relpath(p, path)}:{st.st_size}:"
                f"{st.st_mtime_ns}\n".encode()
            )
    fp = h.hexdigest()[:12]
    _fp_cache[path] = (now, fp)
    return fp


def env_hash(runtime_env: dict | None) -> str:
    """Stable key for a runtime_env: workers are pooled per distinct env
    (reference: runtime_env workers are dedicated + cached by env hash,
    python/ray/_private/runtime_env/). working_dir envs hash the tree's
    CONTENT, so an edit re-stages and re-pools instead of reusing
    workers running stale code."""
    if not runtime_env:
        return ""
    key = dict(runtime_env)
    wd = key.get("working_dir")
    if wd:
        key["working_dir_fp"] = _dir_fingerprint(os.path.expanduser(wd))
    return hashlib.sha1(
        json.dumps(key, sort_keys=True).encode()
    ).hexdigest()[:16]


import threading

_ENV_CACHE_ROOT = os.path.join(tempfile.gettempdir(), "ray_tpu-envs")
_built_envs: dict[str, dict] = {}  # env hash → {"python": ..., "cwd": ...}
# Created at import: lazy creation would itself race between the first
# two concurrent builds.
_env_build_lock = threading.Lock()


def _locked_env_delete(h: str, root: str):
    """GC deletion under the SAME per-hash flock build_runtime_env
    takes: a concurrent rebuild of the just-evicted hash either waits
    for the delete to finish (then rebuilds from a clean slate) or
    holds the lock first (then the marker it wrote stays intact —
    this delete re-checks and aborts)."""
    import fcntl
    import shutil as _shutil

    os.makedirs(_ENV_CACHE_ROOT, exist_ok=True)
    with open(os.path.join(_ENV_CACHE_ROOT, f".{h}.lock"), "w") as lf:
        fcntl.flock(lf, fcntl.LOCK_EX)
        try:
            if h in _built_envs:
                # A rebuild re-registered this hash while the delete
                # was queued: the tree is live again, leave it.
                return
            _shutil.rmtree(root, ignore_errors=True)
        finally:
            fcntl.flock(lf, fcntl.LOCK_UN)


def _make_env_cache():
    from ray_tpu._private import config
    from ray_tpu.runtime.runtime_env import UriCache

    # Evicted envs must also leave the build memo, or the next request
    # would hand out a python/cwd whose files were just deleted.
    return UriCache(
        config.get("ENV_CACHE_BYTES"),
        on_evict=lambda h: _built_envs.pop(h, None),
        delete_fn=_locked_env_delete,
    )


_env_cache = _make_env_cache()


def build_runtime_env(runtime_env: dict, h: str | None = None) -> dict:
    """Materialize a task/actor runtime env on this node: a venv for
    ``pip`` dependencies and a staged copy of ``working_dir``. Cached by
    env hash — the content-addressed URI-cache equivalent (reference:
    the per-node runtime_env agent builds pip/conda envs,
    _private/runtime_env/agent/runtime_env_agent.py, uri_cache.py).

    Offline clusters (no egress) install from local wheels:
    ``{"pip": [...], "pip_no_index": True, "pip_find_links": dir}``.
    """
    if h is None:
        h = env_hash(runtime_env)  # content-aware for working_dir envs
    if h in _built_envs:
        return _built_envs[h]
    with _env_build_lock:
        if h in _built_envs:
            return _built_envs[h]
        info: dict = {"python": None, "cwd": None}
        root = os.path.join(_ENV_CACHE_ROOT, h)
        # Cross-PROCESS exclusion too (several node daemons share one
        # host and one env cache): a file lock per env hash.
        os.makedirs(_ENV_CACHE_ROOT, exist_ok=True)
        import fcntl

        lock_f = open(os.path.join(_ENV_CACHE_ROOT, f".{h}.lock"), "w")
        # tpulint: allow(blocking-under-lock reason=thread lock plus file lock together are the design - one env build per thread AND per host; builds are expected to take seconds)
        fcntl.flock(lock_f, fcntl.LOCK_EX)
        try:
            _build_env_locked(runtime_env, root, info)
        finally:
            # tpulint: allow(blocking-under-lock reason=unlock of the cross-process file lock cannot block)
            fcntl.flock(lock_f, fcntl.LOCK_UN)
            lock_f.close()
        _built_envs[h] = info
        if os.path.isdir(root):
            # Only on-disk builds participate in byte-budget GC (named
            # conda envs and pure env_vars envs occupy no cache space).
            _env_cache.register(h, root)
        return info


def _build_env_locked(runtime_env: dict, root: str, info: dict) -> None:
    import shutil as _shutil

    pip_pkgs = runtime_env.get("pip")
    uv_pkgs = runtime_env.get("uv")
    conda_spec = runtime_env.get("conda")
    if sum(map(bool, (pip_pkgs, uv_pkgs, conda_spec))) > 1:
        raise ValueError(
            "runtime_env: 'pip', 'uv', 'conda' are mutually exclusive — "
            "specify one package manager, not both"
        )
    if conda_spec:
        from ray_tpu.runtime.runtime_env import build_conda_env

        info["python"] = build_conda_env(conda_spec, root)
    if pip_pkgs or uv_pkgs:
        venv_dir = os.path.join(root, "venv")
        vpython = os.path.join(venv_dir, "bin", "python")
        marker = os.path.join(venv_dir, ".ready")
        have_uv = _shutil.which("uv") is not None
        use_uv = bool(uv_pkgs) and have_uv
        if uv_pkgs and not have_uv:
            # Degrade to pip with the same package list rather than
            # fail the lease on hosts without the uv binary — LOUDLY:
            # pip's resolver can pin different versions for the same
            # specs, so heterogeneous clusters would otherwise build
            # divergent envs under one env hash with no trace.
            print(
                f"ray_tpu runtime_env: uv binary not found on this "
                f"node; building {uv_pkgs} with pip instead (resolver "
                f"may differ across nodes)",
                flush=True,
            )
            pip_pkgs = uv_pkgs
        if not os.path.exists(marker):
            os.makedirs(root, exist_ok=True)
            # --clear / fresh dir: a crash mid-build leaves no marker;
            # rebuild from scratch. system-site-packages: jax & friends
            # come from the image, only the requested deps layer on.
            if use_uv:
                # uv venv has no --clear: remove and recreate.
                _shutil.rmtree(venv_dir, ignore_errors=True)
                proc = subprocess.run(
                    [
                        "uv", "venv", "--system-site-packages",
                        "--python", sys.executable, venv_dir,
                    ],
                    capture_output=True,
                    text=True,
                )
                if proc.returncode != 0:
                    raise RuntimeError(
                        f"runtime_env uv venv failed:\n{proc.stderr[-2000:]}"
                    )
                cmd = ["uv", "pip", "install", "--python", vpython]
            else:
                subprocess.run(
                    [
                        sys.executable, "-m", "venv", "--clear",
                        "--system-site-packages", venv_dir,
                    ],
                    check=True,
                    capture_output=True,
                )
                cmd = [vpython, "-m", "pip", "install",
                       "--no-warn-script-location"]
            if runtime_env.get("pip_no_index"):
                cmd.append("--no-index")
            if runtime_env.get("pip_find_links"):
                cmd += ["--find-links", runtime_env["pip_find_links"]]
            cmd += list(uv_pkgs if use_uv else pip_pkgs)
            proc = subprocess.run(cmd, capture_output=True, text=True)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"runtime_env {'uv' if use_uv else 'pip'} install "
                    f"failed:\n{proc.stderr[-2000:]}"
                )
            with open(marker, "w") as f:
                f.write("ok")
        info["python"] = vpython
    working_dir = runtime_env.get("working_dir")
    if working_dir:
        import shutil

        stage = os.path.join(root, "workdir")
        if not os.path.isdir(stage):
            os.makedirs(root, exist_ok=True)
            tmp = f"{stage}.staging-{os.getpid()}"
            shutil.rmtree(tmp, ignore_errors=True)
            shutil.copytree(os.path.expanduser(working_dir), tmp)
            os.rename(tmp, stage)
        info["cwd"] = stage


def detect_resources() -> dict[str, float]:
    """Detect node resources WITHOUT initializing a JAX backend: grabbing
    jax.devices() here would lock the TPU chip into the daemon process,
    and the leased worker that needs it would then fail to open it.
    Accelerators come from the plugin registry (reference: per-vendor
    AcceleratorManagers, python/ray/_private/accelerators/)."""
    from ray_tpu._private.accelerators import detect_accelerator_resources

    resources: dict[str, float] = {"CPU": float(os.cpu_count() or 1)}
    resources.update(detect_accelerator_resources())
    return resources


class Lease:
    __slots__ = (
        "lease_id", "worker", "resources", "actor", "bundle",
        "bundle_resources", "granted_at", "began_at",
    )

    def __init__(
        self, lease_id: str, worker: dict, resources: dict, actor: bool,
        began_at: float | None = None,
    ):
        self.lease_id = lease_id
        self.worker = worker
        self.resources = resources
        self.actor = actor
        self.bundle: tuple | None = None  # (pg_id, index) if bundle-backed
        self.bundle_resources: dict | None = None
        self.granted_at = time.monotonic()
        # time.time() as the grant began (_grant_lease): after any wait
        # in the queue, before the worker was found or started.
        self.began_at = time.time() if began_at is None else began_at


class NodeManager:
    def __init__(
        self,
        head_addr: str,
        store_dir: str,
        resources: dict[str, float] | None = None,
        worker_env: dict[str, str] | None = None,
        labels: dict[str, str] | None = None,
    ):
        self.node_id = NodeID.random().hex()
        self.head_addr = head_addr
        self.store_dir = store_dir
        self.total = resources or detect_resources()
        self.available = dict(self.total)
        self.labels = detect_labels() if labels is None else dict(labels)
        self.worker_env = worker_env or {}
        self.server = rpc.Server(self._handle)
        self.addr: str | None = None
        self.head: rpc.Connection | None = None
        # worker_id → {proc, conn, addr, pid, state: spawning|idle|leased}
        self.workers: dict[str, dict] = {}
        # env_hash → idle worker ids (workers are pooled per runtime_env)
        self.idle: dict[str, list[str]] = collections.defaultdict(list)
        self.leases: dict[str, Lease] = {}
        # (resources, actor, fut, enqueued_at, runtime_env): queued
        # feasible-but-unavailable lease requests. Entries older than
        # PENDING_SPILL_S are bounced with retry_spill so the caller can
        # try another node via the head (lease spillback) instead of
        # camping here while new capacity sits idle elsewhere.
        self._pending: list[tuple] = []
        # (pg_id, index) → {"total": resources, "available": resources}
        self.bundles: dict[tuple, dict] = {}
        # env_hash → waiters for a worker of that env
        self._worker_waiters: dict[str, collections.deque] = (
            collections.defaultdict(collections.deque)
        )
        self._next_lease = 0
        # Physical chips here (0 under RAY_TPU_FAKE_CHIPS): what sends a
        # TPU lease's worker to the TPU (chip.lease_platform).
        from ray_tpu._private.accelerators import TPUAcceleratorManager

        self._real_chips = TPUAcceleratorManager().real_chips()
        # Killed workers that may not be gone yet: chip holders whose
        # lease ended and, in stop(), whatever outlived SIGTERM. Read
        # by _reap_dying alone.
        self._dying_chip_procs: list[subprocess.Popen] = []
        # Whether a worker of this node has been sent to the real chips:
        # stop() then sees them open before it returns.
        self._leased_real_chips = False
        # startup:* spans of this node, sent to the head in batches over
        # the connection it already holds (_emit_span).
        self._spans: list[dict] = []
        self._span_flusher: asyncio.Task | None = None
        self._tasks: list[asyncio.Task] = []
        # Worker log capture (reference: workers write to
        # /tmp/ray/session_*/logs and log_monitor.py:116 tails + streams
        # them to drivers). One file per worker on DISK (not shm);
        # _log_monitor_loop tails them into the "logs" pubsub channel.
        from ray_tpu._private import config as _config

        self.log_dir = Path(
            _config.get("LOG_DIR")
            or os.path.join(
                tempfile.gettempdir(),
                f"{os.path.basename(str(store_dir))}-logs",
            )
        )
        self._log_offsets: dict[str, int] = {}  # filename → bytes shipped
        self.spilled_bytes = 0
        self.spilled_objects = 0
        self.oom_kills = 0
        # Read view of this node's object store: the node serves chunked
        # object pulls to other nodes (reference: the raylet's
        # ObjectManager serves Push/Pull, object_manager.h:128) — workers
        # come and go, the node daemon persists.
        self._store_reader = None
        # Peer-node connections for prefetch/broadcast relays, and the
        # location directory for objects anchored here (client-mode puts
        # name this node as owner address).
        self._peers: dict[str, rpc.Connection] = {}
        self._obj_locations: dict[str, set] = {}
        # Resource-view sync state (reference: ray_syncer.h:90 —
        # versioned per-node updates pushed on CHANGE, not polled).
        self._res_version = 0
        self._sync_event: asyncio.Event | None = None
        # DRAINING: set by the head's drain fan-out or by this node's
        # own preemption watcher / SIGTERM handler. A draining node
        # refuses NEW leases (retry_spill bounces the caller to the
        # head, which excludes draining nodes) while existing leases
        # and bundle-backed work keep running until the deadline.
        self.draining = False
        self.drain_info: dict | None = None
        # Per-node dashboard agent (reference: dashboard/agent.py).
        self.agent = None

    # ----------------------------------------------------------- startup
    async def start(self, host: str = "127.0.0.1", port: int = 0) -> str:
        p = await self.server.start(host, port)
        self.addr = f"{host}:{p}"
        from ray_tpu._private import config

        # Reconnecting client: a head restart re-registers this node
        # (the NotifyGCSRestart-equivalent resubscription,
        # reference: node_manager.proto:325).
        self.head = await rpc.ReconnectingClient(
            self.head_addr,
            on_reconnect=self._register_with_head,
            reconnect_timeout=config.get("HEAD_RECONNECT_S"),
        ).connect()
        if config.get("NODE_AGENT"):
            from ray_tpu.runtime.agent import NodeAgent

            self.agent = NodeAgent(self)
            # Loopback by default: the agent serves worker logs over
            # plain HTTP with NO token handshake — binding the node's
            # routable host would leak stdout/stderr to the network.
            # Operators front it with their own proxy/auth via
            # RAY_TPU_NODE_AGENT_HOST.
            await self.agent.start(config.get("NODE_AGENT_HOST"))
        await self._register_with_head(self.head._conn)
        self._sync_event = asyncio.Event()
        self._sync_event.set()  # first wake sends the initial view
        self._tasks.append(asyncio.ensure_future(self._heartbeat_loop()))
        self._tasks.append(asyncio.ensure_future(self._reap_loop()))
        self._tasks.append(asyncio.ensure_future(self._spill_loop()))
        self._tasks.append(asyncio.ensure_future(self._memory_loop()))
        self._tasks.append(asyncio.ensure_future(self._log_monitor_loop()))
        src = self._preemption_source()
        if src is not None:
            self._tasks.append(
                asyncio.ensure_future(self._preemption_watch_loop(src))
            )
        # Prestart workers up to the CPU count so the first task burst
        # doesn't pay Python-interpreter spawn latency per lease
        # (reference: WorkerPool prestarts workers, worker_pool.h:280).
        for _ in range(min(int(self.total.get("CPU", 1)), IDLE_WORKER_CAP)):
            self._spawn_worker()
        return self.addr

    async def stop(self):
        for t in self._tasks:
            t.cancel()
        if self._span_flusher is not None:
            self._span_flusher.cancel()
        await self.flush_spans()
        if self.agent is not None:
            await self.agent.stop()
        # When this returns, no worker in the node's table and none
        # that held a chip is alive or a zombie, and the chips its
        # workers held open again for the next job on this host.
        procs = [w["proc"] for w in self.workers.values() if w.get("proc")]
        for proc in procs:
            if proc.poll() is None:
                proc.terminate()
        term_by = time.monotonic() + STOP_TERM_S
        for proc in await asyncio.to_thread(_wait_all, procs, term_by):
            proc.kill()
            self._dying_chip_procs.append(proc)
        deadline = time.monotonic() + CHIP_FREE_TIMEOUT_S
        _, left = await self._reap_dying(deadline)
        if not left and self._leased_real_chips:
            # A chip that a live process of somebody else's holds by
            # now is not this node's to wait for.
            _, left = await self._chips_let_go(deadline, others=False)
        if left:
            logger.warning(
                "node %s stopped, and %.0f s after its SIGKILLs "
                "something it started is not gone or a chip it leased "
                "is not free: %s",
                self.node_id[:8], CHIP_FREE_TIMEOUT_S, "; ".join(left),
            )
        for w in self.workers.values():
            core = w.get("core")
            if core is not None:
                # Inproc workers (WORKER_MODE=inproc) have no process
                # to reap: stop their CoreWorker servers/tasks or they
                # keep running on the loop after the node is gone.
                try:
                    await core.stop()
                except Exception:
                    logger.debug(
                        "inproc worker core stop failed during node "
                        "teardown", exc_info=True,
                    )
        if self.head:
            await self.head.close()
        await self.server.stop()

    # ------------------------------------------------------------ workers
    def _spawn_worker(
        self,
        runtime_env: dict | None = None,
        ehash: str | None = None,
        platform: str = "cpu",
        chips: float = 0,
    ) -> str:
        """Start a worker process. ``platform`` is what
        chip.lease_platform decided for the lease it is started for and
        ``chips`` the TPU that lease holds; pooled workers are always
        "cpu" and 0."""
        worker_id = WorkerID.random().hex()
        if ehash is None:
            ehash = env_hash(runtime_env)
        from ray_tpu._private import config

        if (runtime_env or {}).get("language") == "cpp":
            # Checked BEFORE the inproc branch: a cpp lease must never
            # silently get a Python CoreWorker (the binary is a real
            # subprocess even in scale-simulation mode).
            return self._spawn_worker_cpp(worker_id, runtime_env, ehash)
        if config.get("WORKER_MODE") == "inproc":
            # Scale-simulation mode (see the WORKER_MODE knob and the
            # reference's many-node release benchmarks,
            # release/benchmarks/distributed/test_many_actors.py): the
            # worker is a CoreWorker on this node's loop. It still
            # dials the node/head over real sockets and registers like
            # a process worker — the control plane cannot tell the
            # difference — but costs ~100 KB instead of an interpreter,
            # so thousands of actors fit one host.
            return self._spawn_worker_inproc(worker_id, runtime_env, ehash)
        # Workers must find the ray_tpu package regardless of their cwd.
        import ray_tpu

        pkg_root = os.path.dirname(os.path.dirname(ray_tpu.__file__))
        pypath = os.environ.get("PYTHONPATH", "")
        if pkg_root not in pypath.split(os.pathsep):
            pypath = f"{pkg_root}{os.pathsep}{pypath}" if pypath else pkg_root
        # Workers inherit the driver's module search path so functions
        # pickled by reference (top-level defs in driver-side modules)
        # import cleanly (reference: ray workers inherit PYTHONPATH/cwd;
        # runtime_env py_modules covers the multi-host case).
        seen = set(pypath.split(os.pathsep))
        for entry in sys.path:
            # exists (not isdir): zipimport archives are valid entries.
            if entry and entry not in seen and os.path.exists(entry):
                pypath = f"{pypath}{os.pathsep}{entry}"
                seen.add(entry)
        renv = runtime_env or {}
        from ray_tpu.runtime import runtime_env as renv_mod

        in_container = renv_mod.container_image(renv) is not None
        # Pin the env BEFORE reading the build memo: a release-triggered
        # eviction between the two would hand this worker a root whose
        # files are being deleted.
        _env_cache.acquire(ehash)
        built = _built_envs.get(ehash, {})
        python_exe = built.get("python") or sys.executable
        argv = [python_exe, "-m", "ray_tpu.runtime.worker_main"]
        if chips:
            from ray_tpu.runtime.worker_main import CHIP_LEASE_ARG

            argv.append(CHIP_LEASE_ARG)
        # py_modules: local dirs importable in the worker (single-host or
        # shared-FS; the reference ships them via the runtime_env agent).
        for mod_path in renv.get("py_modules", ()):
            mod_path = os.path.abspath(mod_path)
            if mod_path not in pypath.split(os.pathsep):
                pypath = f"{mod_path}{os.pathsep}{pypath}"
        # Staged working_dir: the worker starts there and imports from it
        # (reference: working_dir runtime env, staged + cwd'd per worker).
        if built.get("cwd"):
            pypath = f"{built['cwd']}{os.pathsep}{pypath}"
        env = {
            **os.environ,
            "PYTHONPATH": pypath,
            **self.worker_env,
            **{str(k): str(v) for k, v in renv.get("env_vars", {}).items()},
            "RAY_TPU_HEAD_ADDR": self.head_addr,
            "RAY_TPU_NODE_ADDR": self.addr or "",
            "RAY_TPU_STORE_DIR": self.store_dir,
            "RAY_TPU_WORKER_ID": worker_id,
            # The lease decides who holds the chip (_private/chip.py):
            # "tpu" only for a process started for a lease of real
            # chips, which worker_main turns into chip.hold_chip().
            "JAX_PLATFORMS": platform,
            # Captured stdio is a pipe-to-file, not a tty: without this,
            # worker prints sit in libc buffers and never reach the log
            # pipeline.
            "PYTHONUNBUFFERED": "1",
        }
        try:
            if in_container:
                # Containerized worker (reference: image_uri.py — the
                # worker command runs under podman/docker with host
                # networking and the runtime's paths mounted 1:1 so
                # PYTHONPATH/store paths stay valid inside). Only the
                # vars the worker needs are forwarded — the host
                # environ is not the container's.
                fwd = {
                    k: v
                    for k, v in env.items()
                    if k.startswith(("RAY_TPU_", "PYTHON", "JAX_"))
                    or k in self.worker_env
                    or k in (renv.get("env_vars") or {})
                }
                mounts = [
                    pkg_root,
                    self.store_dir,
                    _ENV_CACHE_ROOT,
                    built.get("cwd") or "",
                    *[
                        os.path.abspath(m)
                        for m in renv.get("py_modules", ())
                    ],
                ]
                argv = renv_mod.wrap_container_argv(
                    renv, argv, fwd, mounts, built.get("cwd")
                )
            # Capture stdio to a per-worker log file (reference: worker
            # logs under /tmp/ray/session_*/logs; log_monitor tails
            # them).
            self.log_dir.mkdir(parents=True, exist_ok=True)
            log_path = self.log_dir / f"worker-{worker_id}.log"
            spawned_at = time.time()
            with open(log_path, "ab") as log_f:
                proc = subprocess.Popen(
                    argv,
                    env=env,
                    cwd=built.get("cwd"),
                    stdout=log_f,
                    stderr=subprocess.STDOUT,
                )
        except Exception:
            # Spawn failed before a worker record existed: nothing will
            # ever release the ref taken above, so release it here or
            # the env is pinned against GC forever.
            _env_cache.release(ehash)
            raise
        self.workers[worker_id] = {
            "proc": proc,
            "state": "spawning",
            "env_hash": ehash,
            "runtime_env": runtime_env,
            "log_path": str(log_path),
            "platform": platform,
            "chips": chips,
            "spawned_at": spawned_at,
        }
        return worker_id

    def _spawn_worker_cpp(
        self, worker_id: str, runtime_env: dict | None, ehash: str
    ) -> str:
        """Spawn the configured C++ worker binary (reference: the C++
        worker the raylet starts for RAY_REMOTE tasks, cpp/src/ray/
        runtime/task/task_executor.cc). It registers back over the
        native wire exactly like a Python worker; the {'language':
        'cpp'} runtime_env gives these their own worker pool, so the
        lease machinery never hands a cpp task to a Python process or
        vice versa."""
        import shlex

        from ray_tpu._private import config

        cmd = config.get("CPP_WORKER_CMD")
        if not cmd:
            raise RuntimeError(
                "runtime_env {'language': 'cpp'} needs RAY_TPU_CPP_"
                "WORKER_CMD to point at a worker binary (build one "
                "with make -C cpp: build/raytpu_worker)"
            )
        _env_cache.acquire(ehash)  # pairs with release on worker death
        env = {
            **os.environ,
            **self.worker_env,
            "RAY_TPU_HEAD_ADDR": self.head_addr,
            "RAY_TPU_NODE_ADDR": self.addr or "",
            "RAY_TPU_STORE_DIR": self.store_dir,
            "RAY_TPU_WORKER_ID": worker_id,
            # The binary reads these from env only (it has no config
            # registry); programmatic overrides would otherwise be
            # invisible to it. Cert/key let it serve AND dial TLS in a
            # --tls cluster.
            "RAY_TPU_AUTH_TOKEN": config.get("AUTH_TOKEN"),
            "RAY_TPU_TLS_CERT": config.get("TLS_CERT"),
            "RAY_TPU_TLS_KEY": config.get("TLS_KEY"),
        }
        try:
            self.log_dir.mkdir(parents=True, exist_ok=True)
            log_path = self.log_dir / f"worker-{worker_id}.log"
            with open(log_path, "ab") as log_f:
                proc = subprocess.Popen(
                    shlex.split(cmd),
                    env=env,
                    stdout=log_f,
                    stderr=subprocess.STDOUT,
                )
        except Exception:
            _env_cache.release(ehash)
            raise
        self.workers[worker_id] = {
            "proc": proc,
            "state": "spawning",
            "env_hash": ehash,
            "runtime_env": runtime_env,
            "log_path": str(log_path),
        }
        return worker_id

    def _spawn_worker_inproc(
        self, worker_id: str, runtime_env: dict | None, ehash: str
    ) -> str:
        # Pair with the unconditional release in the reap loop /
        # _kill_worker: without this, inproc workers decrement a
        # refcount they never took and a registered on-disk env can be
        # evicted while process workers still use it.
        _env_cache.acquire(ehash)
        self.workers[worker_id] = {
            "proc": None,
            "inproc": True,
            "state": "spawning",
            "env_hash": ehash,
            "runtime_env": runtime_env,
            "log_path": "",
        }

        async def boot():
            from ray_tpu.runtime.core_worker import CoreWorker

            core = CoreWorker(
                mode="worker",
                head_addr=self.head_addr,
                node_addr=self.addr or "",
                store_dir=self.store_dir,
                worker_id=worker_id,
            )
            def soft_exit():
                # Mark the record so the reap loop runs the same death
                # path (lease failure, head notification) a subprocess
                # worker's proc.poll() would trigger.
                w2 = self.workers.get(worker_id)
                if w2 is not None:
                    w2["exited"] = True
                asyncio.ensure_future(core.stop())

            core._exit_cb = soft_exit
            try:
                addr = await core.start()
                w = self.workers.get(worker_id)
                if w is None:  # killed while booting
                    await core.stop()
                    return
                w["core"] = core
                await core.node.call(
                    "register_worker",
                    worker_id=worker_id,
                    addr=addr,
                    pid=os.getpid(),
                )
            except Exception:
                logger.warning(
                    "inproc worker %s failed to boot", worker_id,
                    exc_info=True,
                )
                # A subprocess worker dying mid-boot is reaped via
                # proc.poll(); mark this one so the reap loop runs the
                # same path (record cleanup, waiter replacement)
                # instead of leaving a permanent "spawning" zombie
                # whose n_spawning count blocks future spawns.
                w2 = self.workers.get(worker_id)
                if w2 is not None:
                    w2["exited"] = True
                await core.stop()

        asyncio.ensure_future(boot())
        return worker_id

    # ------------------------------------------------------------ leases
    def _feasible(self, resources: dict) -> bool:
        return all(self.total.get(k, 0) >= v for k, v in resources.items())

    def _available(self, resources: dict) -> bool:
        return all(self.available.get(k, 0) >= v for k, v in resources.items())

    def _bump_resources(self):
        """Mark the resource view dirty: the sync loop pushes a
        versioned update to the head as soon as it wakes (reference:
        ray_syncer's per-component version counters — only CHANGED
        state crosses the wire, ray_syncer.h:90)."""
        self._res_version += 1
        if self._sync_event is not None:
            self._sync_event.set()

    def _acquire(self, resources: dict):
        for k, v in resources.items():
            self.available[k] = self.available.get(k, 0) - v
        self._bump_resources()

    def _release(self, resources: dict):
        for k, v in resources.items():
            self.available[k] = self.available.get(k, 0) + v
        self._bump_resources()

    async def _get_worker(
        self,
        runtime_env: dict | None = None,
        platform: str = "cpu",
        chips: float = 0,
    ) -> str:
        """Pop an idle worker of the matching runtime_env, else wait for
        a spawning one; only spawn a fresh process when demand exceeds
        the number already spawning (avoids a thundering herd of Python
        interpreters on cold bursts). A lease that holds ``chips`` never
        takes a pooled worker: see _get_chip_worker."""
        ehash = env_hash(runtime_env)
        bucket = self.idle[ehash]
        if bucket and not chips:
            return bucket.pop()
        if runtime_env and (
            runtime_env.get("pip")
            or runtime_env.get("uv")
            or runtime_env.get("conda")
            or runtime_env.get("working_dir")
        ):
            # Build the isolated env (venv + staged working dir) OFF the
            # event loop; cached per env hash, so only the first lease
            # of an env pays (reference: the per-node runtime_env agent
            # builds pip/conda envs with a URI cache,
            # _private/runtime_env/agent/ + uri_cache.py).
            # Thread THIS lease's ehash through build and spawn: the
            # working_dir fingerprint cache has a short TTL, so
            # recomputing at spawn time could hash a just-edited dir
            # differently and miss _built_envs — the worker would then
            # silently start without the env it was leased for.
            await asyncio.get_running_loop().run_in_executor(
                None, build_runtime_env, runtime_env, ehash
            )
        if chips:
            return await self._get_chip_worker(
                runtime_env, ehash, platform, chips
            )
        n_spawning = sum(
            1
            for w in self.workers.values()
            if w.get("state") == "spawning"
            and w.get("env_hash", "") == ehash
            and "waiter" not in w
        )
        if n_spawning <= len(self._worker_waiters[ehash]):
            self._spawn_worker(runtime_env, ehash=ehash)
        fut = asyncio.get_running_loop().create_future()
        self._worker_waiters[ehash].append(fut)
        return await asyncio.wait_for(fut, SPAWN_TIMEOUT_S)

    async def _get_chip_worker(
        self, runtime_env: dict | None, ehash: str, platform: str,
        chips: float,
    ) -> str:
        """A process of its own for a lease that holds chips. A pooled
        worker may already have created a CPU backend and cannot switch;
        and a process that has opened the chip keeps it until it dies,
        so the worker is started for this lease, killed when the lease
        ends (_on_return_lease), and not granted before the chip is
        free: the chip workers this node killed are reaped first, and
        the device is asked while the new process boots (it opens no
        chip before its first task). Fake chips (``platform`` "cpu")
        have no device to ask and take the same road otherwise, so that
        what a test or a rehearsal starts and measures is what the
        chip's lease does."""
        from ray_tpu._private.chip import ChipUnavailableError

        def unavailable(left: list[str]) -> ChipUnavailableError:
            return ChipUnavailableError(
                f"the chip was not free {CHIP_FREE_TIMEOUT_S:.0f} s after "
                f"this lease asked for it: {'; '.join(left)}"
            )

        wait_began = time.time()
        deadline = time.monotonic() + CHIP_FREE_TIMEOUT_S
        procs, left = await self._reap_dying(deadline)
        if left:
            raise unavailable(left)
        waited = time.time() - wait_began
        worker_id = self._spawn_worker(
            runtime_env, ehash=ehash, platform=platform, chips=chips
        )
        fut = asyncio.get_running_loop().create_future()
        self.workers[worker_id]["waiter"] = fut
        waited_for: dict = {}
        if platform == "tpu":
            self._leased_real_chips = True
            waited_for, left = await self._chips_let_go(deadline, others=True)
            if left:
                self._kill_worker(worker_id)
                raise unavailable(left)
            waited = time.time() - wait_began
        self._emit_span(
            "startup:chip_free_wait", wait_began, waited,
            worker_id=worker_id, procs=procs, **waited_for,
        )
        return await asyncio.wait_for(fut, SPAWN_TIMEOUT_S)

    async def _reap_dying(self, deadline: float) -> tuple[int, list[str]]:
        """Wait, off the event loop, for the workers this node killed to
        be gone: how many there were, and which are not gone at
        ``deadline`` (``time.monotonic()``)."""
        dying, self._dying_chip_procs = self._dying_chip_procs, []
        if not dying:  # a first lease: no thread to start
            return 0, []
        alive = await asyncio.to_thread(_wait_all, dying, deadline)
        self._dying_chip_procs.extend(alive)
        return len(dying), [
            f"pid {proc.pid} (killed, not gone)" for proc in alive
        ]

    async def _chips_let_go(
        self, deadline: float, others: bool
    ) -> tuple[dict, list[str]]:
        """Wait until ``tpu.busy_chips`` finds this host's chips free.
        A device node that only this node's own live workers have open
        is never waited for (they keep it while their leases last); one
        that no process has open is being closed by the kernel and
        always is; one that a live process of somebody else's holds is
        waited for with ``others`` (a lease needs the chip) and not
        without (stop() owes nobody that). Returns what was waited for,
        as ``startup:chip_free_wait``'s attributes, and what is still
        held at ``deadline`` (``time.monotonic()``); a blocked open can
        end later than that."""
        from ray_tpu._private.accelerators import tpu

        nodes: set[str] = set()
        holders: set[int] = set()
        while True:
            own = {
                w["proc"].pid for w in self.workers.values() if w.get("proc")
            }
            busy = {
                node: pids
                for node, pids in (
                    await asyncio.to_thread(tpu.busy_chips)
                ).items()
                if not pids or (others and not set(pids) <= own)
            }
            if not busy or time.monotonic() >= deadline:
                break
            nodes.update(busy)
            holders.update(*busy.values())
            await asyncio.sleep(0.2)
        return {"nodes": sorted(nodes), "holders": sorted(holders)}, [
            f"{node} (open in pids {pids})" if pids else
            f"{node} (open in no process: the kernel is still closing it)"
            for node, pids in sorted(busy.items())
        ]

    def _emit_span(self, name: str, start: float, dur: float, **attrs):
        """A completed ``startup:*`` span of this node, in the shape of
        ``tracing.record_span``'s events. A node daemon has no core
        worker of its own to carry spans, and an embedded one would
        have to find its driver's: both send them over the head
        connection the node holds, a fraction of a second's worth at a
        time."""
        span_id = uuid.uuid4().hex[:16]
        self._spans.append({
            "task_id": f"span:{span_id}", "name": name, "state": "SPAN",
            "ts": start, "dur": dur, "worker": self.addr,
            "trace_id": uuid.uuid4().hex[:16], "span_id": span_id,
            "parent_id": "", "node_id": self.node_id, **attrs,
        })
        if self._span_flusher is None or self._span_flusher.done():
            self._span_flusher = asyncio.ensure_future(self._flush_soon())

    async def _flush_soon(self):
        await asyncio.sleep(0.2)
        await self.flush_spans()

    async def flush_spans(self):
        if not self._spans or self.head is None:
            return
        batch, self._spans = self._spans, []
        try:
            await self.head.call("add_task_events", events=batch)
        except rpc.RpcError:
            pass  # a head that is away: telemetry, not worth a retry

    async def _grant_lease(
        self,
        resources: dict,
        actor: bool,
        runtime_env: dict | None = None,
        held: dict | None = None,
    ) -> dict:
        """``resources`` is what the grant charges to the node's pool;
        ``held`` is what the lease holds, where that differs (a
        bundle-backed lease charges nothing and holds its share of the
        bundle). The worker's JAX platform follows from ``held``."""
        from ray_tpu._private import chip

        began_at = time.time()
        held = resources if held is None else held
        platform = chip.lease_platform(held, self._real_chips)
        self._acquire(resources)
        try:
            worker_id = await self._get_worker(
                runtime_env, platform, held.get("TPU", 0)
            )
            w = self.workers[worker_id]
            w["state"] = "leased"
            self._next_lease += 1
            lease_id = f"{self.node_id[:8]}-{self._next_lease}"
            self.leases[lease_id] = Lease(
                lease_id, {**w, "worker_id": worker_id}, resources, actor,
                began_at,
            )
            return {
                "ok": True,
                "lease_id": lease_id,
                "worker_id": worker_id,
                "addr": w["addr"],
            }
        except Exception:
            self._release(resources)
            raise

    async def _handle(self, method: str, kw: dict, conn: rpc.Connection):
        fn = getattr(self, f"_on_{method}", None)
        if fn is None:
            raise rpc.RpcError(f"node: unknown method {method!r}")
        return await fn(conn=conn, **rpc.tolerant_kwargs(fn, kw))

    # ------------------------------------------------------- node drain
    async def _on_set_draining(
        self,
        conn,
        draining: bool = True,
        reason: str = "",
        deadline_ts: float | None = None,
    ):
        """Head-pushed drain flag (the head is the authority; this flag
        makes the node's OWN lease path refuse work, which is what
        diverts local-first task/actor placement to other nodes)."""
        was_draining = self.draining
        self.draining = bool(draining)
        self.drain_info = (
            {"reason": reason, "deadline_ts": deadline_ts}
            if draining
            else None
        )
        if draining and not was_draining:
            # Drain-window evacuation, node side: owners push their
            # sole-primary objects to healthy peers; when NO healthy
            # peer exists this store is the last copy of everything in
            # it, so sweep it to the remote tier before retiring.
            asyncio.ensure_future(self._drain_evacuate_store())
        if draining:
            # Queued-but-ungranted leases bounce now — their callers
            # should spill to a node that will outlive them.
            for resources, actor, fut, _ts, _renv in self._pending:
                if not fut.done():
                    fut.set_result(
                        {
                            "ok": False,
                            "retry_spill": True,
                            "draining": True,
                            "error": "node is draining",
                        }
                    )
            self._pending = []
            self._bump_resources()
        return {"ok": True}

    async def _drain_evacuate_store(self) -> None:
        """No-healthy-peer endgame of drain evacuation: push every
        store-resident object to the remote tier (owners cover the
        push-to-peer case; with no peer to push to, the tier is the only
        place the bytes can outlive this node)."""
        from ray_tpu._private import config

        if not config.get("OBJECT_DRAIN_EVACUATION"):
            return
        from ray_tpu.checkpoint import remote as _remote
        from ray_tpu.runtime.drain import EVACUATED

        tier = _remote.get_tier()
        if tier is None or self.head is None:
            return
        try:
            status = await self.head.call("cluster_status")
        except rpc.RpcError:
            return
        draining = set(status.get("draining") or {})
        peers = [
            n
            for nid, n in (status.get("nodes") or {}).items()
            if n.get("addr") and n["addr"] != self.addr
            and nid not in draining
        ]
        if peers:
            return  # owners evacuate to peers; nothing for the tier
        store = self._store()
        for oid in store.iter_ids():
            view = store.get(oid)
            if view is None:
                continue
            try:
                seg_lens = [len(view.inband)] + [
                    len(b) for b in view.buffers
                ]
                payload = bytes(view.inband) + b"".join(
                    bytes(b) for b in view.buffers
                )
                blob = _remote.pack_object(seg_lens, payload)
                await asyncio.to_thread(tier.put_object, oid.hex(), blob)
                EVACUATED.inc(1, tags={"outcome": "remote_tier"})
            except _remote.RemoteTierError as e:
                EVACUATED.inc(1, tags={"outcome": "failed"})
                logger.warning(
                    "drain evacuation of %s to remote tier failed: %s",
                    oid.hex()[:12], e,
                )
            finally:
                store.release(oid)

    async def self_drain(
        self, reason: str, deadline_s: float | None = None
    ) -> None:
        """Self-reported drain (preemption notice, SIGTERM): flip the
        local flag first — no new lease may slip in while the head RPC
        is in flight — then tell the head so the notice fans out."""
        from ray_tpu._private import config

        if deadline_s is None:
            deadline_s = config.get("DRAIN_DEADLINE_S")
        already = self.draining
        self.draining = True
        self.drain_info = {
            "reason": reason,
            "deadline_ts": time.time() + float(deadline_s),
        }
        if already:
            return
        await self._on_set_draining(None, draining=True, reason=reason,
                                    deadline_ts=self.drain_info["deadline_ts"])
        if self.head is not None:
            try:
                await self.head.call(
                    "drain_node",
                    node_id=self.node_id,
                    reason=reason,
                    deadline_s=deadline_s,
                )
            except rpc.RpcError:
                pass

    def _preemption_source(self):
        """Pluggable preemption-notice source: the synthetic
        RAY_TPU_PREEMPT_AFTER_S spec for tests, the GCE maintenance-
        event metadata poller on Google VMs, else none."""
        from ray_tpu._private import config

        spec = config.get("PREEMPT_AFTER_S")
        if spec:
            from ray_tpu._private.test_utils import FakePreemptionSource

            return FakePreemptionSource(spec)
        try:
            with open("/sys/class/dmi/id/product_name") as f:
                on_gce = "Google" in f.read()
        except OSError:
            on_gce = False
        if on_gce:
            try:
                from ray_tpu.autoscaler.gcp import GceMaintenanceEventSource

                return GceMaintenanceEventSource()
            except Exception:
                logger.debug(
                    "GCE maintenance event source unavailable",
                    exc_info=True,
                )
                return None
        return None

    async def _preemption_watch_loop(self, source):
        """Poll the preemption source until it reports a notice, then
        self-drain with the notice's deadline and exit. The poll cadence
        is the source's (metadata endpoints want seconds, the fake wants
        sub-second determinism)."""
        interval = getattr(source, "interval_s", 1.0)
        while not self.draining:
            await asyncio.sleep(interval)
            try:
                notice = source.poll(self)
            except asyncio.CancelledError:
                raise
            # tpulint: allow(broad-except reason=metadata server polled every second; one flaky poll must not kill the watcher and logging each would spam)
            except Exception:
                continue
            if notice is None:
                continue
            reason, deadline_s = notice
            await self.self_drain(reason, deadline_s)
            return

    # ---------------------------------------------------- object serving
    def _store(self):
        if self._store_reader is None:
            from ray_tpu.runtime.object_store import ObjectStore

            self._store_reader = ObjectStore(self.store_dir)
        return self._store_reader

    async def _on_put_object(
        self, conn, oid_hex: str, inband, buffers: list
    ):
        """Store an object pushed by a remote client driver (reference:
        Ray Client server-side put, python/ray/util/client/server/).
        The node's store then serves it to any worker via the normal
        pull protocol."""
        from ray_tpu._private.ids import ObjectID
        from ray_tpu._private.serialization import Serialized

        store = self._store()
        store.put(ObjectID.from_hex(oid_hex), Serialized(inband, list(buffers)))
        return {"ok": True, "holder": self.addr}

    async def _on_put_object_begin(
        self, conn, oid_hex: str, seg_lens: list
    ):
        """Chunked client upload, begin: allocate an assembly buffer."""
        import uuid

        token = uuid.uuid4().hex[:16]
        self._uploads = getattr(self, "_uploads", {})
        self._uploads[token] = {
            "oid_hex": oid_hex,
            "seg_lens": list(seg_lens),
            "buf": bytearray(sum(seg_lens)),
            "ts": time.monotonic(),
        }
        return {"ok": True, "token": token}

    def _prune_uploads(self):
        """Drop abandoned upload buffers (client died mid-stream) —
        called from the reap loop so pruning does not depend on another
        client ever starting an upload."""
        uploads = getattr(self, "_uploads", None)
        if not uploads:
            return
        now = time.monotonic()
        for key in list(uploads):
            if now - uploads[key]["ts"] > 300:
                del uploads[key]

    async def _on_put_object_chunk(
        self, conn, token: str, offset: int, data: bytes
    ):
        up = getattr(self, "_uploads", {}).get(token)
        if up is None:
            return {"ok": False, "error": "unknown upload token"}
        up["buf"][offset : offset + len(data)] = data
        up["ts"] = time.monotonic()
        return {"ok": True}

    async def _on_put_object_commit(self, conn, token: str):
        up = getattr(self, "_uploads", {}).pop(token, None)
        if up is None:
            return {"ok": False, "error": "unknown upload token"}
        from ray_tpu._private.ids import ObjectID
        from ray_tpu._private.serialization import Serialized

        mv = memoryview(bytes(up["buf"]))
        segs = []
        pos = 0
        for n in up["seg_lens"]:
            segs.append(mv[pos : pos + n])
            pos += n
        self._store().put(
            ObjectID.from_hex(up["oid_hex"]),
            Serialized(bytes(segs[0]), [bytes(s) for s in segs[1:]]),
        )
        return {"ok": True, "holder": self.addr}

    async def _on_get_object(self, conn, oid_hex: str):
        """Owner-style lookup served by the node for store-resident
        objects (lets node addresses act as object holders for client
        drivers)."""
        from ray_tpu._private.ids import ObjectID

        if self._store().contains(ObjectID.from_hex(oid_hex)):
            return {
                "kind": "in_store",
                "holder": self.addr,
                "holders": [
                    a
                    for a in self._obj_locations.get(oid_hex, ())
                    if a != self.addr
                ],
            }
        import cloudpickle

        from ray_tpu.exceptions import ObjectLostError

        return {
            "kind": "error",
            "inband": cloudpickle.dumps(
                ObjectLostError(f"object {oid_hex[:12]}… not on this node")
            ),
        }

    async def _on_object_location_add(self, conn, oid_hex: str, addr: str):
        self._obj_locations.setdefault(oid_hex, set()).add(addr)
        return {"ok": True}

    async def _on_object_location_remove(
        self, conn, oid_hex: str, addrs: list
    ):
        locs = self._obj_locations.get(oid_hex)
        if locs:
            locs.difference_update(addrs)
        return {"ok": True}

    async def _connect_peer(
        self, addr: str, retries: int = 3
    ) -> rpc.Connection:
        conn = self._peers.get(addr)
        if conn is not None and not conn._closed:
            return conn
        conn = await rpc.connect(addr, retries=retries)
        self._peers[addr] = conn
        return conn

    async def _on_prefetch_object(
        self, conn, oid_hex: str, owner_addr: str, timeout: float = 120.0
    ):
        """Pull an object into THIS node's store (the broadcast relay
        primitive; reference: push_manager.h:28 — the reference pushes
        chunks at nodes, here the coordinator asks nodes to pull, and
        each completed node registers itself as a source for the next
        wave)."""
        from ray_tpu._private.ids import ObjectID
        from ray_tpu.runtime import transfer
        from ray_tpu._private.serialization import Serialized

        oid = ObjectID.from_hex(oid_hex)
        store = self._store()
        if store.contains(oid):
            return {"ok": True, "cached": True}
        owner = await self._connect_peer(owner_addr)
        # tpulint: allow(rpc-reentrancy reason=owner is a PEER node resolved from owner_addr, never this server; pull_object below would deadlock loopback anyway and never does)
        reply = await owner.call("get_object", oid_hex=oid_hex)
        if reply["kind"] == "value":
            store.put(
                oid, Serialized(reply["inband"], list(reply["buffers"]))
            )
        elif reply["kind"] == "in_store":
            srcs, addr_of = await transfer.connect_sources(
                reply.get("holders"),
                reply.get("holder"),
                self.addr,
                lambda a: self._connect_peer(a, retries=1),
                fallback=owner,
            )
            failed: set = set()
            try:
                inband, buffers = await transfer.pull_object(
                    oid_hex, srcs, timeout, failed=failed
                )
            finally:
                bad = [addr_of[c] for c in failed if c in addr_of]
                if bad:
                    try:
                        # tpulint: allow(rpc-reentrancy reason=owner is a peer node connection, not this process)
                        await owner.call(
                            "object_location_remove",
                            oid_hex=oid_hex,
                            addrs=bad,
                        )
                    except (rpc.ConnectionLost, rpc.RpcError):
                        pass
            store.put(oid, Serialized(inband, list(buffers)))
        else:
            return {"ok": False, "error": f"unexpected kind {reply['kind']}"}
        try:
            # tpulint: allow(rpc-reentrancy reason=owner is a peer node connection, not this process)
            await owner.call(
                "object_location_add", oid_hex=oid_hex, addr=self.addr
            )
        except (rpc.ConnectionLost, rpc.RpcError):
            pass
        return {"ok": True, "cached": False}

    async def _on_prefetch_objects(
        self,
        conn,
        oids: list,
        owner_addr: str,
        timeout: float = 120.0,
        concurrency: int = 4,
    ):
        """Batched prefetch (the checkpoint-replication primitive): pull
        many content-addressed chunks into this node's store from one
        owner, skipping the ones already held. Per-oid results let the
        caller record exactly which replicas landed."""
        sem = asyncio.Semaphore(max(1, concurrency))
        results: dict[str, bool] = {}

        async def one(oid_hex: str):
            async with sem:
                try:
                    r = await self._on_prefetch_object(
                        conn, oid_hex, owner_addr, timeout
                    )
                    results[oid_hex] = bool(r.get("ok"))
                # tpulint: allow(broad-except reason=per-chunk prefetch failure is the RESULT of this batch op, reported per-oid to the caller; logging each would spam on a dead owner)
                except Exception:
                    results[oid_hex] = False

        await asyncio.gather(*(one(o) for o in list(oids)))
        return {"ok": True, "results": results}

    async def _on_delete_objects(self, conn, oids: list):
        """Drop store copies (checkpoint-chunk GC from the head)."""
        from ray_tpu._private.ids import ObjectID

        store = self._store()
        deleted = 0
        for oid_hex in oids:
            try:
                store.delete(ObjectID.from_hex(oid_hex))
                deleted += 1
            except ValueError:
                continue
        return {"ok": True, "deleted": deleted}

    async def _on_ckpt_reconstruct(
        self,
        conn,
        chunk: str,
        k: int,
        m: int,
        member: int,
        rows: list,
        lens: list | None = None,
    ):
        """Erasure repair executor: gather ≥k surviving members of a
        parity group (local store first, then their recorded holders),
        decode the lost member, verify it by content hash, and keep the
        result in THIS node's store. The head picks the node already
        holding the most survivors, so most member reads are local."""
        from ray_tpu.checkpoint import erasure
        from ray_tpu.checkpoint.store import ShardStore, chunk_hash
        from ray_tpu.runtime import transfer

        store = ShardStore(self._store())
        if store.has_chunk(chunk):
            return {"ok": True, "cached": True}
        present: dict[int, bytes] = {}
        for row in rows:
            if len(present) >= int(k):
                break
            mh = row["hash"]
            data = store.get_chunk(mh)
            if data is None:
                for addr in row.get("addrs", ()):
                    if addr == self.addr:
                        continue
                    try:
                        peer = await self._connect_peer(addr, retries=1)
                        data, _bufs = await transfer.pull_object(
                            mh, [peer]
                        )
                    # tpulint: allow(broad-except reason=dead survivor holder mid-repair is expected; the next addr or the next repair tick covers it)
                    except Exception:
                        data = None
                        continue
                    if data is not None and chunk_hash(data) == mh:
                        break
                    data = None
            if data is not None:
                present[int(row["member"])] = data
        if len(present) < int(k):
            return {
                "ok": False,
                "error": f"only {len(present)}/{k} group members "
                "reachable",
            }
        try:
            data = erasure.recover_member(
                int(k), int(m), present, int(member), lens
            )
        # tpulint: allow(broad-except reason=a singular survivor set or corrupt member must report as a typed per-chunk failure to the head, not kill the RPC server)
        except Exception as e:
            return {"ok": False, "error": f"decode failed: {e!r}"}
        if chunk_hash(data) != chunk:
            return {
                "ok": False,
                "error": "reconstructed bytes fail content-hash check",
            }
        store.put_chunk(chunk, data)
        return {"ok": True, "cached": False}

    async def _on_get_object_meta(self, conn, oid_hex: str):
        from ray_tpu._private.ids import ObjectID
        from ray_tpu.runtime.object_store import segment_meta

        oid = ObjectID.from_hex(oid_hex)
        store = self._store()
        view = store.get(oid)
        if view is None:
            return {"ok": False}
        try:
            return segment_meta(view)
        finally:
            # The daemon never exits: cached mmaps would pin shm pages
            # for every object ever served.
            store.release(oid)

    async def _on_get_object_chunk(
        self, conn, oid_hex: str, offset: int, size: int
    ):
        from ray_tpu._private.ids import ObjectID
        from ray_tpu.runtime.object_store import segment_window

        oid = ObjectID.from_hex(oid_hex)
        store = self._store()
        view = store.get(oid)
        if view is None:
            return {"ok": False}
        try:
            return {"ok": True, "data": segment_window(view, offset, size)}
        finally:
            store.release(oid)

    async def _on_register_worker(
        self, conn, worker_id: str, addr: str, pid: int
    ):
        w = self.workers.setdefault(worker_id, {})
        w.update(conn=conn, addr=addr, pid=pid, state="idle")
        conn.state["worker_id"] = worker_id
        self._offer_worker(worker_id)
        spawned_at = w.pop("spawned_at", None)
        if spawned_at is not None:
            self._emit_span(
                "startup:spawn", spawned_at, time.time() - spawned_at,
                worker_id=worker_id, pid=pid,
                platform=w.get("platform", "cpu"),
            )
        return {"ok": True, "node_id": self.node_id}

    def _offer_worker(self, worker_id: str):
        w = self.workers.get(worker_id, {})
        own = w.pop("waiter", None)
        if own is not None:  # started for one lease (_get_chip_worker)
            if own.done():  # the grant gave up waiting for it
                self._kill_worker(worker_id)
            else:
                own.set_result(worker_id)
            return
        ehash = w.get("env_hash", "")
        waiters = self._worker_waiters[ehash]
        while waiters:
            fut = waiters.popleft()
            if not fut.done():
                fut.set_result(worker_id)
                return
        self.idle[ehash].append(worker_id)

    async def _on_lease_worker(
        self,
        conn,
        resources: dict | None = None,
        actor: bool = False,
        bundle: tuple | list | None = None,
        runtime_env: dict | None = None,
    ):
        received_at = time.time()
        grant = await self._lease_worker(
            resources, actor, bundle, runtime_env
        )
        lease = self.leases.get(grant.get("lease_id"))
        if lease is not None:
            held = lease.bundle_resources or lease.resources
            self._emit_span(
                "startup:lease", received_at, time.time() - received_at,
                lease_id=lease.lease_id,
                worker_id=lease.worker["worker_id"],
                platform=lease.worker.get("platform", "cpu"),
                tpu=held.get("TPU", 0),
                queued_s=lease.began_at - received_at,
            )
        return grant

    async def _lease_worker(
        self,
        resources: dict | None,
        actor: bool,
        bundle: tuple | list | None,
        runtime_env: dict | None,
    ) -> dict:
        """Grant a worker lease (reference: NodeManager::
        HandleRequestWorkerLease node_manager.h:290). Infeasible requests
        fail fast; unavailable ones queue until resources free up. With
        ``bundle`` = (pg_id, index), resources come from that reserved
        placement-group bundle instead of the node's general pool."""
        resources = dict(resources or {"CPU": 1.0})
        if self.draining and bundle is None:
            # retry_spill (not infeasible): the caller's spillback path
            # re-picks through the head, which excludes draining nodes.
            # Bundle-backed leases stay honored — the bundle was gang-
            # reserved before the drain and dies with the node anyway.
            return {
                "ok": False,
                "retry_spill": True,
                "draining": True,
                "error": "node is draining; lease elsewhere",
            }
        if bundle is not None:
            b = self.bundles.get(tuple(bundle))
            if b is None:
                return {"ok": False, "error": f"no bundle {bundle} here"}
            if any(b["available"].get(k, 0) < v for k, v in resources.items()):
                return {
                    "ok": False,
                    "error": f"bundle {bundle} lacks {resources}",
                }
            for k, v in resources.items():
                b["available"][k] -= v
            # The lease draws on the bundle, not the general pool — spawn
            # a worker without double-charging node resources. Credit the
            # bundle back if the grant itself fails (worker spawn error).
            try:
                grant = await self._grant_lease(
                    {}, actor, runtime_env, held=resources
                )
            except Exception:
                for k, v in resources.items():
                    b["available"][k] += v
                raise
            lease = self.leases[grant["lease_id"]]
            lease.bundle = tuple(bundle)
            lease.bundle_resources = resources
            grant["bundle"] = tuple(bundle)
            return grant
        if not self._feasible(resources):
            return {
                "ok": False,
                "infeasible": True,
                "error": f"infeasible request {resources} on {self.total}",
            }
        if self._available(resources):
            return await self._grant_lease(resources, actor, runtime_env)
        fut = asyncio.get_running_loop().create_future()
        self._pending.append(
            (resources, actor, fut, asyncio.get_running_loop().time(),
             runtime_env)
        )
        self._bump_resources()  # queued demand is a scale-up signal
        return await fut

    def _credit_bundle(self, lease: "Lease"):
        if lease.bundle is None:
            return
        b = self.bundles.get(lease.bundle)
        if b is not None and lease.bundle_resources:
            for k, v in lease.bundle_resources.items():
                b["available"][k] = b["available"].get(k, 0) + v

    async def _on_return_lease(self, conn, lease_id: str):
        lease = self.leases.pop(lease_id, None)
        if lease is None:
            return {"ok": False}
        self._release(lease.resources)
        self._credit_bundle(lease)
        worker_id = lease.worker["worker_id"]
        w = self.workers.get(worker_id)
        if w and w.get("chips"):
            # It has opened the chip and keeps it while it lives: the
            # next chip lease gets a new process once this one is gone.
            self._kill_worker(worker_id)
        elif w and w.get("state") == "leased":
            w["state"] = "idle"
            ehash = w.get("env_hash", "")
            if self._worker_waiters[ehash]:
                # Hand the warm worker straight to a blocked lease grant
                # rather than parking (or killing) it while the grant
                # waits out an interpreter spawn.
                self._offer_worker(worker_id)
            else:
                self.idle[ehash].append(worker_id)
                self._enforce_idle_cap()
        self._drain_pending()
        return {"ok": True}

    async def _on_reserve_bundle(
        self, conn, pg_id: str, index: int, resources: dict
    ):
        resources = dict(resources)
        if self.draining and (pg_id, index) not in self.bundles:
            # The head's planner already excludes draining nodes; this
            # backstops a plan computed before the drain landed.
            return {
                "ok": False,
                "error": f"node {self.node_id[:8]} is draining",
            }
        if (pg_id, index) in self.bundles:
            # Idempotent re-reserve: the head may retry after a lost
            # response (reference: node_manager.proto documents per-RPC
            # idempotence for the 2PC prepare/commit).
            return {"ok": True}
        if not self._available(resources):
            return {
                "ok": False,
                "error": f"bundle {resources} unavailable on {self.node_id[:8]}",
            }
        self._acquire(resources)
        self.bundles[(pg_id, index)] = {
            "total": resources,
            "available": dict(resources),
        }
        return {"ok": True}

    async def _on_free_bundle(self, conn, pg_id: str, index: int):
        b = self.bundles.pop((pg_id, index), None)
        if b is None:
            return {"ok": False}
        self._release(b["total"])
        self._drain_pending()
        return {"ok": True}

    async def _on_kill_worker(self, conn, worker_id: str, force: bool = True):
        self._kill_worker(worker_id)
        self._release_worker_leases(worker_id)
        # _kill_worker drops the record, so the reap loop never sees this
        # death — publish it here or collective groups (and any other
        # "worker" subscriber) would only learn via op deadlines.
        if self.head:
            try:
                await self.head.call(
                    "publish",
                    channel="worker",
                    msg={"event": "died", "worker_id": worker_id},
                )
            except rpc.RpcError:
                pass
        return {"ok": True}

    def _release_worker_leases(self, worker_id: str):
        """Free leases of a worker killed OUTSIDE the reap loop
        (_kill_worker removes it from the table so the reap loop never
        sees the death, and lease holders that saw ConnectionLost will
        not return their lease)."""
        for lease_id, lease in list(self.leases.items()):
            if lease.worker["worker_id"] == worker_id:
                self.leases.pop(lease_id)
                self._release(lease.resources)
                self._credit_bundle(lease)
        self._drain_pending()

    async def _on_list_workers(self, conn):
        """Worker inventory for chaos tooling and debugging (reference:
        the state API's worker table; killers in test_utils.py:1646)."""
        out = []
        leased_ids = {
            lease.worker["worker_id"]: lease.actor
            for lease in self.leases.values()
        }
        for wid, w in self.workers.items():
            out.append({
                "worker_id": wid,
                "pid": w.get("pid"),
                "state": w.get("state"),
                "leased": wid in leased_ids,
                "is_actor": bool(leased_ids.get(wid)),
                "platform": w.get("platform", "cpu"),
            })
        return {"workers": out}

    async def _on_node_info(self, conn):
        return {
            "node_id": self.node_id,
            "addr": self.addr,
            "resources": self.total,
            "available": self.available,
            "n_workers": len(self.workers),
            "store_dir": self.store_dir,
            "spilled_bytes": self.spilled_bytes,
            "spilled_objects": self.spilled_objects,
            "oom_kills": self.oom_kills,
            "draining": self.draining,
            "drain_info": self.drain_info,
        }

    def _enforce_idle_cap(self):
        """Cap TOTAL idle workers across all runtime_env pools: many
        distinct envs must not each park IDLE_WORKER_CAP interpreters.
        Evicts from the fullest bucket (oldest entry first)."""
        while (
            sum(len(b) for b in self.idle.values()) > IDLE_WORKER_CAP
        ):
            ehash = max(self.idle, key=lambda k: len(self.idle[k]))
            victim = self.idle[ehash].pop(0)
            self._kill_worker(victim)

    def _kill_worker(self, worker_id: str):
        w = self.workers.pop(worker_id, None)
        if not w:
            return
        ehash = w.get("env_hash", "")
        if worker_id in self.idle[ehash]:
            self.idle[ehash].remove(worker_id)
        proc = w.get("proc")
        if proc and proc.poll() is None:
            proc.kill()
            if w.get("chips"):
                self._dying_chip_procs.append(proc)
        core = w.get("core")
        if core is not None:  # inproc worker: stop its rpc endpoints
            asyncio.ensure_future(core.stop())
        _env_cache.release(ehash)

    def _drain_pending(self):
        now = asyncio.get_event_loop().time()
        still = []
        for resources, actor, fut, ts, runtime_env in self._pending:
            if fut.done():
                continue
            if self._available(resources):
                asyncio.ensure_future(
                    self._fulfil(resources, actor, fut, runtime_env)
                )
            elif now - ts > PENDING_SPILL_S:
                fut.set_result(
                    {"ok": False, "retry_spill": True,
                     "error": "queued past age limit; spill via head"}
                )
            else:
                still.append((resources, actor, fut, ts, runtime_env))
        if len(still) != len(self._pending):
            self._bump_resources()
        self._pending = still

    async def _fulfil(self, resources, actor, fut, runtime_env=None):
        try:
            result = await self._grant_lease(resources, actor, runtime_env)
            if not fut.done():
                fut.set_result(result)
        # tpulint: allow(broad-except reason=failure propagates to the waiter via fut.set_exception, not swallowed)
        except Exception as e:
            if not fut.done():
                fut.set_exception(e)

    # ------------------------------------------------------------- loops
    async def _log_monitor_loop(self):
        """Tail worker log files and publish new output on the "logs"
        pubsub channel; drivers subscribed there print it (reference:
        LogMonitor log_monitor.py:116 tails /tmp/ray/session_*/logs and
        streams to the driver, worker.py:2295 print_worker_logs)."""
        MAX_SHIP = 64 * 1024  # per worker per tick; floods are chunked
        loop = asyncio.get_running_loop()
        while True:
            await asyncio.sleep(0.3)
            try:
                if self.head is None or not self.log_dir.is_dir():
                    continue
                for path in self.log_dir.glob("worker-*.log"):
                    name = path.name
                    try:
                        size = path.stat().st_size
                    except OSError:
                        continue
                    off = self._log_offsets.get(name, 0)
                    if size <= off:
                        continue

                    def read_chunk(path=path, off=off):
                        with open(path, "rb") as f:
                            f.seek(off)
                            return f.read(MAX_SHIP)

                    data = await loop.run_in_executor(None, read_chunk)
                    if not data:
                        continue
                    wid = name[len("worker-"):-len(".log")]
                    w = self.workers.get(wid, {})
                    # retry=False: a publish whose ack was lost across a
                    # head restart must not re-send — subscribers would
                    # see the same log chunk twice. The offset advances
                    # only once the chunk was (at least) handed to the
                    # wire: a provably-unsent chunk (sent=False) is
                    # re-read next tick instead of vanishing.
                    try:
                        await self.head.call(
                            "publish",
                            retry=False,
                            channel="logs",
                            msg={
                                "worker_id": wid,
                                "node_id": self.node_id,
                                "pid": w.get("pid"),
                                "data": data.decode("utf-8", "replace"),
                            },
                        )
                    except rpc.RpcError as e:
                        if getattr(e, "sent", True) is False:
                            continue  # never reached the wire: retry it
                    self._log_offsets[name] = off + len(data)
            except asyncio.CancelledError:
                raise
            except Exception:
                # Best-effort: the node's own logger is NOT among the
                # tailed worker logs, so this cannot feedback-loop.
                logger.debug("log shipping tick failed", exc_info=True)

    async def _on_list_logs(self, conn):
        out = []
        if self.log_dir.is_dir():
            for path in sorted(self.log_dir.glob("worker-*.log")):
                wid = path.name[len("worker-"):-len(".log")]
                w = self.workers.get(wid)
                try:
                    size = path.stat().st_size
                except OSError:
                    continue
                out.append(
                    {
                        "worker_id": wid,
                        "size": size,
                        "alive": bool(
                            w
                            and w.get("proc")
                            and w["proc"].poll() is None
                        ),
                    }
                )
        return {"logs": out, "node_id": self.node_id}

    async def _on_read_log(
        self,
        conn,
        worker_id: str,
        offset: int = 0,
        max_bytes: int = 1 << 20,
    ):
        """Serve a worker's captured log — including DEAD workers'
        (reference: `ray logs` reads session log files after the worker
        exits). Prefix match on worker_id; negative offset = tail."""
        matches = [
            p
            for p in self.log_dir.glob("worker-*.log")
            if p.name[len("worker-"):-len(".log")].startswith(worker_id)
        ]
        if not matches:
            return {"ok": False, "error": f"no log for worker {worker_id!r}"}
        path = sorted(matches)[0]
        size = path.stat().st_size
        if offset < 0:
            offset = max(0, size + offset)
        with open(path, "rb") as f:
            f.seek(offset)
            data = f.read(max_bytes)
        return {
            "ok": True,
            "worker_id": path.name[len("worker-"):-len(".log")],
            "offset": offset,
            "size": size,
            "data": data,
        }

    async def _register_with_head(self, conn: "rpc.Connection"):
        """(Re-)announce this node. Runs at startup AND after every head
        reconnect, so a restarted head rebuilds its node table from live
        nodes (reference: raylet re-registration on NotifyGCSRestart)."""
        await conn.call(
            "register_node",
            node_id=self.node_id,
            addr=self.addr,
            resources=self.total,
            # The CURRENT view, not the totals: re-registration after a
            # connection blip must not reset the head to full capacity
            # while leases are live.
            available=self.available,
            res_version=self._res_version,
            labels=self.labels,
            agent_addr=self.agent.addr if self.agent else None,
        )
        # Force a follow-up sync regardless: the version counter keeps
        # moving, so a concurrent change between snapshot and reply
        # can't be skipped as already-sent.
        self._bump_resources()

    _SYNC_KEEPALIVE_S = 5.0
    _SYNC_DEBOUNCE_S = 0.02

    async def _heartbeat_loop(self):
        """Resource-view sync (reference: ray_syncer.h:90 — streaming
        versioned updates, not polling). A resource CHANGE (lease
        grant/release, queued demand, bundle ops) wakes this loop
        immediately and pushes one versioned update — sub-50ms
        propagation instead of a 2s poll; an unchanged view sends only
        a tiny keepalive every _SYNC_KEEPALIVE_S so the head's health
        loop still sees liveness. At 2,000 idle nodes this is ~400
        payload-free messages/s cluster-wide instead of 1,000 full
        snapshots/s."""
        sent_version = -1
        while True:
            try:
                await asyncio.wait_for(
                    self._sync_event.wait(), timeout=self._SYNC_KEEPALIVE_S
                )
                # Coalesce bursts (a lease storm is one update).
                await asyncio.sleep(self._SYNC_DEBOUNCE_S)
            except asyncio.TimeoutError:
                pass
            self._sync_event.clear()
            version = self._res_version
            try:
                if version != sent_version:
                    reply = await self.head.call(
                        "sync",
                        node_id=self.node_id,
                        version=version,
                        available=self.available,
                        # Feasible-but-queued lease demand: a scale-up
                        # signal (reference: raylets report
                        # resource_load_by_shape to GCS for
                        # GcsAutoscalerStateManager).
                        pending=[dict(r) for r, *_rest in self._pending],
                    )
                    if reply.get("ok"):
                        sent_version = version
                else:
                    reply = await self.head.call(
                        "keepalive", node_id=self.node_id
                    )
                if not reply.get("ok") and reply.get("reregister"):
                    # The head lost this node's entry (restart, or a
                    # health-loop reap during a long GC pause): rejoin
                    # and force a full re-send.
                    await self._register_with_head(self.head._conn)
                    sent_version = -1
                    self._sync_event.set()
            except rpc.RpcError:
                pass

    async def _spill_loop(self):
        """Watermark-driven object spilling: when the node's shm store
        runs past SPILL_HIGH of capacity, move the coldest sealed
        objects to disk until usage drops below SPILL_LOW. Spilled
        objects are served transparently by ObjectStore.get (and the
        pull protocol), so readers never notice."""
        while True:
            await asyncio.sleep(0.5)
            try:
                high, low = _spill_watermarks()
                store = self._store()
                cap = store.capacity_bytes
                if not cap:
                    continue

                def spill_tick():
                    # All filesystem scanning runs here, OFF the event
                    # loop: the daemon also serves chunked object pulls
                    # and must not stall on iterdir/stat storms.
                    used = store.used_bytes()
                    if used <= high * cap:
                        return 0, 0
                    target = low * cap
                    freed_total = 0
                    n = 0
                    for oid, _size, _lru in store.spill_candidates():
                        if used - freed_total <= target:
                            break
                        try:
                            freed = store.spill_one(oid)
                        except OSError:
                            continue
                        if freed:
                            freed_total += freed
                            n += 1
                    return freed_total, n

                freed, n = await asyncio.to_thread(spill_tick)
                self.spilled_bytes += freed
                self.spilled_objects += n
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.warning(
                    "object spill tick failed (disk full? bad spill "
                    "dir?)", exc_info=True,
                )

    async def _memory_loop(self):
        """Kill a worker when the host runs out of memory (reference:
        MemoryMonitor memory_monitor.h:52 + WorkerKillingPolicy
        worker_killing_policy.h:33). Policy: newest NON-ACTOR lease
        first — its task is retriable and has lost the least work;
        actors are last resorts (their state dies with them)."""
        from ray_tpu._private import config

        while True:
            await asyncio.sleep(1.0)
            try:
                # Re-read each tick so runtime overrides apply, same as
                # the spill watermarks.
                if system_memory_fraction() < config.get("MEMORY_THRESHOLD"):
                    continue
                victim = self._pick_oom_victim()
                if victim is None:
                    continue
                lease, wid = victim
                self.oom_kills += 1
                rss = worker_rss_bytes(lease.worker.get("pid") or 0)
                self._kill_worker(wid)
                self._release_worker_leases(wid)
                if self.head:
                    try:
                        await self.head.call(
                            "publish",
                            channel="worker",
                            msg={
                                "event": "oom_killed",
                                "worker_id": wid,
                                "node_id": self.node_id,
                                "rss": rss,
                            },
                        )
                    except rpc.RpcError:
                        pass
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.debug("memory monitor tick failed",
                             exc_info=True)

    def _pick_oom_victim(self):
        """(lease, worker_id) to kill, or None. Newest task lease first,
        then newest actor lease (reference: the retriable-first ordering
        of worker_killing_policy_group_by_owner.h:87)."""
        candidates = sorted(
            (
                (not lease.actor, lease.granted_at, lease, lease.worker["worker_id"])
                for lease in self.leases.values()
                if lease.worker.get("worker_id") in self.workers
            ),
            key=lambda t: (t[0], t[1]),
            reverse=True,
        )
        if not candidates:
            return None
        _, _, lease, wid = candidates[0]
        return lease, wid

    async def _reap_loop(self):
        """Detect worker process death and fail affected leases
        (reference: raylet detects worker death via process wait + IPC
        disconnect, SURVEY.md section 5)."""
        while True:
            await asyncio.sleep(1.0)
            # Age-bounce stale queued leases even when no grant/return
            # event fires (the age check lives in _drain_pending).
            self._drain_pending()
            self._prune_uploads()
            dead = [
                wid
                for wid, w in self.workers.items()
                if (
                    w.get("proc") is not None
                    and w["proc"].poll() is not None
                )
                or w.get("exited")  # inproc worker told to exit
            ]
            for wid in dead:
                w = self.workers.pop(wid, None)
                ehash = (w or {}).get("env_hash", "")
                if wid in self.idle[ehash]:
                    self.idle[ehash].remove(wid)
                _env_cache.release(ehash)
                own = (w or {}).get("waiter")
                if own is not None and not own.done():
                    own.set_exception(
                        rpc.RpcError(
                            f"worker {wid[:8]} started for a TPU lease "
                            f"died before registering; see "
                            f"{w.get('log_path')}"
                        )
                    )
                elif (
                    w
                    and w.get("state") == "spawning"
                    and self._worker_waiters[ehash]
                ):
                    # A worker died mid-spawn with grants still blocked on
                    # registration — spawn a replacement (same runtime_env)
                    # rather than letting the waiter run out the timeout.
                    # Reuse the dead worker's ehash: recomputing could
                    # hash an edited working_dir differently and strand
                    # the waiters in the old bucket.
                    self._spawn_worker(w.get("runtime_env"), ehash=ehash)
                for lease_id, lease in list(self.leases.items()):
                    if lease.worker["worker_id"] == wid:
                        self.leases.pop(lease_id)
                        self._release(lease.resources)
                        self._credit_bundle(lease)
                if self.head:
                    try:
                        await self.head.call(
                            "publish",
                            channel="worker",
                            msg={"event": "died", "worker_id": wid},
                        )
                    except rpc.RpcError:
                        pass
            if dead:
                self._drain_pending()


def detect_labels() -> dict[str, str]:
    """Node labels: accelerator topology from the plugin registry
    (reference: TPU env vars become labels, accelerators/tpu.py:18–66 +
    util/tpu.py slice labels) plus user labels from RAY_TPU_NODE_LABELS
    (k=v,k=v)."""
    from ray_tpu._private import config
    from ray_tpu._private.accelerators import detect_accelerator_labels

    labels: dict[str, str] = {}
    for pair in config.get("NODE_LABELS").split(","):
        if "=" in pair:
            k, v = pair.split("=", 1)
            labels[k.strip()] = v.strip()
    labels.update(detect_accelerator_labels())
    labels.update(_gce_metadata_labels())
    # Canonical slice fault-domain label: the head's slice table, the
    # checkpoint replicator's cross-slice placement, and the autoscaler's
    # slice-unit replacement all key on "slice". On real TPU VMs the
    # accelerator plugin reports the slice name under the ray-style
    # label; alias it unless the operator set "slice" explicitly.
    if "slice" not in labels and labels.get("ray_tpu.io/tpu-slice-name"):
        labels["slice"] = labels["ray_tpu.io/tpu-slice-name"]
    return labels


def _gce_metadata_labels() -> dict[str, str]:
    """On GCE/GKE VMs, pick up the provider id the autoscaler stamped
    into instance metadata (gcp.py create_node) so the autoscaler can
    map its provider node ids to registered runtime nodes. The DMI
    product name gates the network probe — non-GCE hosts never touch
    the metadata endpoint."""
    try:
        with open("/sys/class/dmi/id/product_name") as f:
            if "Google" not in f.read():
                return {}
    except OSError:
        return {}
    import urllib.request

    labels: dict[str, str] = {}
    base = "http://metadata.google.internal/computeMetadata/v1/instance/"
    # node_pool-mode slices have no stamped provider id (setSize is
    # anonymous); the instance NAME is what the provider's targeted
    # scale-down and runtime_node_id match against instead.
    for path, key in (
        ("attributes/ray-tpu-provider-id", "ray-tpu-provider-id"),
        ("name", "ray-tpu-gce-instance"),
    ):
        try:
            req = urllib.request.Request(
                base + path, headers={"Metadata-Flavor": "Google"}
            )
            with urllib.request.urlopen(req, timeout=2) as resp:
                value = resp.read().decode().strip()
            if value:
                labels[key] = value
        except OSError:
            pass
    return labels
