"""TPU accelerator manager (reference:
python/ray/_private/accelerators/tpu.py:18–66 — TPU_VISIBLE_CHIPS, GKE
env vars, devfs chip files; topology env vars become labels the way
util/tpu.py slice scheduling expects)."""

from __future__ import annotations

import errno
import glob
import os

from ray_tpu._private.accelerators.accelerator import AcceleratorManager

# One /dev/accel* node or one /dev/vfio/<group> per chip; /dev/vfio/vfio
# is the container device, not a chip.
_CHIP_NODE_GLOBS = ("/dev/accel*", "/dev/vfio/*")


def chip_nodes() -> list[str]:
    """The device nodes of this host's chips, one a chip."""
    for pattern in _CHIP_NODE_GLOBS:
        nodes = sorted(c for c in glob.glob(pattern) if c != "/dev/vfio/vfio")
        if nodes:
            return nodes
    return []


def busy_chips(nodes=None, opener=os.open) -> dict[str, list[int]]:
    """The chip nodes that are not free, each with the pids that have it
    open. The verdict is the open's alone: a node is busy while
    ``open(node, O_RDWR)`` fails with EBUSY (one that opens is closed at
    once; any other error is not ours to wait for and counts as free),
    because the kernel goes on closing a dead holder's vfio groups for
    seconds after its pid has left ``/proc`` (PERF.md section 7). The
    pids, a walk of ``/proc/*/fd``, only tell a live holder from a group
    nobody holds any more, and go into the error's text. An open of a
    group that is being let go may block: call this off the event loop.
    ``benchmarks/chipwait.py`` asks the same question for the instrument
    and is its own copy by design: neither imports the other."""
    busy: dict[str, list[int]] = {}
    for node in chip_nodes() if nodes is None else nodes:
        try:
            os.close(opener(node, os.O_RDWR))
        except OSError as e:
            if e.errno == errno.EBUSY:
                busy[node] = []
    if not busy:
        return busy
    for fds in glob.glob("/proc/[0-9]*/fd"):
        try:
            for fd in os.listdir(fds):
                held = os.readlink(os.path.join(fds, fd))
                if held in busy:
                    busy[held].append(int(fds.split("/")[2]))
        except OSError:  # gone meanwhile, or not ours to read
            continue
    return {node: sorted(set(pids)) for node, pids in busy.items()}


class TPUAcceleratorManager(AcceleratorManager):
    def resource_name(self) -> str:
        return "TPU"

    def detect_count(self) -> int:
        from ray_tpu._private import config

        fake = config.get("FAKE_CHIPS")
        if fake != "":  # "0" is a valid fake (simulate a chipless host)
            return int(fake)
        return self.real_chips()

    def real_chips(self) -> int:
        """Physical chips this host exposes (never the FAKE_CHIPS test
        hook): the lease->platform rule sends a worker to the TPU only
        where this is non-zero."""
        from ray_tpu._private import config

        if config.get("FAKE_CHIPS") != "":
            return 0
        visible = os.environ.get("TPU_VISIBLE_CHIPS")
        if visible is None:
            visible = os.environ.get("TPU_VISIBLE_DEVICES")
        if visible is not None:
            # "" means explicitly zero visible chips.
            return len([c for c in visible.split(",") if c])
        return len(chip_nodes())

    def detect_labels(self) -> dict[str, str]:
        labels: dict[str, str] = {}
        for var, label in (
            ("TPU_ACCELERATOR_TYPE", "ray_tpu.io/accelerator-type"),
            ("TPU_WORKER_ID", "ray_tpu.io/tpu-worker-id"),
            ("TPU_NAME", "ray_tpu.io/tpu-slice-name"),
        ):
            val = os.environ.get(var)
            if val:
                labels[label] = val
        return labels
