"""Do the chip's device nodes open? The one question ``benchmarks/run.py``
asks before a run's clock starts and again before its process exits.

A TPU v5e chip is a VFIO group, ``/dev/vfio/<n>``, that one process may
have open at a time. When a process that held chips dies, the kernel goes
on closing its devices after its pid has left ``/proc``, one group after
another: for 14-23 s after a four-chip worker on one machine (builder,
PR 48 (refused)), 7-9 s on two others (PR 49); ``open()`` of a group returns
``EBUSY`` meanwhile, or blocks until the group is let go (PERF.md
section 7). Nothing else answers: ``proc.wait()``, a walk of
``/proc/*/fd`` and the node's ``list_workers`` table all say "gone"
while the next process's ``open()`` is still refused. So the probe is
the open itself: every node ``O_RDWR``, closed at once, and only
``EBUSY`` counts as busy. A node that fails any other way (``ENOENT``,
``EACCES``, ``EPERM``, ...) is not ours to wait for: it counts as free
and is named once. A node whose open blocked is named among the busy
ones, and the seconds it blocked are seconds waited; no bound can cut a
blocked ``open()`` short, so the wait can end later than its bound.

The wait guards the *measurement* against whatever ran before it (the
other side of a check, another tenant); it cannot make the system look
better, since a run that met a busy chip used to give no number at all.
That ``ray_tpu.shutdown()`` returns on chips that cannot be opened is
the program's fault and stays open under that name (PERF.md section 7).

Imports nothing of ``ray_tpu`` and nothing of JAX. Where no node exists
(the CPU rehearsals, tier 1) it returns at once without sleeping.
"""

from __future__ import annotations

import errno
import glob
import os
import time

PROBE_EVERY_S = 0.2
# The container of all groups, open to everyone at any time: not a chip.
VFIO_CONTAINER = "/dev/vfio/vfio"


def chip_nodes() -> list[str]:
    """Every ``/dev/vfio/*`` but the container, plus ``/dev/accel*``."""
    groups = [n for n in glob.glob("/dev/vfio/*") if n != VFIO_CONTAINER]
    return sorted(groups + glob.glob("/dev/accel*"))


def wait_chips_free(timeout_s, *, nodes=None, opener=os.open):
    """Probe ``nodes`` every 0.2 s until none is busy or ``timeout_s``
    is up. Returns ``(waited_s, busy_seen, still_busy)``: the seconds
    from the call to the last probe's end, every node ever seen busy,
    and the nodes the last probe found busy (empty unless the time ran
    out). A node whose open blocked for longer than a probe's interval
    was being let go meanwhile: it is among ``busy_seen`` too."""
    if nodes is None:
        nodes = chip_nodes()
    if not nodes:
        return 0.0, [], []
    began = time.monotonic()
    seen: list[str] = []
    not_ours: set[str] = set()
    while True:
        busy, blocked = [], []
        for node in nodes:
            asked = time.monotonic()
            try:
                fd = opener(node, os.O_RDWR)
            except OSError as e:
                if e.errno == errno.EBUSY:
                    busy.append(node)
                elif node not in not_ours:
                    not_ours.add(node)
                    name = errno.errorcode.get(e.errno, e.errno)
                    print(f"[bench] {node}: {name}, not ours to wait for; "
                          "taken as free", flush=True)
            else:
                os.close(fd)
                if time.monotonic() - asked > PROBE_EVERY_S:
                    blocked.append(node)
        seen += [n for n in busy + blocked if n not in seen]
        waited = time.monotonic() - began
        if not busy or waited >= timeout_s:
            return waited, seen, busy
        time.sleep(PROBE_EVERY_S)
