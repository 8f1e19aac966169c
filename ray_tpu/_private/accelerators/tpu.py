"""TPU accelerator manager (reference:
python/ray/_private/accelerators/tpu.py:18–66 — TPU_VISIBLE_CHIPS, GKE
env vars, devfs chip files; topology env vars become labels the way
util/tpu.py slice scheduling expects)."""

from __future__ import annotations

import glob
import os

from ray_tpu._private.accelerators.accelerator import AcceleratorManager


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
        try:
            # One /dev/accel* node or one /dev/vfio/<group> per chip;
            # /dev/vfio/vfio is the container device, not a chip.
            chips = glob.glob("/dev/accel*") or glob.glob("/dev/vfio/*")
            return len([c for c in chips if c != "/dev/vfio/vfio"])
        except OSError:
            return 0

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
