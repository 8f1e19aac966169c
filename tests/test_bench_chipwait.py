"""Tier 1 runs the benchmark's own checks of ``benchmarks/chipwait.py``
and of the two places ``benchmarks/run.py`` asks it: the cases live in
``benchmarks/tests/test_chipwait.py``, which ``pytest tests/`` does not
collect, and are imported here as they are, fixtures and all. The
program's own copy of the question (``_private/accelerators/tpu.py
busy_chips``) is held by ``tests/test_chip_lease.py``."""

import pytest

pytest.register_assert_rewrite("benchmarks.tests.test_chipwait")

from benchmarks.tests.test_chipwait import *  # noqa: E402,F401,F403
