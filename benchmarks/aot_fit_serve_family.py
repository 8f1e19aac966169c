#!/usr/bin/env python3
"""``aot_fit_serve_model.py`` for any family: do a ``serve_family``
configuration's programs fit the chip? The programs are the ones the
model's own module lowers (``benchmarks/models/<model>.py``,
``lowered_programs``), compiled for a described v5e with no chip
attached, weights and cache included. What this prints is recorded in
the configuration file under ``fit``.

    JAX_PLATFORMS=cpu python3 benchmarks/aot_fit_serve_family.py --config pangu-ultra-moe-serve1 --traffic longdoc-closed

A compile that passes is not a chip run and gives no time.
"""

from __future__ import annotations

import importlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import aot_fit_serve_model as one_model  # noqa: E402


def lowered_programs(conf: dict, traffic: dict, device, use_kernel=True):
    """name -> the lowered program, as `LLMEngine` would call it for this
    configuration and mix: what the family's module says."""
    model = importlib.import_module(f"benchmarks.models.{conf['model']}")
    return model.lowered_programs(conf, traffic, device, use_kernel=use_kernel)


def main() -> None:
    # Its `main` compiles and prints whatever its module's
    # `lowered_programs` returns; the file itself cannot be edited here
    # (folding the two is a benchmark PR's: ROADMAP D20).
    one_model.lowered_programs = lowered_programs
    one_model.main()


if __name__ == "__main__":
    main()
