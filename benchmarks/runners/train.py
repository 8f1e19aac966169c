"""Runner for training cells: ``JaxTrainer.fit()`` with one worker that
leases the cell's chips and runs ``benchmarks/train_loop.py``. This
process never opens a JAX backend."""

from __future__ import annotations

import math
import statistics
import tempfile
import time

# The first step's loss of a randomly initialised model is the entropy
# of a near-uniform guess, ln(vocab), plus what the spread of its logits
# adds (PR 21 measured +0.4 at these widths). Further off, the model or
# the loss is wrong.
FIRST_LOSS_BAND = 1.0
# Largest |logit| difference between models.forward as the step runs it
# (bf16 weights and activations at use, flash attention) and the float32
# reference, on logits of magnitude ~4: bf16 keeps 8 bits, so a few
# layers of matmuls land within a few hundredths (PR 21 measured
# 0.035-0.044 between two bf16 paths; measured against the float32
# reference in PR 24: see PERF.md). Computing in 8-bit floats, or leaving
# out a term, moves logits by tenths and fails.
LOGIT_TOLERANCE = 0.12


def run(cell: dict, conf: dict, traffic: dict, args, t_start: float) -> dict:
    import ray_tpu
    from benchmarks import train_loop
    from benchmarks.runners import common
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    chips = cell["chips"]
    trace = common.trace_plan(cell["name"], args) if args.trace else None
    ray_tpu.init()
    try:
        common.require_chips(chips, bool(args.rehearse))
        called_at = time.time()
        with tempfile.TemporaryDirectory() as storage:
            result = JaxTrainer(
                train_loop.loop,
                train_loop_config={
                    "config": conf, "traffic": traffic, "seed": args.seed,
                    "seconds": args.seconds, "trace": trace,
                },
                scaling_config=ScalingConfig(
                    num_workers=1, use_tpu=True, chips_per_worker=chips
                ),
                run_config=RunConfig(
                    name=f"bench_{cell['name']}", storage_path=storage
                ),
            ).fit()
        if result.error is not None:
            raise result.error
    finally:
        ray_tpu.shutdown()
    rec = result.metrics

    tokens = rec["steps"] * rec["tokens_per_step"]
    per_chip = tokens / rec["window_s"] / rec["chips"]
    check = rec["reference_check"]
    vocab = conf["vocab_size"]
    problems = []
    if not rec["losses_finite"]:
        problems.append("a loss was not finite")
    if abs(rec["first_loss"] - math.log(vocab)) > FIRST_LOSS_BAND:
        problems.append(
            f"first loss {rec['first_loss']:.3f} is not near "
            f"ln({vocab}) = {math.log(vocab):.3f}"
        )
    if not check["finite"] or check["logit_max_abs_err"] > LOGIT_TOLERANCE:
        problems.append(
            f"logits differ from the reference by "
            f"{check['logit_max_abs_err']:.4f} (tolerance {LOGIT_TOLERANCE})"
        )
    if conf["train"]["attn_impl"] == "flash" and rec["device"]["platform"] == "tpu":
        if not rec["tpu_custom_call"]:
            problems.append("no tpu_custom_call in the step program")
    for p in problems:
        print(f"[bench] NOT CORRECT: {p}")
    print(
        f"[bench] steps={rec['steps']} first_loss={rec['first_loss']:.4f} "
        f"last_loss={rec['last_loss']:.4f} reference_check={check} "
        f"program_peak_bytes={rec['program_peak_bytes']}"
    )
    return {
        "correct": not problems,
        "attempted": rec["steps"],
        "failed": 0 if rec["losses_finite"] else rec["steps"],
        "end_to_end": {
            "train_tokens_per_s": per_chip,
            "setup_s": rec["window_start_at"] - t_start,
        },
        "device": rec["device"],
        "counters": {
            "entry_to_worker_s": rec["first_line_at"] - called_at,
            "compile_s": rec["compile_s"],
            "compiles_in_window": rec["compiles_in_window"],
            "compiled_in_window": rec["compiled_in_window"],
            "step_ms_p50": statistics.median(rec["step_s"]) * 1e3,
            "median_step_tokens_per_s_per_chip": rec["tokens_per_step"]
            / rec["chips"] / statistics.median(rec["step_s"]),
            "seq": traffic["seq"],
        },
        "trace_dir": trace["dir"] if trace else None,
    }
