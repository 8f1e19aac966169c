"""Runner for training cells whose model is not Llama-shaped:
``JaxTrainer.fit()`` with one worker that leases the cell's chips and
runs ``benchmarks/model_loop.py``. The configuration names its model's
module under ``model`` (``benchmarks/models/<model>.py``), which holds
the comparison that decides ``correct`` and the operations the
utilization counts. This process never opens a JAX backend."""

from __future__ import annotations

import math
import statistics
import tempfile
import time

# As runners/train.py: the first step's loss of a randomly initialised
# model is ln(vocab) plus what the spread of its logits adds.
FIRST_LOSS_BAND = 1.0


def run(cell: dict, conf: dict, traffic: dict, args, t_start: float) -> dict:
    import ray_tpu
    from benchmarks import model_loop
    from benchmarks.runners import common
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    # Built here first, so that a program that cannot run the model (a
    # field it does not have) fails before a chip is leased.
    model_loop.program_config(conf, traffic)
    chips = cell["chips"]
    trace = common.trace_plan(cell["name"], args) if args.trace else None
    ray_tpu.init()
    try:
        common.require_chips(chips, bool(args.rehearse))
        called_at = time.time()
        with tempfile.TemporaryDirectory() as storage:
            result = JaxTrainer(
                model_loop.loop,
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
    model = model_loop.model_module(conf)

    tokens = rec["steps"] * rec["tokens_per_step"]
    per_chip = tokens / rec["window_s"] / rec["chips"]
    check = rec["reference_check"]
    vocab = conf["vocab_size"]
    per_step = rec["step_metrics"]
    problems = list(model.check_problems(check))
    if not rec["losses_finite"]:
        problems.append("a loss was not finite")
    if abs(rec["first_loss"] - math.log(vocab)) > FIRST_LOSS_BAND:
        problems.append(
            f"first loss {rec['first_loss']:.3f} is not near "
            f"ln({vocab}) = {math.log(vocab):.3f}"
        )
    if conf["train"]["attn_impl"] == "flash" and rec["device"]["platform"] == "tpu":
        if not rec["tpu_custom_call"]:
            problems.append("no tpu_custom_call in the step program")
    problems += model.step_problems(conf, per_step, rec["tokens_per_step"])
    for p in problems:
        print(f"[bench] NOT CORRECT: {p}")
    print(
        f"[bench] steps={rec['steps']} first_loss={rec['first_loss']:.4f} "
        f"last_loss={rec['last_loss']:.4f} reference_check={check} "
        f"program_peak_bytes={rec['program_peak_bytes']} "
        + " ".join(
            f"{k}={statistics.median(v):.6g}" for k, v in per_step.items()
        )
    )
    median_step = statistics.median(rec["step_s"])
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
            "step_ms_p50": median_step * 1e3,
            "median_step_tokens_per_s_per_chip": rec["tokens_per_step"]
            / rec["chips"] / median_step,
            "seq": traffic["seq"],
            "tokens_per_step_per_chip": rec["tokens_per_step"] / rec["chips"],
            "program_peak_bytes": rec["program_peak_bytes"],
            "step_program_text": rec["program_text"],
            **model.step_counters(per_step),
        },
        "trace_dir": trace["dir"] if trace else None,
    }
