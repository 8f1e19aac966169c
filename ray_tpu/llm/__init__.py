"""TPU-native LLM library (ray.llm equivalent).

The reference delegates engines to vLLM (reference:
python/ray/llm/_internal/serve/engines/vllm/vllm_models.py:234 passes
tensor_parallel_size through; vllm_engine.py gang-schedules workers on
placement groups). Here the engine is native: static-shape JAX prefill
and decode programs over one paged KV cache (`paged_kv.py`; continuous
batching, chunked prefill, prompt-lookup speculation), with tensor
parallelism as a pjit sharding of the same programs — no external engine
process.

- :class:`LLMEngine` — prefill + decode with continuous batching.
- :func:`build_llm_deployment` — serve integration.
- :func:`build_batch_inferencer` — Data integration (map_batches actors).
"""

from ray_tpu.llm.engine import LLMEngine, SamplingParams
from ray_tpu.llm.serve_integration import build_llm_deployment
from ray_tpu.llm.batch import build_batch_inferencer
from ray_tpu.llm.tokenizer import ByteTokenizer

__all__ = [
    "ByteTokenizer",
    "LLMEngine",
    "SamplingParams",
    "build_batch_inferencer",
    "build_llm_deployment",
]

from ray_tpu._private.usage import record_library_usage as _rlu

_rlu('llm')
del _rlu
