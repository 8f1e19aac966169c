"""From a configuration file's published keys to the program's config.

A configuration file carries the model's sizes under the names its
public ``config.json`` uses. ``models/llama.py`` has its own names, and
derives or fixes three things a file could contradict; those are checked
here, so that a file cannot state a size the program does not run.
"""

from __future__ import annotations


def llama_config(model: dict, **program):
    """``LlamaConfig`` for the published keys in ``model``; ``program``
    are fields of the program's own (``attn_impl``, ``remat``,
    ``max_seq``)."""
    from ray_tpu.models.llama import LlamaConfig

    if model["head_dim"] * model["num_attention_heads"] != model["hidden_size"]:
        raise ValueError(
            "models/llama.py derives head_dim as hidden_size / heads; the "
            f"file says {model['head_dim']}"
        )
    if model["rms_norm_eps"] != 1e-5:
        raise ValueError("ops/norms.py fixes rms_norm eps at 1e-5")
    if model.get("sliding_window") is not None:
        raise ValueError("models/llama.py has no sliding-window attention")
    if model.get("tie_word_embeddings"):
        raise ValueError("models/llama.py keeps an untied output head")
    if model.get("hidden_act", "silu") != "silu":
        raise ValueError("models/llama.py's feed-forward is SwiGLU")
    program.setdefault("max_seq", model["max_position_embeddings"])
    return LlamaConfig(
        vocab_size=model["vocab_size"],
        d_model=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"],
        rope_theta=float(model["rope_theta"]),
        **program,
    )
