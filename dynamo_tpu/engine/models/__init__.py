"""The model families, one module each, and the one door into them.

A module that ``module_for`` can return is the interface (docs/
architecture.md "What a model family brings"): ``refusals``,
``engine_cache`` and ``prefill_counters`` beside its forward passes and
``param_shapes`` / ``init_one_param`` / ``init_params``."""

from . import granite_hybrid, kimi_linear, llama, mla, sambay

__all__ = ["granite_hybrid", "kimi_linear", "llama", "mla", "sambay",
           "module_for"]


def module_for(cfg):
    """The module that serves a ``ModelConfig``. mimo_v2 is entered through
    ``llama``'s own entry points (ROADMAP D17)."""
    if cfg.is_sambay:
        return sambay
    if cfg.has_kda:
        return kimi_linear
    if cfg.has_ssd:
        return granite_hybrid
    if cfg.kv_lora_rank > 0:
        return mla
    return llama
