from . import model
from .model import (
    cache_sds,
    cache_specs,
    count_params,
    decode_step,
    forward,
    init_cache,
    init_params,
    loss_fn,
    paged_step,
    param_defs,
    param_sds,
    param_specs,
    prefill,
)

__all__ = [
    "model",
    "cache_sds",
    "cache_specs",
    "count_params",
    "decode_step",
    "forward",
    "init_cache",
    "init_params",
    "loss_fn",
    "paged_step",
    "param_defs",
    "param_sds",
    "param_specs",
    "prefill",
]
