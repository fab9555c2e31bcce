"""Input / state specs for every (architecture × shape) dry-run cell.

Everything is ShapeDtypeStruct-based (jax.eval_shape): the 405B configs are
lowered and compiled without a single real allocation.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

import repro.models as M
from repro.configs import get_config
from repro.models.config import ModelConfig

SHAPES = {
    "train_4k":    dict(kind="train",   seq=4096,   batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768,  batch=32),
    "decode_32k":  dict(kind="decode",  seq=32768,  batch=128),
    "long_500k":   dict(kind="decode",  seq=524288, batch=1),
}

# long_500k needs sub-quadratic sequence mixing (DESIGN.md §4: skips)
LONG_OK = {"zamba2-7b", "rwkv6-1.6b"}


def cell_is_runnable(arch: str, shape: str) -> tuple[bool, str]:
    if shape == "long_500k" and arch not in LONG_OK:
        return False, "pure full-attention arch: 500k decode skipped (DESIGN.md §4)"
    return True, ""


def all_cells():
    for arch in __import__("repro.configs", fromlist=["ARCH_IDS"]).ARCH_IDS:
        for shape in SHAPES:
            yield arch, shape


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def batch_specs_for(cfg: ModelConfig, shape_name: str) -> dict:
    """Training / prefill batch as ShapeDtypeStructs."""
    info = SHAPES[shape_name]
    B, S = info["batch"], info["seq"]
    i32, bf16 = jnp.int32, jnp.bfloat16
    if cfg.family == "encdec":
        # seq axis = encoder frames (stub frontend); decoder length fixed
        return {"frames": _sds((B, S, cfg.d_model), bf16),
                "tokens": _sds((B, cfg.decoder_max_len), i32),
                "labels": _sds((B, cfg.decoder_max_len), i32)}
    if cfg.rope_type == "mrope":
        return {"embeds": _sds((B, S, cfg.d_model), bf16),
                "positions": _sds((3, B, S), i32),
                "labels": _sds((B, S), i32)}
    return {"tokens": _sds((B, S), i32), "labels": _sds((B, S), i32)}


def decode_inputs_for(cfg: ModelConfig, shape_name: str):
    """(tokens, cache) ShapeDtypeStructs for a serve_step cell.

    The cache length is rounded up to a multiple of 512 so the kv_seq axis
    is cleanly divisible by any mesh-axis product (16, 256, 512) — uneven
    shardings get silently dropped by the divisibility guard and the cache
    then fails to fit in HBM (§Perf cell B).
    """
    info = SHAPES[shape_name]
    B, S = info["batch"], info["seq"]
    if cfg.family == "encdec":
        cache = jax.eval_shape(
            lambda: M.init_cache(cfg, B, S, jnp.bfloat16))
    else:
        cache_len = -(-(S + 1) // 512) * 512
        cache = jax.eval_shape(
            lambda: M.init_cache(cfg, B, cache_len, jnp.bfloat16))
    tokens = _sds((B, 1), jnp.int32)
    rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    return tokens, cache, rng


def params_specs_for(cfg: ModelConfig, dtype=jnp.bfloat16):
    return jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg, dtype))


def opt_state_specs_for(opt, params_sds):
    return jax.eval_shape(opt.init, params_sds)


def flops_estimate(cfg: ModelConfig, shape_name: str) -> float:
    """MODEL_FLOPS: 6·N·D (dense train), 6·N_active·D (MoE); 2·N·D forward
    for prefill; 2·N_active per token for decode."""
    info = SHAPES[shape_name]
    B, S = info["batch"], info["seq"]
    n = cfg.active_param_count() if cfg.moe else cfg.param_count()
    if cfg.family == "encdec" and info["kind"] != "decode":
        # encoder sees S frames, decoder decoder_max_len tokens
        tokens = B * (S + cfg.decoder_max_len) / 2  # rough split of params
    else:
        tokens = B * S
    if info["kind"] == "train":
        return 6.0 * n * tokens
    if info["kind"] == "prefill":
        return 2.0 * n * tokens
    return 2.0 * n * B  # decode: one token per sequence
