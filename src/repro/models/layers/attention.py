"""Multi-head attention: GQA/MQA, RoPE, causal/prefix/bidirectional/sliding
masks, cross-attention, and a ring-buffer KV cache for decode.

The full-sequence path is plain jnp einsum attention (XLA-fused); the Pallas
flash-attention kernel in ``repro.kernels`` is a drop-in replacement for the
inner softmax(QK^T)V on TPU (enabled via ``use_flash``), validated against
this code path in tests.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers.rotary import apply_rope

__all__ = [
    "AttnDims",
    "attn_init",
    "attention_full",
    "attention_decode",
    "init_kv_cache",
    "cross_attn_init",
    "cross_attention",
    "precompute_cross_kv",
]

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnDims:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    use_rope: bool = True
    # §Perf: repeat kv heads to num_heads before the score einsum so BOTH
    # operands shard heads over 'model' (Megatron-style GQA).  Avoids XLA's
    # involuntary batch replication when num_kv_heads doesn't divide the
    # model axis; costs g x kv HBM traffic (small vs the S^2 tensors).
    repeat_kv: bool = False


def _maybe_constrain(x: jnp.ndarray, spec: tuple) -> jnp.ndarray:
    """with_sharding_constraint when a mesh with these axes is set
    (``jax.set_mesh``, the production lowering path); a no-op without
    one.  Axes are kept when the GSPMD padding waste
    ceil(dim/axis)*axis/dim is <= 2x —
    so 8 heads still shard over 16 devices (2x padding beats full batch
    replication, measured on paligemma prefill), but a batch-1 decode
    tensor is never forced onto a 16-way axis (measured regression)."""
    axis_sizes = dict(jax.sharding.get_abstract_mesh().shape_tuple)
    if not axis_sizes:
        return x
    def keep(i, s):
        if s is None or s not in axis_sizes or i >= x.ndim:
            return False
        dim, ax = x.shape[i], axis_sizes[s]
        padded = -(-dim // ax) * ax
        return padded <= 2 * dim

    used = tuple(s if keep(i, s) else None for i, s in enumerate(spec))
    return jax.lax.with_sharding_constraint(x, jax.sharding.PartitionSpec(*used))


def attn_init(key, dims: AttnDims, dtype=jnp.bfloat16) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, n, k, h = dims.d_model, dims.num_heads, dims.num_kv_heads, dims.head_dim
    scale = d**-0.5
    params = {
        "wq": (jax.random.normal(kq, (d, n, h), jnp.float32) * scale).astype(dtype),
        "wk": (jax.random.normal(kk, (d, k, h), jnp.float32) * scale).astype(dtype),
        "wv": (jax.random.normal(kv, (d, k, h), jnp.float32) * scale).astype(dtype),
        "wo": (jax.random.normal(ko, (n, h, d), jnp.float32) * (n * h) ** -0.5).astype(dtype),
    }
    if dims.qkv_bias:
        params["bq"] = jnp.zeros((n, h), dtype)
        params["bk"] = jnp.zeros((k, h), dtype)
        params["bv"] = jnp.zeros((k, h), dtype)
    return params


def _project_qkv(params, x, dims: AttnDims, positions):
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"])
    k = jnp.einsum("bsd,dkh->bskh", x, params["wk"])
    v = jnp.einsum("bsd,dkh->bskh", x, params["wv"])
    if dims.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    if dims.use_rope:
        q = apply_rope(q, positions, dims.rope_theta)
        k = apply_rope(k, positions, dims.rope_theta)
    return q, k, v


def _grouped_scores(q, k, dims: AttnDims):
    """(B,S,N,h) x (B,T,K,h) -> (B,K,G,S,T) with G = N/K query groups."""
    b, s, n, h = q.shape
    kk = dims.num_kv_heads
    g = n // kk
    qg = q.reshape(b, s, kk, g, h)
    return jnp.einsum("bskgh,btkh->bkgst", qg, k, preferred_element_type=jnp.float32)


def _grouped_out(probs, v, dims: AttnDims):
    b, kk, g, s, t = probs.shape
    out = jnp.einsum("bkgst,btkh->bskgh", probs.astype(v.dtype), v)
    return out.reshape(b, s, kk * g, -1)


def _repeated_scores(q, k, dims: AttnDims):
    """repeat_kv path: kv repeated to N heads; heads shard over 'model'."""
    g = dims.num_heads // dims.num_kv_heads
    k = jnp.repeat(k, g, axis=2)  # (B,T,N,h)
    q = _maybe_constrain(q, ("data", None, "model", None))
    k = _maybe_constrain(k, ("data", None, "model", None))
    return jnp.einsum("bsnh,btnh->bnst", q, k, preferred_element_type=jnp.float32)


def _repeated_out(probs, v, dims: AttnDims):
    g = dims.num_heads // dims.num_kv_heads
    v = jnp.repeat(v, g, axis=2)
    v = _maybe_constrain(v, ("data", None, "model", None))
    out = jnp.einsum("bnst,btnh->bsnh", probs.astype(v.dtype), v)
    return _maybe_constrain(out, ("data", None, "model", None))


def make_mask(
    seq_len: int,
    mode: str,
    *,
    window: int = 0,
    prefix_len: int = 0,
    dtype=jnp.float32,
) -> jnp.ndarray:
    """Additive (S, S) mask.  mode: 'causal' | 'prefix' | 'bidir'.

    ``window > 0`` restricts causal attention to the last ``window`` keys
    (sliding window).  'prefix' is the PaliGemma prefix-LM mask: full
    attention within the first ``prefix_len`` positions, causal after.
    """
    i = jnp.arange(seq_len)[:, None]
    j = jnp.arange(seq_len)[None, :]
    if mode == "bidir":
        allowed = jnp.ones((seq_len, seq_len), bool)
    elif mode == "causal":
        allowed = j <= i
    elif mode == "prefix":
        allowed = (j <= i) | ((i < prefix_len) & (j < prefix_len))
    else:
        raise ValueError(f"unknown mask mode {mode!r}")
    if window > 0 and mode != "bidir":
        allowed = allowed & (j > i - window)
    return jnp.where(allowed, 0.0, NEG_INF).astype(dtype)


def attention_full(
    params: dict,
    x: jnp.ndarray,  # (B, S, D)
    dims: AttnDims,
    *,
    mode: str = "causal",
    window: int = 0,
    prefix_len: int = 0,
    positions: Optional[jnp.ndarray] = None,
    use_flash: bool = False,
) -> jnp.ndarray:
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _project_qkv(params, x, dims, positions)
    if use_flash and mode in ("causal", "bidir"):
        # Pallas flash-attention kernel (TPU; interpret mode on CPU) —
        # (B,S,N,h) layout, GQA folded in the kernel's kv index_map.
        # 'prefix' masks fall through to the einsum path below.
        from repro.kernels.flash_attention.ops import mha

        out = mha(
            q, k.astype(q.dtype), v.astype(q.dtype),
            causal=mode == "causal", window=window,
        ).astype(x.dtype)
        return jnp.einsum("bsnh,nhd->bsd", out, params["wo"])
    mask = make_mask(s, mode, window=window, prefix_len=prefix_len)
    if dims.repeat_kv:
        scores = _repeated_scores(q, k, dims) * (dims.head_dim**-0.5)
        scores = scores + mask[None, None]
        probs = jax.nn.softmax(scores, axis=-1)
        out = _repeated_out(probs, v, dims)
    else:
        scores = _grouped_scores(q, k, dims) * (dims.head_dim**-0.5)
        scores = scores + mask[None, None, None]
        probs = jax.nn.softmax(scores, axis=-1)
        out = _grouped_out(probs, v, dims)
    return jnp.einsum("bsnh,nhd->bsd", out, params["wo"])


# ---------------------------------------------------------------------------
# Decode path: ring-buffer KV cache (window = full seq_len or sliding window)
# ---------------------------------------------------------------------------


def init_kv_cache(
    batch: int, window: int, num_kv_heads: int, head_dim: int, dtype=jnp.bfloat16
) -> dict:
    return {
        "k": jnp.zeros((batch, window, num_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, window, num_kv_heads, head_dim), dtype),
        "slot_pos": jnp.full((window,), -1, jnp.int32),  # absolute pos per slot
    }


def attention_decode(
    params: dict,
    x: jnp.ndarray,  # (B, 1, D) current token hidden
    cache: dict,
    pos: jnp.ndarray,  # scalar int32 absolute position of this token
    dims: AttnDims,
) -> tuple[jnp.ndarray, dict]:
    b = x.shape[0]
    positions = jnp.broadcast_to(pos[None], (b, 1))
    q, k_new, v_new = _project_qkv(params, x, dims, positions)

    window = cache["k"].shape[1]
    slot = pos % window
    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype), (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype), (0, slot, 0, 0))
    slot_pos = jax.lax.dynamic_update_slice(cache["slot_pos"], pos[None], (slot,))

    scores = _grouped_scores(q, k, dims) * (dims.head_dim**-0.5)  # (B,K,G,1,W)
    valid = (slot_pos >= 0) & (slot_pos <= pos)
    scores = jnp.where(valid[None, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _grouped_out(probs, v, dims)  # (B,1,N,h)
    y = jnp.einsum("bsnh,nhd->bsd", out, params["wo"])
    return y, {"k": k, "v": v, "slot_pos": slot_pos}


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder -> encoder memory)
# ---------------------------------------------------------------------------


def cross_attn_init(key, dims: AttnDims, dtype=jnp.bfloat16) -> dict:
    return attn_init(key, dims, dtype)


def precompute_cross_kv(params: dict, memory: jnp.ndarray, dims: AttnDims) -> dict:
    """Encoder memory -> (k, v) once per request (no RoPE on cross path)."""
    k = jnp.einsum("btd,dkh->btkh", memory, params["wk"])
    v = jnp.einsum("btd,dkh->btkh", memory, params["wv"])
    if dims.qkv_bias:
        k = k + params["bk"]
        v = v + params["bv"]
    return {"k": k, "v": v}


def cross_attention(
    params: dict,
    x: jnp.ndarray,  # (B, S, D) decoder states
    memory_kv: dict,  # precomputed {k, v}: (B, T, K, h)
    dims: AttnDims,
) -> jnp.ndarray:
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"])
    if dims.qkv_bias:
        q = q + params["bq"]
    scores = _grouped_scores(q, memory_kv["k"], dims) * (dims.head_dim**-0.5)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _grouped_out(probs, memory_kv["v"], dims)
    return jnp.einsum("bsnh,nhd->bsd", out, params["wo"])
