"""Blocked (flash) attention Pallas kernel for the TPU MXU.

Grid = (batch*q_heads, Sq/BLOCK_Q, Skv/BLOCK_K); the last axis is the
sequential ("arbitrary") dimension, so the (m, l, acc) online-softmax state
lives in VMEM scratch across kv steps of one (bh, iq) tile.  Supports GQA
(kv head = q head // group), causal masking, and sliding-window (local)
attention — the assigned architectures need all three.

Block shapes are (BLOCK_Q, HEAD_DIM) / (BLOCK_K, HEAD_DIM): HEAD_DIM of the
assigned archs is 64..256, a multiple of the 128-lane register width in all
but the 64-d case, which Pallas pads transparently.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256
NEG_INF = -1e30


def _attn_kernel(
    causal: bool,
    window: int | None,
    sm_scale: float,
    block_q: int,
    block_k: int,
    q_ref, k_ref, v_ref,          # inputs
    o_ref,                        # output
    m_scr, l_scr, acc_scr,        # scratch
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)              # (block_q, d)
    k = k_ref[0, 0].astype(jnp.float32)              # (block_k, d)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale   # (block_q, block_k)

    q_ids = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_ids = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask = mask & (q_ids >= k_ids)
    if window is not None:
        mask = mask & (k_ids > q_ids - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                            # (block_q, 1)
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                         # (block_q, block_k)
    # fully-masked rows: exp(NEG_INF - NEG_INF) = 1 would poison l; zero them
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / safe).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,                  # (B, Hq, Sq, D)
    k: jax.Array,                  # (B, Hkv, Sk, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    sm_scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
) -> jax.Array:
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    if sm_scale is None:
        sm_scale = D ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)

    grid = (B * Hq, Sq // block_q, Sk // block_k)

    def q_map(bh, iq, ik):
        return (bh // Hq, bh % Hq, iq, 0)

    def kv_map(bh, iq, ik):
        return (bh // Hq, (bh % Hq) // group, ik, 0)

    kernel = functools.partial(
        _attn_kernel, causal, window, sm_scale, block_q, block_k)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda bh, iq, ik: q_map(bh, iq, ik)),
            pl.BlockSpec((1, 1, block_k, D), lambda bh, iq, ik: kv_map(bh, iq, ik)),
            pl.BlockSpec((1, 1, block_k, D), lambda bh, iq, ik: kv_map(bh, iq, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda bh, iq, ik: q_map(bh, iq, ik)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
