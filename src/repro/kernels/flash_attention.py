"""Pallas TPU flash attention (forward), GQA-aware, causal + window.

The prefill/train compute hot-spot of every attention architecture in
the pool.  TPU-native design decisions (vs a CUDA port):

  * Tiling is (block_q x block_k) with both dims multiples of 128 so the
    q @ k^T and p @ v contractions land on the MXU at full occupancy.
  * Online softmax state (m, l, acc) lives in VMEM **scratch** that
    persists across the innermost ("arbitrary") grid dimension — the
    standard Pallas accumulation idiom, replacing the CUDA shared-memory
    staging loop.
  * GQA is expressed through the k/v BlockSpec ``index_map`` (query head
    h reads kv head h // group) — no materialized head broadcast.
  * Fully-masked (future) k-blocks are skipped with ``pl.when`` so the
    causal prefill does ~half the block work, like the CUDA kernel's
    early-exit but decided statically from grid indices.

Layouts: q (B, H, S, hd); k/v (B, Hkv, T, hd); out (B, H, S, hd).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0 ** 30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, block_q: int, block_k: int, causal: bool, window: int,
    scale: float, n_kblocks: int,
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = kj * block_k

    # Static-ish skip: a k-block strictly in the future contributes
    # nothing under the causal mask.
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1

    def body():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, hd)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                     # (bq, bk)

        if causal or window > 0:
            qpos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = jnp.ones((block_q, block_k), bool)
            if causal:
                mask = jnp.logical_and(mask, kpos <= qpos)
            if window > 0:
                mask = jnp.logical_and(mask, kpos > qpos - window)
            scores = jnp.where(mask, scores, NEG_INF)

        m_prev = m_scr[...]                           # (bq, 1)
        m_new = jnp.maximum(
            m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    if causal:
        pl.when(run)(body)
    else:
        body()

    @pl.when(kj == n_kblocks - 1)
    def _finish():
        o_ref[0, 0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    softmax_scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, H, S, hd); k/v: (B, Hkv, T, hd) -> (B, H, S, hd)."""
    b, h, s, hd = q.shape
    hkv, t = k.shape[1], k.shape[2]
    g = h // hkv
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    assert s % block_q == 0 and t % block_k == 0
    nq = s // block_q
    nk = t // block_k
    scale = (hd ** -0.5) if softmax_scale is None else softmax_scale

    kernel = functools.partial(
        _flash_kernel,
        block_q=block_q, block_k=block_k, causal=causal, window=window,
        scale=scale, n_kblocks=nk,
    )
    grid = (b, h, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda bi, hi, qi, kj: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, hi, qi, kj, g=g: (bi, hi // g, kj, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, hi, qi, kj, g=g: (bi, hi // g, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda bi, hi, qi, kj: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), q.dtype),
        scratch_shapes=[
            # Online-softmax state persists across the k grid dim: VMEM.
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum
            pltpu.VMEM((block_q, hd), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
