"""Flash-attention Pallas kernels (forward): online softmax, causal, GQA.

Grid: (batch, q_heads, q_blocks, kv_blocks), kv innermost.  The query block
and the fp32 (m, l, acc) statistics stay VMEM-resident across the kv sweep;
K/V blocks stream.  GQA needs no materialized head repeat: the K/V BlockSpec
index map folds ``q_head // rep`` so each query head reads its group's KV.

``int8_flash_attention_fwd`` is the operand-width variant: Q/K/V stream as
per-head symmetric int8, both GeMMs (QK^T and PV) run on int8 operands with
int32 accumulation, and only the softmax statistics stay float -- the
probability block requantizes to int8 (scale 1/127, exact for p in [0, 1])
before the PV product.  An explicit boolean mask input replaces the
index-derived causal mask so the serve engine's per-slot cached-decode
masks (rolling SWA windows, per-slot lengths) drop in unchanged.

These are the MXU counterparts of the model-level ``layers.flash_attention``
/ ``layers.cached_attention`` (pure-jnp), which serve as their oracles in
the tests.  Causal masking skips nothing structurally (masked blocks are
computed) -- the exact-causal grid shaving is a documented follow-up; the
model-level path already supports it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_EPS = 1e-9


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               nk: int, bq: int, bkv: int, scale: float, causal: bool,
               seq_len: int):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                                 # (bq, dh)
    k = k_ref[0, 0]                                 # (bkv, dh)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (bq, bkv)

    q_idx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    k_idx = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = k_idx < seq_len
    if causal:
        mask = mask & (k_idx <= q_idx)
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _store():
        o_ref[0, 0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,          # (B, H, Sq, dh)
    k: jax.Array,          # (B, KvH, Skv, dh)
    v: jax.Array,          # (B, KvH, Skv, dh)
    *,
    causal: bool = True,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, H, Sq, dh = q.shape
    KvH, Skv = k.shape[1], k.shape[2]
    rep = H // KvH
    bq = min(block_q, Sq)
    bkv = min(block_kv, Skv)
    scale = dh ** -0.5

    q_p = jnp.pad(q, ((0, 0), (0, 0), (0, (-Sq) % bq), (0, 0)))
    k_p = jnp.pad(k, ((0, 0), (0, 0), (0, (-Skv) % bkv), (0, 0)))
    v_p = jnp.pad(v, ((0, 0), (0, 0), (0, (-Skv) % bkv), (0, 0)))
    nq = q_p.shape[2] // bq
    nk = k_p.shape[2] // bkv

    out = pl.pallas_call(
        functools.partial(_fa_kernel, nk=nk, bq=bq, bkv=bkv, scale=scale,
                          causal=causal, seq_len=Skv),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            # GQA: query head h reads KV group h // rep -- no repeat
            pl.BlockSpec((1, 1, bkv, dh),
                         lambda b, h, qi, ki, rep=rep: (b, h // rep, ki, 0)),
            pl.BlockSpec((1, 1, bkv, dh),
                         lambda b, h, qi, ki, rep=rep: (b, h // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * bq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max
            pltpu.VMEM((bq, 1), jnp.float32),     # running denominator
            pltpu.VMEM((bq, dh), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(q_p, k_p, v_p)
    return out[:, :, :Sq]


# ---------------------------------------------------------------------------
# int8 attention: quantized QK^T and PV, float softmax, per-head scales
# ---------------------------------------------------------------------------


def _fa_int8_kernel(q_ref, k_ref, v_ref, mask_ref, sqk_ref, sv_ref, o_ref,
                    m_ref, l_ref, acc_ref, *, nk: int, rep: int):
    h = pl.program_id(1)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                                 # (bq, dh) int8
    k = k_ref[0, 0]                                 # (bkv, dh) int8
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32
    ).astype(jnp.float32) * sqk_ref[h]              # dequant + softmax scale
    s = jnp.where(mask_ref[0], s, _NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    # requantize the probability block: p in [0, 1] -> round(p * 127).  The
    # denominator uses the SAME quantized p so numerator and normalization
    # stay consistent.
    pq = jnp.round(p * 127.0).astype(jnp.int8)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = (l_ref[...] * corr
                  + pq.astype(jnp.float32).sum(axis=1, keepdims=True) / 127.0)
    m_ref[...] = m_new
    pv = jax.lax.dot_general(
        pq, v_ref[0, 0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    acc_ref[...] = (acc_ref[...] * corr
                    + pv.astype(jnp.float32) * (sv_ref[h // rep] / 127.0))

    @pl.when(ki == nk - 1)
    def _store():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _per_head_quantize(x: jax.Array, qmax: float = 127.0):
    """(B, H, S, dh) float -> (int8 payload, (H,) f32 per-head scale)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(0, 2, 3))
    scale = jnp.maximum(amax, _EPS) / qmax                     # (H,)
    xq = jnp.clip(jnp.round(x.astype(jnp.float32)
                            / scale[None, :, None, None]), -qmax, qmax
                  ).astype(jnp.int8)
    return xq, scale.astype(jnp.float32)


def int8_flash_attention_fwd(
    q: jax.Array,          # (B, H, Sq, dh) float
    k: jax.Array,          # (B, KvH, Skv, dh) float
    v: jax.Array,          # (B, KvH, Skv, dh) float
    *,
    mask: jax.Array | None = None,   # (B, Sq, Skv) bool; None = causal
    causal: bool = True,
    scale: float | None = None,      # None = dh ** -0.5
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Int8-operand flash attention with float softmax statistics.

    Q/K/V are symmetrically quantized per head on the way in (the KV-cache
    byte stream the decode step is bound by shrinks 2-4x vs bf16/f32);
    scores dequantize via the per-head scale product before the online
    softmax, and the probability block requantizes for the int8 PV product.
    ``mask`` replaces the built-in causal mask when given -- the cached
    decode path passes its per-slot position masks straight through.
    """
    B, H, Sq, dh = q.shape
    KvH, Skv = k.shape[1], k.shape[2]
    rep = H // KvH
    sm_scale = dh ** -0.5 if scale is None else scale
    bq = min(block_q, Sq)
    bkv = min(block_kv, Skv)

    qq, sq = _per_head_quantize(q)                 # (H,)
    kq, sk = _per_head_quantize(k)                 # (KvH,)
    vq, sv = _per_head_quantize(v)                 # (KvH,)
    # combined per-q-head dequant scale for the score block
    sqk = sq * sk[jnp.arange(H) // rep] * sm_scale  # (H,)

    if mask is None:
        q_idx = jnp.arange(Sq)
        kv_idx = jnp.arange(Skv)
        m2 = kv_idx[None, :] <= q_idx[:, None] if causal \
            else jnp.ones((Sq, Skv), bool)
        mask = jnp.broadcast_to(m2[None], (B, Sq, Skv))
    mask = jnp.pad(mask, ((0, 0), (0, (-Sq) % bq), (0, (-Skv) % bkv)))

    q_p = jnp.pad(qq, ((0, 0), (0, 0), (0, (-Sq) % bq), (0, 0)))
    k_p = jnp.pad(kq, ((0, 0), (0, 0), (0, (-Skv) % bkv), (0, 0)))
    v_p = jnp.pad(vq, ((0, 0), (0, 0), (0, (-Skv) % bkv), (0, 0)))
    nq = q_p.shape[2] // bq
    nk = k_p.shape[2] // bkv

    out = pl.pallas_call(
        functools.partial(_fa_int8_kernel, nk=nk, rep=rep),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bkv, dh),
                         lambda b, h, qi, ki, rep=rep: (b, h // rep, ki, 0)),
            pl.BlockSpec((1, 1, bkv, dh),
                         lambda b, h, qi, ki, rep=rep: (b, h // rep, ki, 0)),
            pl.BlockSpec((1, bq, bkv), lambda b, h, qi, ki: (b, qi, ki)),
            # per-head scalars, whole in SMEM (a (1, 1) VMEM block of an
            # (H, 1) array is not a tile the chip's compiler accepts)
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * bq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),     # running max
            pltpu.VMEM((bq, 1), jnp.float32),     # running denominator
            pltpu.VMEM((bq, dh), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(q_p, k_p, v_p, mask, sqk, sv)
    return out[:, :, :Sq]
