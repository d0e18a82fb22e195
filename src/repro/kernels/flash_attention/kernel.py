"""Pallas TPU kernel: causal GQA flash attention (fwd).

The §Perf "flash-fuse" iteration: the jnp flash path materializes the
(Sq, Sk-chunk) probability tensors and running (m, l, acc) statistics to HBM
every chunk — measured at 8-25% of the memory term on the train/prefill
cells (flash_attn_interior rows of the dry-run profile).  This kernel keeps
the entire online-softmax interior in VMEM:

  grid = (B * Hq, Sq / BLOCK_Q)    one program per query block per head
  for each k block (BLOCK_K wide, ascending):
      s   = q_blk @ k_blk^T        (MXU, f32 accum)
      causal masking via iota comparison (no materialized mask)
      online-softmax update of (m, l, acc) — all VMEM residents
  out = acc / l

VMEM budget per program (defaults BLOCK_Q=512, BLOCK_K=512, D=128, f32):
  q 256KB + k/v 2x256KB + s 1MB + acc 256KB  ≈ 2MB  « 16MB/core.
Block shapes are (multiple-of-8, 128)-aligned for the MXU/VPU.

GQA: the kernel receives k/v already grouped per q-head (index_map selects
the kv head h // group); no repeat materialization in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, sk: int,
                      causal: bool, q_offset_blocks: int):
    """q_ref: (1, BLOCK_Q, D); k_ref/v_ref: (1, Sk, D); o_ref: (1, BLOCK_Q, D)."""
    _, block_q, d = q_ref.shape
    qi = pl.program_id(1)                       # query block index
    q = q_ref[0].astype(jnp.float32) / jnp.sqrt(jnp.float32(d))

    q_start = qi * block_q
    n_kblocks = sk // block_k

    def body(ki, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(ki * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(ki * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (BQ, BK)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v_blk.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    # causal: skip k blocks entirely above the diagonal
    if causal:
        last = (q_start + block_q + block_k - 1) // block_k
        n_iter = jnp.minimum(n_kblocks, last)
    else:
        n_iter = n_kblocks

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_iter, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, block_q: int = 512,
                        block_k: int = 512, interpret: bool = True) -> jax.Array:
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Sk, D). Returns (B, Hq, Sq, D)."""
    b, hq, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    assert hq % hk == 0 and sq % block_q == 0 and sk % block_k == 0
    group = hq // hk

    grid = (b * hq, sq // block_q)

    def q_map(bh, qi):
        return (bh, qi, 0)

    def kv_map(bh, qi):
        return (bh // group if group > 1 else bh, 0, 0)

    qr = q.reshape(b * hq, sq, d)
    kr = k.reshape(b * hk, sk, d)
    vr = v.reshape(b * hk, sk, d)
    # kv index_map works on the flattened (B*Hkv) axis: program bh maps to
    # (bh // hq) * hk + (bh % hq) // group
    def kv_map2(bh, qi):
        bidx = bh // hq
        hidx = (bh % hq) // group
        return (bidx * hk + hidx, 0, 0)

    kernel = functools.partial(
        _flash_fwd_kernel, block_k=block_k, sk=sk, causal=causal,
        q_offset_blocks=0)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, sk, d), kv_map2),
            pl.BlockSpec((1, sk, d), kv_map2),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, hq, sq, d)
