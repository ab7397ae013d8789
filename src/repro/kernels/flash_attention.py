"""Pallas kernel: fused blockwise (flash) attention with online softmax.

The perf-critical attention hot loop as an explicit TPU kernel: one grid
step computes one q block for one (batch·head); K/V rows stream through
VMEM; GQA is expressed in the K/V BlockSpec index maps (head h reads KV
head h // group — no materialized head expansion); causal blocks beyond
the q block are skipped via the fori upper bound, so compute is the
causal half, not the full S².

VMEM sizing: this variant holds one (S, dh) K/V row per grid step —
fine to ~16k×128 bf16.  Longer sequences would add a third grid dim with
revisited outputs; the jnp blockwise path in models/attention.py remains
the production fallback and the numerical oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import interpret_default

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, kv_block: int, scale: float,
            causal: bool, cap: float, seq_len: int):
    qb = q_ref.shape[1]
    dh = q_ref.shape[2]
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale               # (qb, dh)
    qpos = qi * qb + jax.lax.broadcasted_iota(jnp.int32, (qb, 1), 0)

    n_kv = seq_len // kv_block
    if causal:
        # only kv blocks that intersect the causal triangle
        n_kv_eff = jnp.minimum(((qi + 1) * qb + kv_block - 1) // kv_block,
                               n_kv)
    else:
        n_kv_eff = n_kv

    def body(ki, carry):
        m, l, acc = carry
        kv_rows = pl.ds(ki * kv_block, kv_block)
        kblk = k_ref[0, kv_rows, :].astype(jnp.float32)
        vblk = v_ref[0, kv_rows, :].astype(jnp.float32)
        s = q @ kblk.T                                     # (qb, kv_block)
        if cap:
            s = cap * jnp.tanh(s / cap)
        kpos = ki * kv_block + jax.lax.broadcasted_iota(
            jnp.int32, (1, kv_block), 1)
        mask = jnp.ones((qb, kv_block), jnp.bool_)
        if causal:
            mask = kpos <= qpos
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * corr + p @ vblk
        return m_new, l_new, acc_new

    m0 = jnp.full((qb, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((qb, 1), jnp.float32)
    a0 = jnp.zeros((qb, dh), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_kv_eff, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "cap", "q_block", "kv_block", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, cap: float = 0.0,
                    q_block: int = 128, kv_block: int = 128,
                    interpret: bool | None = None) -> jnp.ndarray:
    """q (B,S,H,dh), k/v (B,S,KV,dh) → (B,S,H,dh).  S % blocks == 0."""
    if interpret is None:
        interpret = interpret_default()
    b, s, h, dh = q.shape
    kvh = k.shape[2]
    g = h // kvh
    scale = dh ** -0.5
    assert s % q_block == 0 and s % kv_block == 0, (s, q_block, kv_block)
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    kr = k.transpose(0, 2, 1, 3).reshape(b * kvh, s, dh)
    vr = v.transpose(0, 2, 1, 3).reshape(b * kvh, s, dh)
    nq = s // q_block

    out = pl.pallas_call(
        functools.partial(_kernel, kv_block=kv_block, scale=scale,
                          causal=causal, cap=cap, seq_len=s),
        grid=(b * h, nq),
        in_specs=[
            pl.BlockSpec((1, q_block, dh), lambda bh, qi: (bh, qi, 0)),
            # GQA via index map: query head bh reads KV row bh // g
            pl.BlockSpec((1, s, dh), lambda bh, qi: (bh // g, 0, 0)),
            pl.BlockSpec((1, s, dh), lambda bh, qi: (bh // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, dh),
                               lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, dh), q.dtype),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, s, dh).transpose(0, 2, 1, 3)
