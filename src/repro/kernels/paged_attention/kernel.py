"""SPARTA paged decode attention as a Pallas TPU kernel.

This is the kernel-level embodiment of the paper's translate-while-fetching:
the block table (the per-partition page table, logical KV page -> physical
pool slot) is a **scalar-prefetch operand** whose values drive the KV
BlockSpec ``index_map``.  On TPU the scalar prefetch happens ahead of the
grid step, so the *translation* (table lookup) programs the DMA that fetches
the KV page — translation and data fetch literally overlap, and while page
``p`` is being processed the DMA for page ``p+1`` (already translated) is in
flight.  The centralised-IOMMU analogue (gather through a *global* table on
another device) would serialise those steps.

Grid: (batch, pages).  Page blocks walk sequentially per sequence with the
f32 flash statistics (m, l, acc) in VMEM scratch.  Invalid pages (past the
context length, or unmapped table entries) are skipped with ``pl.when`` —
no DMA descriptors are wasted on them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(
    table_ref, ctx_ref,             # scalar-prefetch (SMEM)
    q_ref, k_ref, v_ref,            # VMEM blocks; q [1, Hkv, G, D]
    o_acc_ref, o_m_ref, o_l_ref,    # residuals [1, Hkv, G, D], [1, Hkv, G] x2
    m_scr, l_scr, acc_scr,          # [Hkv, G], [Hkv, G], [Hkv, G, D]
    *,
    sm_scale: float,
    page: int,
    pages: int,
):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ctx = ctx_ref[b]
    page_start = p * page
    valid_page = (page_start < ctx) & (table_ref[b, p] >= 0)

    @pl.when(valid_page)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                 # [Hkv, G, D]
        k = k_ref[0].astype(jnp.float32)                 # [page, Hkv, D]
        v = v_ref[0].astype(jnp.float32)
        kt = jnp.transpose(k, (1, 0, 2))                 # [Hkv, page, D]
        # s[h, g, t] over the page
        s = jax.lax.dot_general(
            q, kt, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        ) * sm_scale                                     # [Hkv, G, page]

        t_ids = page_start + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
        mask = t_ids < ctx
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        pr = jnp.exp(s - m_new[..., None])
        pr = jnp.where(mask, pr, 0.0)
        v_ids = page_start + jax.lax.broadcasted_iota(jnp.int32, (1, page, 1), 1)
        vt = jnp.where(v_ids < ctx, jnp.transpose(v, (1, 0, 2)), 0.0)
        l_scr[...] = l_scr[...] * alpha + pr.sum(axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[..., None] + jax.lax.dot_general(
            pr, vt, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(p == pages - 1)
    def _finish():
        o_acc_ref[0] = acc_scr[...].astype(o_acc_ref.dtype)
        o_m_ref[0] = m_scr[...].astype(o_m_ref.dtype)
        o_l_ref[0] = l_scr[...].astype(o_l_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "interpret"),
)
def paged_attention_pallas(
    q: jnp.ndarray,            # [B, Hq, D]
    k_pool: jnp.ndarray,       # [slots, page, Hkv, D]
    v_pool: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, pages] int32
    ctx_len: jnp.ndarray,      # [B] int32
    *,
    sm_scale: float | None = None,
    interpret: bool = False,
):
    """Returns residuals (acc [B, Hq, D], m [B, Hq], l [B, Hq]); normalise
    with ``ref.merge_partials`` (single-partition callers divide locally in
    ops.py).  Inside the kernel the query heads are grouped by KV head
    ([Hkv, G] blocks), so no block or scratch needs an in-kernel reshape."""
    B, Hq, D = q.shape
    slots, page, Hkv, _ = k_pool.shape
    G = Hq // Hkv
    pages = block_table.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / (D ** 0.5)

    grid = (B, pages)
    kernel = functools.partial(
        _paged_kernel,
        sm_scale=scale, page=page, pages=pages,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Hkv, G, D), lambda b, p, tbl, ctx: (b, 0, 0, 0)),
            # THE SPARTA LOOKUP: the table value selects the pool slot the
            # DMA reads — translation programs the fetch.
            pl.BlockSpec(
                (1, page, Hkv, D),
                lambda b, p, tbl, ctx: (jnp.maximum(tbl[b, p], 0), 0, 0, 0),
            ),
            pl.BlockSpec(
                (1, page, Hkv, D),
                lambda b, p, tbl, ctx: (jnp.maximum(tbl[b, p], 0), 0, 0, 0),
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, Hkv, G, D), lambda b, p, tbl, ctx: (b, 0, 0, 0)),
            pl.BlockSpec((1, Hkv, G), lambda b, p, tbl, ctx: (b, 0, 0)),
            pl.BlockSpec((1, Hkv, G), lambda b, p, tbl, ctx: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((Hkv, G), jnp.float32),
            pltpu.VMEM((Hkv, G), jnp.float32),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, G, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, G), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, G), jnp.float32),
        ],
        interpret=interpret,
    )(block_table, ctx_len, q.reshape(B, Hkv, G, D), k_pool, v_pool)
    return acc.reshape(B, Hq, D), m.reshape(B, Hq), l.reshape(B, Hq)
