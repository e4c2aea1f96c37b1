"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mamba2_scan import mamba2_scan
from repro.kernels.paged_attention import merge_partials, paged_attention
from repro.kernels.rwkv6_scan import rwkv6_scan
from repro.kernels.stackdist import stack_scan
from repro.kernels.tlb_sim import tlb_sim, tlb_sim_batched, tlb_sim_batched_carry
from repro.models.flash_ref import flash_attention_jnp


@pytest.mark.parametrize("B,Hq,Hkv,Tq,Tk,D,causal,dtype", [
    (1, 4, 2, 64, 64, 32, True, jnp.float32),
    (2, 8, 8, 96, 96, 64, True, jnp.float32),
    (1, 4, 1, 33, 80, 64, False, jnp.float32),
    (2, 2, 2, 128, 128, 128, True, jnp.bfloat16),
    (1, 4, 2, 1, 96, 32, True, jnp.float32),  # decode: single query
])
def test_flash_attention_vs_oracle(rng, B, Hq, Hkv, Tq, Tk, D, causal, dtype):
    q = jnp.asarray(rng.standard_normal((B, Hq, Tq, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, Hkv, Tk, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, Hkv, Tk, D)), dtype)
    ref = attention_ref(q, k, v, causal=causal)
    pal = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          kernel_mode="pallas_interpret")
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_flash_ref_chunked_equals_naive(rng):
    q = jnp.asarray(rng.standard_normal((2, 4, 50, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 2, 70, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 2, 70, 32)), jnp.float32)
    for causal in (True, False):
        a = flash_attention_jnp(q, k, v, causal=causal, block_k=16)
        b = attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.parametrize("B,Hq,Hkv,D,page,pages,slots", [
    (2, 8, 2, 64, 16, 4, 32),
    (3, 4, 4, 32, 8, 6, 64),
    (1, 16, 8, 128, 32, 3, 16),
])
def test_paged_attention_vs_oracle(rng, B, Hq, Hkv, D, page, pages, slots):
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((slots, page, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((slots, page, Hkv, D)), jnp.float32)
    tbl = np.full((B, pages), -1, np.int32)
    ctx = np.zeros(B, np.int32)
    for b in range(B):
        n = int(rng.integers(1, pages + 1))
        tbl[b, :n] = rng.choice(slots, n, replace=False)
        ctx[b] = (n - 1) * page + int(rng.integers(1, page + 1))
    tbl, ctx = jnp.asarray(tbl), jnp.asarray(ctx)
    ref = paged_attention(q, kp, vp, tbl, ctx, kernel_mode="reference")
    pal = paged_attention(q, kp, vp, tbl, ctx, kernel_mode="pallas_interpret")
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref), atol=2e-5)


def test_merge_partials_is_exact_partition_of_softmax(rng):
    """Splitting the KV across partitions then merging == one-shot attention."""
    from repro.kernels.paged_attention import paged_attention_partial
    B, Hq, Hkv, D, page = 2, 4, 2, 32, 8
    slots, pages = 16, 4
    q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((slots, page, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((slots, page, Hkv, D)), jnp.float32)
    tbl = jnp.asarray(rng.choice(slots, (B, pages), replace=False).astype(np.int32))
    ctx = jnp.asarray(np.full(B, pages * page, np.int32))
    full = paged_attention(q, kp, vp, tbl, ctx, kernel_mode="reference")
    # Partition pages across 2 "devices": mask halves of the table.
    parts = []
    for half in range(2):
        t = np.asarray(tbl).copy()
        t[:, half::2] = -1  # this partition owns the other pages... keep ctx
        acc, m, l = paged_attention_partial(q, kp, vp, jnp.asarray(t), ctx,
                                            kernel_mode="reference")
        parts.append((acc, m, l))
    merged = merge_partials(
        jnp.stack([p[0] for p in parts]),
        jnp.stack([p[1] for p in parts]),
        jnp.stack([p[2] for p in parts]),
    )
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full), atol=2e-5)


@pytest.mark.parametrize("B,H,T,N,chunk", [(2, 2, 64, 32, 32), (1, 4, 96, 16, 16)])
def test_rwkv6_chunked_vs_exact(rng, B, H, T, N, chunk):
    r = jnp.asarray(rng.standard_normal((B, H, T, N)) * 0.5, jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, T, N)) * 0.5, jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, T, N)) * 0.5, jnp.float32)
    w = jnp.asarray(rng.uniform(0.75, 0.999, (B, H, T, N)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((H, N)) * 0.5, jnp.float32)
    o_ref, s_ref = rwkv6_scan(r, k, v, w, u, kernel_mode="reference")
    o_pal, s_pal = rwkv6_scan(r, k, v, w, u, chunk=chunk, kernel_mode="pallas_interpret")
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref), atol=5e-4)
    np.testing.assert_allclose(np.asarray(s_pal), np.asarray(s_ref), atol=5e-4)


@pytest.mark.parametrize("B,H,T,P,N,chunk", [(2, 2, 64, 32, 16, 32), (1, 4, 96, 16, 32, 16)])
def test_mamba2_chunked_vs_exact(rng, B, H, T, P, N, chunk):
    x = jnp.asarray(rng.standard_normal((B, H, T, P)) * 0.5, jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (B, H, T)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 4.0, (H,)), jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((B, T, N)) * 0.5, jnp.float32)
    C = jnp.asarray(rng.standard_normal((B, T, N)) * 0.5, jnp.float32)
    D = jnp.asarray(rng.standard_normal((H,)), jnp.float32)
    y_ref, s_ref = mamba2_scan(x, dt, A, Bm, C, D, kernel_mode="reference")
    y_pal, s_pal = mamba2_scan(x, dt, A, Bm, C, D, chunk=chunk, kernel_mode="pallas_interpret")
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref), atol=5e-4)
    np.testing.assert_allclose(np.asarray(s_pal), np.asarray(s_ref), atol=5e-4)


@pytest.mark.parametrize("TS,W,N,blk", [
    (16, 4, 1024, 256), (64, 4, 2048, 512), (8, 2, 512, 128),
    (5, 3, 512, 128),      # ways padded to a power of two in the lane layout
    (3, 256, 1024, 256),   # a set wider than the 128 lanes
])
def test_tlb_sim_kernel_bit_exact(rng, TS, W, N, blk):
    s = jnp.asarray(rng.integers(0, TS, N), jnp.int32)
    t = jnp.asarray(rng.integers(0, 50, N), jnp.int32)
    ref = tlb_sim(s, t, TS, W, kernel_mode="reference")
    pal = tlb_sim(s, t, TS, W, block=blk, kernel_mode="pallas_interpret")
    assert (np.asarray(ref) == np.asarray(pal)).all()


@pytest.mark.parametrize("TS,W,N,blk,valid", [
    (16, 4, 1024, 256, (4, 2, 1)),    # heterogeneous associativity
    (32, 4, 512, 128, (4, 4, 4, 3)),
    (9, 6, 512, 128, (6, 5)),         # envelope ways no power of two
])
def test_tlb_sim_batched_kernel_bit_exact(rng, TS, W, N, blk, valid):
    B = len(valid)
    s = jnp.asarray(rng.integers(0, TS, (B, N)), jnp.int32)
    t = jnp.asarray(rng.integers(0, 50, (B, N)), jnp.int32)
    ref = tlb_sim_batched(s, t, TS, W, valid, kernel_mode="reference")
    pal = tlb_sim_batched(s, t, TS, W, valid, block=blk, kernel_mode="pallas_interpret")
    assert (np.asarray(ref) == np.asarray(pal)).all()
    # Each batched row == the single-config kernel on that config's geometry.
    for b in range(B):
        one = tlb_sim(s[b], t[b], TS, valid[b], kernel_mode="reference")
        assert (np.asarray(ref[b]) == np.asarray(one)).all()


@pytest.mark.parametrize("TS,W,valid", [(17, 4, (4, 2)), (4, 256, (256, 200))])
def test_tlb_sim_carry_kernel_bit_exact_across_chunks(rng, TS, W, valid):
    """The carried state round-trips through the kernel's lane-dense layout
    between chunks, including a ragged (padded) final chunk."""
    from repro.core.tlbsim import padded_tlb_state

    B, N = len(valid), 700
    s = jnp.asarray(rng.integers(0, TS - 1, (B, N)), jnp.int32)
    t = jnp.asarray(rng.integers(0, 300, (B, N)), jnp.int32)
    outs = {}
    for mode in ("reference", "pallas_interpret"):
        tags, last = padded_tlb_state(B, TS, W, valid)
        hits = []
        for lo, hi in ((0, 256), (256, 512), (512, N)):
            h, tags, last = tlb_sim_batched_carry(
                s[:, lo:hi], t[:, lo:hi], tags, last, lo, block=128,
                kernel_mode=mode)
            hits.append(np.asarray(h))
        # The last set row is the parked row that block padding writes to.
        outs[mode] = (np.concatenate(hits, 1), np.asarray(tags)[:, :-1],
                      np.asarray(last)[:, :-1])
    for a, b in zip(outs["reference"], outs["pallas_interpret"]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("L,C,W", [(37, 100, 4), (130, 64, 12)])
def test_stack_scan_kernel_bit_exact_ragged(rng, L, C, W):
    """Lane counts off the 128-lane tile and access counts off the 8-row
    block: the kernel pads both and the padding never shows."""
    tags = jnp.asarray(rng.integers(0, 30, (L, C)), jnp.int32)
    flags = rng.random((L, C)) < 0.05
    flags[:, 0] = True
    flags = jnp.asarray(flags)
    init = jnp.asarray(rng.integers(-1, 30, (L, W)), jnp.int32)
    d_ref, f_ref = stack_scan(tags, flags, init, kernel_mode="reference")
    d_pal, f_pal = stack_scan(tags, flags, init, kernel_mode="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(d_ref), np.asarray(d_pal))
    np.testing.assert_array_equal(np.asarray(f_ref), np.asarray(f_pal))


def test_lane_layout_round_trips():
    """The lane-dense state layout of the LRU kernels is a lossless view of
    the [sets, ways] state the scans carry."""
    from repro.kernels.tlb_sim.kernel import from_lanes, lane_layout, to_lanes

    rng = np.random.default_rng(0)
    for sets, ways in [(17, 4), (5, 3), (3, 256), (2049, 8), (1, 1)]:
        lay = lane_layout(sets, ways)
        assert lay.rows % 8 == 0 and lay.ways >= ways
        x = jnp.asarray(rng.integers(0, 1 << 20, (2, sets, ways)), jnp.int32)
        packed = to_lanes(x, lay, -2)
        assert packed.shape == (2, lay.rows, 128)
        np.testing.assert_array_equal(from_lanes(packed, lay, ways), x)
