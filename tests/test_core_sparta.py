"""Core SPARTA invariants: partition hash, timelines, TLB simulator."""
import numpy as np
import pytest
from _propcheck import given, settings, st  # hypothesis, or deterministic fallback

from repro.core import tlbsim, traces
from repro.core.sparta import (
    SystemLatencies, TLBConfig, conventional_timelines,
    mem_partition_index_hash, partition_local_vpn, sparta_timelines,
)


@pytest.fixture(scope="module")
def jax_started():
    """The first call of each JAX operation in a process starts the backend
    and compiles the operation, which can take longer than an example's
    deadline: make those first calls before the first example."""
    import jax.numpy as jnp

    int(mem_partition_index_hash(jnp.int32(0), 1))
    int(partition_local_vpn(jnp.int32(0), 1))


@pytest.mark.usefixtures("jax_started")
@given(st.integers(0, 2**40), st.sampled_from([1, 2, 4, 8, 32, 128]))
def test_partition_hash_bijective(vpn, P):
    import jax.numpy as jnp
    p = int(mem_partition_index_hash(jnp.int32(vpn % 2**25), P))
    local = int(partition_local_vpn(jnp.int32(vpn % 2**25), P))
    assert 0 <= p < P
    assert local * P + p == vpn % 2**25  # (p, local) reconstructs the vpn


def test_sparta_miss_penalty_is_local_dram():
    lat = SystemLatencies()
    _, _, _, conv = conventional_timelines(lat)
    _, _, _, sp = sparta_timelines(lat)
    assert sp == lat.l_tlb + lat.l_dram   # no network in the SPARTA walk
    assert conv > sp                      # conventional pays round trips


def test_sparta_penalty_grows_slower_with_machine_size():
    red = {}
    for n in (2, 8):
        lat = SystemLatencies(n_sockets=n)
        _, _, _, conv = conventional_timelines(lat)
        _, _, _, sp = sparta_timelines(lat)
        red[n] = conv / sp
    assert red[8] > red[2]


def test_tlb_lru_exact_small_case():
    # 1-set, 2-way LRU: [1, 2, 1, 3, 2] -> hits [F, F, T, F, F]
    vpns = np.array([1, 2, 1, 3, 2])
    res = tlbsim.simulate_tlb(vpns, TLBConfig(entries=2, ways=2), warmup_frac=0.0)
    assert list(res.hits) == [False, False, True, False, False]


def test_partitioning_never_hurts_capacity():
    """P partitions x E entries >= 1 partition x E entries (same per-TLB size)."""
    tr = traces.generate("bst_internal", n_ops=4000, footprint_bytes=1 << 33)
    vp = tr.vpns(12)
    m1 = tlbsim.miss_ratio(vp, 128, num_partitions=1)
    m16 = tlbsim.miss_ratio(vp, 128, num_partitions=16)
    assert m16 <= m1 + 0.02


@settings(deadline=None, max_examples=10)
@given(st.integers(2, 64), st.integers(1, 4))
def test_bigger_tlb_never_worse(sets_pow, ways):
    tr = traces.generate("hash_table", n_ops=1500, footprint_bytes=1 << 30)
    vp = tr.vpns(12)
    small = tlbsim.miss_ratio(vp, 8 * ways, ways=ways)
    big = tlbsim.miss_ratio(vp, 8 * ways * 8, ways=ways)
    assert big <= small + 0.02


def test_joint_system_sim_consistency():
    tr = traces.generate("bst_internal", n_ops=2000, footprint_bytes=1 << 32)
    ev = tlbsim.simulate_system(tr.lines, tlbsim.SystemSimConfig(num_partitions=4))
    assert 0.0 <= ev.cache_hit_ratio <= 1.0
    assert 0.0 <= ev.mem_tlb_hit_ratio_given_cache_miss() <= 1.0


def test_2mb_pages_reduce_misses():
    tr = traces.generate("bst_internal", n_ops=4000, footprint_bytes=1 << 33)
    m4k = tlbsim.miss_ratio_curve(tr.lines, [256], page_shift=12)[0]
    m2m = tlbsim.miss_ratio_curve(tr.lines, [256], page_shift=21)[0]
    assert m2m <= m4k
