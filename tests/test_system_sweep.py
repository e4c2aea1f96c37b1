"""Oracle-equivalence + poisoning properties for the batched joint-system
Pallas kernel (repro.kernels.system_sim) and its sweep_system wiring.

The per-config simulator ``simulate_system`` is the reference path; the
batched scan (``system_sim_batched_ref``) and the batched Pallas kernel
(``system_sim_batched_pallas``, run under the interpreter on CPU) must match
it **bit-exactly** across heterogeneous batches: mixed cache/accel presence,
probe policies, partition counts, page sizes, way-envelope padding, VMEM
chunking, and non-block-multiple trace tails.
"""
import numpy as np
import pytest
from _propcheck import given, settings, st  # hypothesis, or deterministic fallback

from repro.core import sweep
from repro.core.sparta import TLBConfig
from repro.core.sweep import _system_vmem_chunks, sweep_system
from repro.core.tlbsim import SystemSimConfig, simulate_system
from repro.kernels.system_sim import resolve_system_mode

HIT_KEYS = ("cache_hit", "accel_tlb_hit", "mem_tlb_hit")


def _random_lines(seed: int, n: int = 1111) -> np.ndarray:
    # Deliberately not a multiple of any block size: every kernel run
    # exercises the trace-tail padding parked in the extra set row.
    return np.random.default_rng(seed).integers(0, 1 << 28, n).astype(np.int64)


def _assert_rows_match(bev, cfgs, lines):
    for i, c in enumerate(cfgs):
        ev = simulate_system(lines, c)
        for k in HIT_KEYS:
            np.testing.assert_array_equal(
                getattr(bev, k)[i], getattr(ev, k), err_msg=f"cfg {i} {k}")


# ---------------------------------------------------------------------------
# Oracle equivalence.
# ---------------------------------------------------------------------------

_HETEROGENEOUS = [
    SystemSimConfig(),                               # cache, no accel TLB
    SystemSimConfig(cache=None, num_partitions=8),   # cacheless
    SystemSimConfig(accel_tlb=TLBConfig(entries=8, ways=4),
                    num_partitions=4, accel_probe_on_miss_only=False),
    SystemSimConfig(accel_tlb=TLBConfig(entries=2, ways=4),   # entries < ways
                    page_shift=21, num_partitions=32),
    SystemSimConfig(mem_tlb=TLBConfig(entries=64, ways=8)),
    SystemSimConfig(cache=TLBConfig(entries=512, ways=8), num_partitions=16),
    SystemSimConfig(cache=None, accel_tlb=TLBConfig(entries=16, ways=2),
                    num_partitions=2, accel_probe_on_miss_only=False),
    SystemSimConfig(page_shift=21, num_partitions=128),
]


@settings(deadline=None, max_examples=3)
@given(st.integers(0, 10_000))
def test_system_kernel_bitexact_vs_oracle_heterogeneous(seed):
    """All three backends on a heterogeneous batch: every structure-presence
    combination, both probe policies, mixed partitions and page sizes."""
    lines = _random_lines(seed)
    cfgs = _HETEROGENEOUS
    ref = sweep_system(lines, cfgs, kernel_mode="reference")
    pal = sweep_system(lines, cfgs, kernel_mode="pallas_interpret", block=256)
    _assert_rows_match(ref, cfgs, lines)
    _assert_rows_match(pal, cfgs, lines)


def test_system_kernel_flags_are_data_not_structure():
    """One pallas_call serves present AND absent structures: flipping a
    config's flags must not perturb its batch neighbours (the flag-gating
    analogue of way poisoning)."""
    lines = _random_lines(3, n=900)
    base = SystemSimConfig(accel_tlb=TLBConfig(entries=16, ways=4),
                           num_partitions=4)
    neighbours = [
        SystemSimConfig(cache=None, num_partitions=4),
        SystemSimConfig(accel_tlb=None, num_partitions=4),
        SystemSimConfig(accel_tlb=TLBConfig(entries=16, ways=4),
                        num_partitions=4, accel_probe_on_miss_only=False),
    ]
    solo = sweep_system(lines, [base], kernel_mode="pallas_interpret", block=256)
    batched = sweep_system(lines, [base] + neighbours,
                           kernel_mode="pallas_interpret", block=256)
    for k in HIT_KEYS:
        np.testing.assert_array_equal(getattr(batched, k)[0], getattr(solo, k)[0])
    _assert_rows_match(batched, [base] + neighbours, lines)


# ---------------------------------------------------------------------------
# Padding / poisoning properties.
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=3)
@given(st.integers(0, 10_000))
def test_system_kernel_envelope_poisoning_invariance(seed):
    """A small config's rows are identical whether it runs alone (tight
    envelope) or stacked with a much larger config (every structure padded in
    sets AND ways): poisoned padding must be invisible."""
    lines = _random_lines(seed, n=800)
    small = SystemSimConfig(cache=TLBConfig(entries=8, ways=2),
                            accel_tlb=TLBConfig(entries=4, ways=2),
                            mem_tlb=TLBConfig(entries=8, ways=2),
                            num_partitions=2)
    big = SystemSimConfig(cache=TLBConfig(entries=1024, ways=8),
                          accel_tlb=TLBConfig(entries=256, ways=8),
                          mem_tlb=TLBConfig(entries=256, ways=8),
                          num_partitions=32)
    for mode in ("reference", "pallas_interpret"):
        solo = sweep_system(lines, [small], kernel_mode=mode, block=256)
        pair = sweep_system(lines, [small, big], kernel_mode=mode, block=256)
        for k in HIT_KEYS:
            np.testing.assert_array_equal(
                getattr(pair, k)[0], getattr(solo, k)[0], err_msg=f"{mode} {k}")


def test_system_kernel_block_multiple_trace_skips_padding():
    """Exact block-multiple traces take the no-padding path (no extra set
    row) and still match the oracle."""
    lines = _random_lines(5, n=1024)
    cfgs = [SystemSimConfig(num_partitions=p) for p in (1, 8)]
    pal = sweep_system(lines, cfgs, kernel_mode="pallas_interpret", block=256)
    _assert_rows_match(pal, cfgs, lines)


# ---------------------------------------------------------------------------
# VMEM chunking.
# ---------------------------------------------------------------------------

def test_system_sweep_chunking_under_tight_vmem_budget(monkeypatch):
    """When the three-structure envelope exceeds the scratch budget the
    kernel path splits the batch into like-sized chunks — results unchanged
    and every config lands in exactly one chunk."""
    monkeypatch.setattr(sweep, "_VMEM_STATE_BUDGET_BYTES", 64 * 1024)
    lines = _random_lines(11, n=700)
    cfgs = [
        SystemSimConfig(cache=TLBConfig(entries=1024, ways=8), num_partitions=64),
        SystemSimConfig(),
        SystemSimConfig(cache=None, num_partitions=4),
        SystemSimConfig(accel_tlb=TLBConfig(entries=4, ways=4), num_partitions=2),
    ]
    c_geo = [sweep._geom(c.cache) for c in cfgs]
    a_geo = [sweep._geom(c.accel_tlb) for c in cfgs]
    m_geo = [(sweep._geom(c.mem_tlb)[0] * c.num_partitions,
              sweep._geom(c.mem_tlb)[1]) for c in cfgs]
    dims = [c_geo[i] + a_geo[i] + m_geo[i] for i in range(len(cfgs))]
    chunks = _system_vmem_chunks(dims, block=256)
    assert len(chunks) > 1  # budget actually forces a split
    assert sorted(i for c in chunks for i in c) == list(range(len(cfgs)))
    pal = sweep_system(lines, cfgs, kernel_mode="pallas_interpret", block=256)
    _assert_rows_match(pal, cfgs, lines)


# ---------------------------------------------------------------------------
# Mode resolution policy.
# ---------------------------------------------------------------------------

def test_system_sweep_rejects_stackdist_loudly():
    """PR 4 policy: a sweep-only backend raises (stack inclusion does not
    hold for cache-hit-conditional probes) instead of being silently run as
    the scan."""
    with pytest.raises(ValueError, match="stack-inclusion"):
        sweep_system(_random_lines(0, n=64), [SystemSimConfig()],
                     kernel_mode="stackdist")
    with pytest.raises(ValueError, match="stack-inclusion"):
        resolve_system_mode("stackdist")


def test_system_mode_resolution():
    import jax

    with pytest.raises(ValueError):
        resolve_system_mode("not-a-mode")
    expect = "pallas" if jax.default_backend() == "tpu" else "reference"
    assert resolve_system_mode("auto") == expect
    assert resolve_system_mode("pallas_interpret") == "pallas_interpret"


# ---------------------------------------------------------------------------
# Grid steps that are no multiple of the 128-lane hit tile.
# ---------------------------------------------------------------------------

# Per structure (sets, ways) of the envelope, one spare parked set row
# included, and each of the three configurations' own ways.
_GEOM = (17, 4, 9, 4, 33, 4)
_VALID = ((4, 2, 4), (4, 4, 1), (2, 4, 4))
_FLAGS = np.array([[1, 1, 1], [0, 1, 0], [1, 0, 1]], np.int32)
# The share map of raw streams: every design its own representative.
_IDENTITY = ((0, 1, 2),) * 3


def _key_streams(seed: int, n: int):
    """Random (set, tag) streams of the three structures for the three
    configurations; sets stop short of each structure's parked row."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    streams = []
    for k in range(3):
        streams.append(rng.integers(0, _GEOM[2 * k] - 1, (3, n)))
        streams.append(rng.integers(0, 12, (3, n)))
    return [jnp.asarray(s, jnp.int32) for s in streams]


def _ref_flags():
    import jax.numpy as jnp

    return tuple(jnp.asarray(_FLAGS[:, c] > 0) for c in range(3))


@pytest.mark.parametrize("n,block", [(200, 512), (100, 512), (400, 200)])
def test_system_kernel_ragged_blocks_match_scan(n, block):
    """Blocks of 200 (a full 128-access hit tile and a 72-access tail),
    100 (a tail alone) and two grid steps of 200 against the batched scan."""
    import jax.numpy as jnp

    from repro.kernels.system_sim.kernel import system_sim_batched_pallas
    from repro.kernels.system_sim.ref import system_sim_batched_ref

    streams = _key_streams(n + block, n)
    ref = system_sim_batched_ref(tuple(streams), _ref_flags(), _GEOM, _VALID)
    pal = system_sim_batched_pallas(*streams, jnp.asarray(_FLAGS), _GEOM,
                                    _VALID, share=_IDENTITY, block=block,
                                    interpret=True)
    for k, r, p in zip(HIT_KEYS, ref, pal):
        np.testing.assert_array_equal(np.asarray(p), np.asarray(r), err_msg=k)


@pytest.mark.parametrize("chunks,block", [((200, 100), 512),
                                          ((256, 128, 44), 128)])
def test_system_carry_kernel_ragged_chunks_match_scan(chunks, block):
    """Chunks whose kernel blocks are no multiple of 128 (200, 100 and a
    partial last chunk of 44) carry state exactly as the carried scan does,
    and together equal one monolithic scan."""
    import jax.numpy as jnp

    from repro.core.tlbsim import padded_tlb_state
    from repro.kernels.system_sim.kernel import system_sim_batched_pallas_carry
    from repro.kernels.system_sim.ref import (
        system_sim_batched_carry_ref, system_sim_batched_ref)

    n = sum(chunks)
    streams = _key_streams(n, n)
    state0 = tuple(x for k in range(3) for x in padded_tlb_state(
        3, _GEOM[2 * k], _GEOM[2 * k + 1], _VALID[k]))
    flags = jnp.asarray(_FLAGS)
    got, want = [], []
    st_p = st_r = state0
    lo = 0
    for size in chunks:
        part = [s[:, lo:lo + size] for s in streams]
        h_p, st_p = system_sim_batched_pallas_carry(
            *part, flags, st_p, lo, size, share=_IDENTITY, block=block,
            interpret=True)
        h_r, st_r = system_sim_batched_carry_ref(
            tuple(part), _ref_flags(), st_r, jnp.asarray(lo))
        got.append([np.asarray(h) for h in h_p])
        want.append([np.asarray(h) for h in h_r])
        lo += size
    for a, b in zip(st_p, st_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    whole = system_sim_batched_ref(tuple(streams), _ref_flags(), _GEOM,
                                   _VALID)
    for k in range(3):
        p = np.concatenate([g[k] for g in got], axis=1)
        np.testing.assert_array_equal(
            p, np.concatenate([w[k] for w in want], axis=1), err_msg=HIT_KEYS[k])
        np.testing.assert_array_equal(p, np.asarray(whole[k]),
                                      err_msg=HIT_KEYS[k])


@pytest.mark.parametrize("sets,ways,valid", [(17, 4, (4, 2)),
                                             (4, 256, (256, 200))])
def test_tlb_carry_kernel_ragged_chunks_match_scan(sets, ways, valid):
    """The probe the system kernel shares, in the TLB carry kernel: chunks of
    200 and 100 accesses (blocks no multiple of 128), narrow ways and ways
    that span two rows."""
    import jax.numpy as jnp

    from repro.core.tlbsim import padded_tlb_state
    from repro.kernels.tlb_sim.kernel import tlb_sim_batched_pallas_carry
    from repro.kernels.tlb_sim.ref import tlb_sim_batched_carry_ref

    rng = np.random.default_rng(ways)
    s = jnp.asarray(rng.integers(0, sets, (2, 300)), jnp.int32)
    t = jnp.asarray(rng.integers(0, 3 * ways, (2, 300)), jnp.int32)
    pal = ref = padded_tlb_state(2, sets, ways, valid)
    for lo, hi in ((0, 200), (200, 300)):
        h_p, *pal = tlb_sim_batched_pallas_carry(
            s[:, lo:hi], t[:, lo:hi], *pal, lo, interpret=True)
        h_r, *ref = tlb_sim_batched_carry_ref(s[:, lo:hi], t[:, lo:hi], *ref,
                                              lo)
        np.testing.assert_array_equal(np.asarray(h_p), np.asarray(h_r))
    for a, b in zip(pal, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Shared structures: the share map and the kernel that probes only
# representatives.
# ---------------------------------------------------------------------------

def _fig10_cfgs():
    from benchmarks import fig10_performance as fig10

    return fig10.configs()


def _fig9_cfgs():
    from benchmarks import fig9_accel_tlb as fig9

    return fig9.configs()


# Duplicate and unique structures of every kind: designs 0-3 and 7 share
# one cache, 0 and 2 an accelerator TLB, 2 and 3 (same partitions, other
# probe policy) a memory TLB, and the cacheless 4 and 5 everything.
_SHARED = [
    SystemSimConfig(accel_tlb=TLBConfig(entries=16, ways=4), num_partitions=4),
    SystemSimConfig(num_partitions=4),
    SystemSimConfig(accel_tlb=TLBConfig(entries=16, ways=4), num_partitions=8),
    SystemSimConfig(accel_tlb=TLBConfig(entries=16, ways=4), num_partitions=8,
                    accel_probe_on_miss_only=False),
    SystemSimConfig(cache=None, num_partitions=4),
    SystemSimConfig(cache=None, num_partitions=4),
    SystemSimConfig(cache=TLBConfig(entries=64, ways=2), num_partitions=4),
    SystemSimConfig(accel_tlb=TLBConfig(entries=16, ways=4), page_shift=21,
                    num_partitions=4),
]

_DISTINCT = [
    SystemSimConfig(cache=TLBConfig(entries=64 << i, ways=4),
                    accel_tlb=TLBConfig(entries=8 << i, ways=2),
                    num_partitions=1 << i)
    for i in range(3)
]


@pytest.mark.parametrize("batch,want,probes", [
    # One cache, the two conventional designs' accelerator TLBs, seven
    # memory TLBs: conv-4K, DIPTA and ideal share 1 partition of 4K pages.
    (_fig10_cfgs, ((0,) * 9, (0, 1) + (-1,) * 7, (0, 1, 2, 3, 4, 5, 6, 0, 0)),
     10),
    # One cache and the 8-partition memory TLB of every design but the
    # baseline; every accelerator TLB differs in size or probe policy.
    (_fig9_cfgs, ((0,) * 10, tuple(range(9)) + (-1,), (0,) + (1,) * 9), 12),
    (lambda: _DISTINCT, ((0, 1, 2),) * 3, 9),
    # Cacheless designs and designs without an accelerator TLB probe no
    # such structure.
    (lambda: [SystemSimConfig(cache=None), SystemSimConfig(cache=None)],
     ((-1, -1), (-1, -1), (0, 0)), 1),
    (lambda: _SHARED, ((0, 0, 0, 0, -1, -1, 6, 0), (0, -1, 0, 3, -1, -1, -1, 7),
                       (0, 0, 2, 2, 4, 4, 6, 7)), 10),
])
def test_system_share_map(batch, want, probes):
    share = sweep._system_share(batch())
    assert share == want
    assert sweep._probes_per_access(share) == probes


def test_system_kernel_shared_structures_bitexact():
    """A batch with duplicate and unique structures, whole, through the
    monolithic kernel: every design's hit bits, followers' included, equal
    the scan's and ``simulate_system``'s."""
    lines = _random_lines(17, n=900)
    ref = sweep_system(lines, _SHARED, kernel_mode="reference")
    pal = sweep_system(lines, _SHARED, kernel_mode="pallas_interpret",
                       block=256)
    _assert_rows_match(ref, _SHARED, lines)
    _assert_rows_match(pal, _SHARED, lines)


def _assert_states_equal(got: dict, want: dict):
    """Every design's every set row; the parked row that each structure's
    state ends with holds only the pads' stamps (the carry op's state
    layout contract), so it is left out."""
    assert got.keys() == want.keys()
    for k in want:
        rows = slice(None) if k == "now" else np.s_[:, :-1]
        np.testing.assert_array_equal(got[k][rows], want[k][rows], err_msg=k)


@pytest.mark.parametrize("cuts,block", [((0, 256, 456, 500), 256),
                                        ((0, 512, 712, 756), 128)])
def test_system_carry_shared_structures_keep_every_state(cuts, block):
    """Ragged chunks (after a full one, 200 and 44 accesses: no multiple of
    128) of the duplicate-and-unique batch through the carry kernel, each
    padded to the stream's longest chunk, so that with blocks of 128 the
    kernel skips the grid steps past the chunk: hits equal
    ``simulate_system``, every design's carried state (followers and absent
    structures included) equals the carried scan's after every chunk, and a
    stream resumed from ``export_state`` mid-trace (whose chunks pad to
    another length) stays bit-identical."""
    from repro.core.sweep import SystemSweepStream

    lines = _random_lines(19, n=cuts[-1])
    pal = SystemSweepStream(_SHARED, block=block)
    ref = SystemSweepStream(_SHARED, block=block)
    got = []
    for lo, hi in zip(cuts, cuts[1:]):
        got.append(pal.run_chunk(lines[lo:hi], kernel_mode="pallas_interpret"))
        ref.run_chunk(lines[lo:hi], kernel_mode="reference")
        _assert_states_equal(pal.export_state(), ref.export_state())
        if lo == 0:
            resumed = SystemSweepStream(_SHARED, block=block)
            resumed.import_state(pal.export_state())
    for lo, hi in zip(cuts[1:], cuts[2:]):
        again = resumed.run_chunk(lines[lo:hi], kernel_mode="pallas_interpret")
        for a, b in zip(again, got[cuts.index(lo)]):
            np.testing.assert_array_equal(a, b)
    _assert_states_equal(resumed.export_state(), pal.export_state())
    whole = [np.concatenate([g[k] for g in got], axis=1) for k in range(3)]
    _assert_rows_match(sweep.BatchedSystemEvents(*whole, n_warm=0),
                       _SHARED, lines)


def test_system_stream_traces_one_kernel_for_a_ragged_trace():
    """A trace's tail chunks (200 and 44 accesses after a full 512) run in
    the kernel its full chunk traced and compiled: one trace of the carry
    kernel for the whole trace, with hits equal to ``simulate_system``
    (lines from a narrow range, so every structure hits)."""
    from repro.core.sweep import SystemSweepStream
    from repro.kernels.system_sim.kernel import system_sim_batched_pallas_carry

    lines = np.random.default_rng(23).integers(0, 2048, 756).astype(np.int64)
    system_sim_batched_pallas_carry.clear_cache()
    stream = SystemSweepStream(_DISTINCT, block=128)
    got = [stream.run_chunk(lines[lo:hi], kernel_mode="pallas_interpret")
           for lo, hi in ((0, 512), (512, 712), (712, 756))]
    assert system_sim_batched_pallas_carry._cache_size() == 1
    whole = [np.concatenate([g[k] for g in got], axis=1) for k in range(3)]
    assert all(w[:, 512:].any() for w in whole)
    _assert_rows_match(sweep.BatchedSystemEvents(*whole, n_warm=0),
                       _DISTINCT, lines)


@pytest.mark.parametrize("batch,mode,per_access", [
    (_fig10_cfgs, "pallas_interpret", 10),
    (_fig10_cfgs, "reference", 27),
    # The heterogeneous batch above: two caches, three accelerator TLBs and
    # eight memory TLBs present and distinct.
    (lambda: _HETEROGENEOUS, "pallas_interpret", 13),
])
def test_system_stream_counts_lru_probes(batch, mode, per_access):
    """``sweep_system.lru_probes`` counts the structures probed per access:
    each representative once in the kernel, every structure in the scan."""
    from repro.core.sweep import SystemSweepStream
    from repro.runtime import telemetry

    cfgs = batch()
    n = 300
    tr = telemetry.get_tracer()
    tr.start_run(None, run="probes")
    try:
        stream = SystemSweepStream(cfgs, block=256)
        stream.run_chunk(_random_lines(23, n=n), kernel_mode=mode)
    finally:
        counters = tr.end_run()["counters"]
    assert counters["sweep_system.lru_probes"]["value"] == per_access * n
    assert counters["sweep_system.sim_accesses"]["value"] == len(cfgs) * n


# ---------------------------------------------------------------------------
# Key streams derived on the device from one uint32 line upload.
# ---------------------------------------------------------------------------

# Sets and partition counts that are no power of two.
_ODD = [
    SystemSimConfig(cache=TLBConfig(entries=96, ways=4),
                    accel_tlb=TLBConfig(entries=12, ways=4), num_partitions=3),
    SystemSimConfig(cache=TLBConfig(entries=40, ways=8),
                    mem_tlb=TLBConfig(entries=80, ways=8), num_partitions=6,
                    page_shift=21),
    SystemSimConfig(cache=None, mem_tlb=TLBConfig(entries=28, ways=4),
                    num_partitions=7),
]

# Lines at the edges of the int32 and uint32 ranges, among random ones.
_EDGES = np.array([0, 2**31 - 1, 2**31, 2**32 - 1], np.int64)


def _wide_lines(seed: int, n: int) -> np.ndarray:
    lines = np.random.default_rng(seed).integers(0, 2**32, n).astype(np.int64)
    lines[:_EDGES.size] = _EDGES
    return lines


@pytest.mark.parametrize("batch,n,length", [
    (_fig10_cfgs, 700, 700),
    (_fig9_cfgs, 700, 700),
    (lambda: _ODD, 700, 700),
    (lambda: _HETEROGENEOUS, 700, 700),
    # A partial chunk: pads from the 300th position on.
    (_fig10_cfgs, 300, 768),
    (lambda: _ODD, 5, 512),
])
def test_device_keys_match_host_keys(batch, n, length):
    """The device key streams equal ``_system_keys`` stacked over the
    designs, bit for bit, and every pad sits in its structure's parked row
    with tag 0."""
    import jax.numpy as jnp

    cfgs = batch()
    lines = _wide_lines(n, n)
    rules = sweep._system_key_rules(cfgs)
    assert sweep._keys_fit_device(lines, rules)
    parked = (1001, 1002, 1003)
    got = sweep._device_system_keys(
        jnp.asarray(sweep._padded_lines(lines, length)), n, rules=rules,
        parked=parked)
    want = [np.stack(rows) for rows in
            zip(*(sweep._system_keys(lines, c) for c in cfgs))]
    for k, (g, w) in enumerate(zip(got, want)):
        g = np.asarray(g)
        assert g.shape == (len(cfgs), length) and g.dtype == np.int32
        np.testing.assert_array_equal(g[:, :n], w, err_msg=f"stream {k}")
        assert (g[:, n:] == (parked[k // 2] if k % 2 == 0 else 0)).all()


def _hits(evs):
    return [evs.cache_hit, evs.accel_tlb_hit, evs.mem_tlb_hit]


@pytest.mark.parametrize("mode", ["reference", "pallas_interpret"])
@pytest.mark.parametrize("batch", [_fig10_cfgs, lambda: _ODD])
def test_chunked_sweep_matches_monolithic_on_device_keys(batch, mode):
    """Chunked ``run_sweep_system`` (a full chunk of 512 and a partial one
    of 188, padded to the full one) equals the monolithic ``sweep_system``
    and ``simulate_system`` on lines across the whole uint32 range, and
    every chunk's keys come from the device."""
    from repro.core.orchestrator import SweepRunConfig, run_sweep_system
    from repro.runtime import telemetry

    cfgs = batch()
    lines = _wide_lines(29, 700)
    tr = telemetry.get_tracer()
    tr.start_run(None, run="device-keys")
    try:
        bev, _ = run_sweep_system(lines, cfgs, kernel_mode=mode, block=256,
                                  run=SweepRunConfig(chunk_accesses=512))
    finally:
        counters = tr.end_run()["counters"]
    assert counters["sweep_system.device_key_chunks"]["value"] == 2
    assert "sweep_system.host_key_chunks" not in counters
    whole = sweep_system(lines, cfgs, kernel_mode=mode, block=256)
    for a, b in zip(_hits(bev), _hits(whole)):
        np.testing.assert_array_equal(a, b)
    _assert_rows_match(bev, cfgs, lines)


def _count_key_chunks(fn):
    from repro.runtime import telemetry

    tr = telemetry.get_tracer()
    tr.start_run(None, run="key-chunks")
    try:
        fn()
    finally:
        counters = tr.end_run()["counters"]
    return {k: counters.get(f"sweep_system.{k}_key_chunks", {}).get("value", 0)
            for k in ("device", "host")}


@pytest.mark.parametrize("mode", ["reference", "pallas_interpret"])
def test_lines_past_uint32_take_the_host_keys(mode):
    """A chunk holding a line at 2**32 builds its keys on the host, with
    the hits ``simulate_system`` gives, and counts a host key chunk; the
    chunk before it, whose lines fit, a device key chunk."""
    from repro.core.sweep import SystemSweepStream

    cfgs = _fig10_cfgs()
    lines = _wide_lines(31, 600)
    lines[450] = 2**32
    stream = SystemSweepStream(cfgs, block=256)
    got = []
    counts = [_count_key_chunks(lambda lo=lo, hi=hi: got.append(
        stream.run_chunk(lines[lo:hi], kernel_mode=mode)))
        for lo, hi in ((0, 400), (400, 600))]
    assert counts == [{"device": 1, "host": 0}, {"device": 0, "host": 1}]
    whole = [np.concatenate([g[k] for g in got], axis=1) for k in range(3)]
    _assert_rows_match(sweep.BatchedSystemEvents(*whole, n_warm=0), cfgs,
                       lines)
    mono = _count_key_chunks(lambda: _assert_rows_match(
        sweep_system(lines, cfgs, kernel_mode=mode, block=256), cfgs, lines))
    assert mono == {"device": 0, "host": 1}


@pytest.mark.parametrize("line", [2**31 + 5, 2**40])
def test_tag_overflow_takes_the_host_keys_and_raises(line):
    """A line whose tag passes 2**31 under a one-set cache (below 2**32 as
    well as past it) takes the host keys, which raise their ``tag
    overflow`` as before."""
    from repro.core.sweep import SystemSweepStream

    cfgs = [SystemSimConfig(cache=TLBConfig(entries=1, ways=1))]
    lines = np.array([3, line, 7], np.int64)
    assert not sweep._keys_fit_device(lines, sweep._system_key_rules(cfgs))
    stream = SystemSweepStream(cfgs, block=256)

    def run():
        with pytest.raises(ValueError, match="tag overflow"):
            stream.run_chunk(lines, kernel_mode="reference")
    assert _count_key_chunks(run) == {"device": 0, "host": 1}
    with pytest.raises(ValueError, match="tag overflow"):
        sweep_system(lines, cfgs, kernel_mode="reference")


@pytest.mark.parametrize("line", [2**31 + 3, 2**32])
def test_key_streams_pad_each_group_alike_on_both_paths(line):
    """``_key_streams`` gives each VMEM group its designs' keys, then pads
    in the group's parked rows with tag 0, whether the keys are derived on
    the device (a line below 2**32) or built on the host (one at 2**32)."""
    cfgs = _fig10_cfgs()
    lines = _wide_lines(37, 300) % (2**31)
    lines[7] = line
    groups = [[0, 2, 4], [1, 3, 5, 6, 7, 8]]
    parked = [(501, 502, 503), (601, 602, 603)]
    got = sweep._key_streams(lines, cfgs, groups, parked, 512)
    want = [np.stack(rows) for rows in
            zip(*(sweep._system_keys(lines, c) for c in cfgs))]
    for g, park, streams in zip(groups, parked, got):
        for k, s in enumerate(streams):
            s = np.asarray(s)
            assert s.shape == (len(g), 512) and s.dtype == np.int32
            np.testing.assert_array_equal(s[:, :300], want[k][g])
            assert (s[:, 300:] == (park[k // 2] if k % 2 == 0 else 0)).all()
