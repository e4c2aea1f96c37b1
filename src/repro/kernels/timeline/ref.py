"""Pure-jnp oracle for the cycle-approximate timeline engine.

One :func:`timeline_step` advances the full queueing state by one trace
access; :func:`timeline_scan_ref` wraps it in a ``lax.scan``.  The Pallas
kernel (:mod:`repro.kernels.timeline.kernel`) executes the *same* step
function against VMEM-resident state, so the two paths are bit-identical by
construction (asserted by ``tests/test_timeline.py``).

Latency composition per access (virtual-cache accelerator, Fig 3 timelines):

* cache hit — ``l_cache``; never leaves the accelerator, no queueing.
* cache miss — design-specific translation + data path with three queueing
  points threaded in:

  - **MSHR window** (per accelerator): a miss may only *issue* once one of
    the accelerator's ``mshrs`` outstanding-miss slots is free (FIFO slot
    reuse: the i-th miss waits on the (i - mshrs)-th miss's completion).
  - **Memory-side TLB ports** (per partition, SPARTA only): a translation
    waits for the earliest-free of the partition's ``tlb_ports`` ports and
    occupies it for ``tlb_occ`` cycles.
  - **DRAM banks** (machine-wide): every DRAM reference (page walk, PTE
    read, data fetch) waits for its bank and occupies it for ``dram_occ``
    cycles.

With every resource unbounded (count 0) all waits vanish and the per-access
latency is exactly the Fig 3 analytical composition, so the post-warmup mean
reproduces :mod:`repro.core.cpi` — the subsystem's oracle property.

Arithmetic is float32 but every default latency parameter is an integer
number of cycles, so all absolute times and latencies stay exactly
representable (integer cycle counts) far beyond any benchmark's horizon.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class TimelineParams(NamedTuple):
    """Static (compile-time) scan parameters.

    ``serial_walk`` selects the conventional design (private accel-side TLB,
    page walk serialized before the data fetch); ``mem_tlb`` selects SPARTA
    (translation at the partition's memory-side TLB, overlapped with the
    network traversal).  Neither flag => DIPTA/ideal (translation fully
    overlapped; the per-access ``pen`` input carries DIPTA's serialized
    way-misprediction penalty, 0 for ideal).

    A resource count of 0 means *unbounded* (no queueing on that resource).
    """

    serial_walk: bool = False
    mem_tlb: bool = False
    num_accels: int = 1
    mshrs: int = 0            # outstanding-miss slots per accelerator
    num_partitions: int = 1   # memory-side TLB partitions (SPARTA P)
    tlb_ports: int = 0        # service ports per partition TLB
    dram_banks: int = 0       # DRAM banks machine-wide
    l_cache: float = 2.0
    l_tlb: float = 2.0
    l_dram: float = 120.0
    t_net: float = 390.0
    tlb_occ: float = 2.0      # port busy time per probe
    dram_occ: float = 120.0   # bank busy time per access
    issue_interval: float = 1.0  # cycles between successive issues per accel


def timeline_init_state(p: TimelineParams):
    """All-zero queueing state (times in cycles; everything free at t=0)."""
    A = p.num_accels
    return (
        jnp.zeros((A,), jnp.float32),                       # next nominal issue
        jnp.zeros((A, max(p.mshrs, 1)), jnp.float32),       # MSHR slot free times
        jnp.zeros((A,), jnp.int32),                         # per-accel miss count
        jnp.zeros((max(p.num_partitions, 1), max(p.tlb_ports, 1)), jnp.float32),
        jnp.zeros((max(p.dram_banks, 1),), jnp.float32),    # bank free times
    )


def timeline_step(state, inp, p: TimelineParams):
    """Advance the queueing state by one access.

    ``inp`` is the per-access tuple ``(accel, partition, bank_data, bank_pte,
    cache_hit, tlb_hit, mem_tlb_hit, pen)`` (int32 scalars + float32 ``pen``).
    Returns ``(state', (latency, overhead, done))`` where ``latency`` is
    issue->completion cycles, ``overhead`` the translation-induced component
    (including translation queue waits), ``done`` the absolute completion
    time.  Latencies are composed from *segments* (waits + service times), not
    endpoint differences, so unqueued runs are exact in float32 regardless of
    how far absolute time has advanced.
    """
    acc_next, mshr_ring, mshr_cnt, port_free, bank_free = state
    a, part, bank_d, bank_p, c, th, mh, pen = inp
    zero = jnp.float32(0.0)
    c_hit = c != 0
    nominal = acc_next[a]

    # --- MSHR admission: a miss needs a free outstanding-miss slot. ---------
    if p.mshrs > 0:
        slot = mshr_cnt[a] % p.mshrs
        w_mshr = jnp.maximum(mshr_ring[a, slot] - nominal, zero)
        issue = nominal + jnp.where(c_hit, zero, w_mshr)
    else:
        slot = jnp.int32(0)
        issue = nominal

    t0 = issue + p.l_cache  # cache probe; a miss leaves the accelerator here

    # --- translation path (computed unconditionally, applied on miss) -------
    if p.serial_walk:
        # Conventional: private accel-side TLB probe, then a page walk (one
        # memory reference over the network) serialized before the data fetch.
        walk_arr = t0 + p.l_tlb + p.t_net
        if p.dram_banks > 0:
            w_walk = jnp.maximum(bank_free[bank_p] - walk_arr, zero)
            do_walk = (~c_hit) & (th == 0)
            bank_free = bank_free.at[bank_p].set(jnp.where(
                do_walk, walk_arr + w_walk + p.dram_occ, bank_free[bank_p]))
        else:
            w_walk = zero
        walk = 2.0 * p.t_net + w_walk + p.l_dram
        trans = p.l_tlb + jnp.where(th != 0, zero, walk)
        # data fetch departs only after the walk returns: l_cache + trans,
        # then a full network round trip around the data DRAM access.
        data_arr = t0 + trans + p.t_net
        pen_eff = zero
    elif p.mem_tlb:
        # SPARTA: request reaches the partition after one traversal; the
        # memory-side TLB probe queues on the partition's ports and a miss
        # reads the PTE from the *local* DRAM (no extra traversals).
        arr = t0 + p.t_net
        if p.tlb_ports > 0:
            row = port_free[part]
            pslot = jnp.argmin(row)
            w_port = jnp.maximum(row[pslot] - arr, zero)
            port_free = port_free.at[part, pslot].set(jnp.where(
                ~c_hit, arr + w_port + p.tlb_occ, row[pslot]))
        else:
            w_port = zero
        probe_done = arr + w_port + p.l_tlb
        if p.dram_banks > 0:
            w_pte = jnp.maximum(bank_free[bank_p] - probe_done, zero)
            do_pte = (~c_hit) & (mh == 0)
            bank_free = bank_free.at[bank_p].set(jnp.where(
                do_pte, probe_done + w_pte + p.dram_occ, bank_free[bank_p]))
        else:
            w_pte = zero
        trans = w_port + p.l_tlb + jnp.where(mh != 0, zero, w_pte + p.l_dram)
        data_arr = arr + trans  # translation completes at the partition
        pen_eff = zero
    else:
        # DIPTA/ideal: translation fully overlapped with the row fetch; pen
        # carries DIPTA's serialized way-misprediction penalty (0 for ideal).
        trans = pen
        data_arr = t0 + p.t_net
        pen_eff = pen

    # --- data DRAM access (all designs) -------------------------------------
    if p.dram_banks > 0:
        w_data = jnp.maximum(bank_free[bank_d] - data_arr, zero)
        bank_free = bank_free.at[bank_d].set(jnp.where(
            ~c_hit, data_arr + w_data + p.dram_occ + pen_eff, bank_free[bank_d]))
    else:
        w_data = zero

    if p.serial_walk:
        lat_miss = p.l_cache + trans + p.t_net + w_data + p.l_dram + p.t_net
    elif p.mem_tlb:
        lat_miss = p.l_cache + p.t_net + trans + w_data + p.l_dram + p.t_net
    else:
        lat_miss = p.l_cache + p.t_net + w_data + p.l_dram + pen_eff + p.t_net

    latency = jnp.where(c_hit, jnp.float32(p.l_cache), lat_miss)
    overhead = jnp.where(c_hit, zero, trans)
    done = issue + latency

    # --- state updates -------------------------------------------------------
    if p.mshrs > 0:
        mshr_ring = mshr_ring.at[a, slot].set(
            jnp.where(c_hit, mshr_ring[a, slot], done))
        mshr_cnt = mshr_cnt.at[a].add(jnp.where(c_hit, 0, 1))
    acc_next = acc_next.at[a].set(issue + p.issue_interval)
    return (acc_next, mshr_ring, mshr_cnt, port_free, bank_free), (
        latency, overhead, done)


# ---------------------------------------------------------------------------
# Batched multi-simulation path: per-sim parameters become *data*.
# ---------------------------------------------------------------------------
#
# ``sweep_timeline`` (repro.core.timeline) stacks B heterogeneous simulations
# (mixed designs, accelerator counts, resource bounds, trace lengths) on a
# leading sim axis and advances all of them per trace element.  The static
# Python branches of :func:`timeline_step` (``if p.serial_walk`` / ``if
# p.mshrs > 0``) cannot be vmapped across sims that disagree on them, so
# :func:`timeline_step_dyn` re-expresses the same step with the per-sim
# configuration as two packed *traced* rows:
#
# * ``fp`` float32 [8]  — ``FP_COLS``: the latency table (plus ``walk2``, the
#   host-precomputed ``float32(2.0 * t_net)`` so the conventional walk's
#   round-trip term is rounded exactly like the oracle's Python-float fold).
# * ``ip`` int32   [7]  — ``IP_COLS``: design flags + resource counts.
#
# State arrays are padded to the batch's common resource envelope.  Padding is
# *poisoned* exactly like the PR-1 TLB sweep so it can never be observed:
#
# * MSHR slots / DRAM banks beyond a sim's own count are never indexed (slot
#   ids come from ``cnt % mshrs`` and per-sim bank ids are ``< dram_banks``),
#   so they stay at their always-free initial 0.
# * TLB-port columns beyond a sim's own ``tlb_ports`` are initialised to
#   ``PORT_POISON`` (~f32 max): the earliest-free ``argmin`` can never select
#   them, so the chosen port index — and hence every wait — matches the
#   oracle's own-width ``argmin`` bit-exactly.
#
# Every jnp.where selects between expressions computed in the oracle's exact
# float32 operation order, so per-sim outputs are bit-identical to
# :func:`timeline_step` on that sim's own configuration
# (tests/test_timeline_sweep.py asserts this across heterogeneous batches).

FP_COLS = ("l_cache", "l_tlb", "l_dram", "t_net", "walk2", "tlb_occ",
           "dram_occ", "issue_interval")
IP_COLS = ("serial_walk", "mem_tlb", "num_accels", "mshrs", "num_partitions",
           "tlb_ports", "dram_banks")

PORT_POISON = 3.0e38  # ~f32 max: argmin never selects a padded port column


def pack_params(p: TimelineParams):
    """(fp float32 [8], ip int32 [7]) rows for one sim's configuration."""
    fp = np.array([p.l_cache, p.l_tlb, p.l_dram, p.t_net,
                   np.float32(2.0 * p.t_net), p.tlb_occ, p.dram_occ,
                   p.issue_interval], np.float32)
    ip = np.array([int(p.serial_walk), int(p.mem_tlb), p.num_accels, p.mshrs,
                   p.num_partitions, p.tlb_ports, p.dram_banks], np.int32)
    return fp, ip


def timeline_init_state_batched(B: int, envelope, tlb_ports: jnp.ndarray):
    """Stacked all-zero queueing state on the (A, M, P, T, D) resource
    envelope, with port columns beyond each sim's own ``tlb_ports`` poisoned
    as always-busy (see module notes above)."""
    A, M, P, T, D = envelope
    col = jax.lax.broadcasted_iota(jnp.int32, (B, P, T), 2)
    port0 = jnp.where(col < tlb_ports[:, None, None],
                      jnp.float32(0.0), jnp.float32(PORT_POISON))
    return (
        jnp.zeros((B, A), jnp.float32),
        jnp.zeros((B, A, M), jnp.float32),
        jnp.zeros((B, A), jnp.int32),
        port0,
        jnp.zeros((B, D), jnp.float32),
    )


def _masked_set(arr, mask, value):
    """Dense equivalent of ``arr.at[idx].set(value)`` (``mask`` one-hot at
    idx): identical result, but vmapping it over sims yields wide
    compare/selects instead of batched scatters — the latter are the
    dominant cost of the batched scan on CPU backends."""
    return jnp.where(mask, value, arr)


def _masked_get(arr, mask):
    """Dense equivalent of ``arr[idx]`` (``mask`` one-hot at idx): the max
    over the masked-in element and the dtype's lowest value, which returns
    that element bit-exactly.  A gather from a value at a traced index is a
    dynamic slice, which Mosaic cannot lower; this form compiles there and
    vmaps into plain selects."""
    low = (-jnp.inf if jnp.issubdtype(arr.dtype, jnp.floating)
           else jnp.iinfo(arr.dtype).min)
    return jnp.max(jnp.where(mask, arr, jnp.asarray(low, arr.dtype)))


def _ix(arr, axis):
    return jax.lax.broadcasted_iota(jnp.int32, arr.shape, axis)


def timeline_step_dyn(state, inp, fp, ip):
    """One sim's :func:`timeline_step` with traced per-sim parameters and
    envelope-padded state.  Shared by the batched ``lax.scan`` reference
    (vmapped over sims) and the batched Pallas kernel (fori over sims), so
    those two paths are bit-identical by construction — and each sim is
    bit-identical to the static-param oracle on its own configuration.

    Every read and write at a traced index goes through a one-hot mask
    (:func:`_masked_get` / :func:`_masked_set`), so the step holds no gather
    or scatter.  The per-accelerator and per-bank vectors may carry a
    leading unit axis (the kernel keeps them as ``[1, n]`` rows); their
    index runs along the last axis.  ``fp`` / ``ip`` may be arrays or
    sequences of scalars."""
    acc_next, mshr_ring, mshr_cnt, port_free, bank_free = state
    a, part, bank_d, bank_p, c, th, mh, pen = inp
    l_cache, l_tlb, l_dram, t_net = fp[0], fp[1], fp[2], fp[3]
    walk2, tlb_occ, dram_occ, issue_iv = fp[4], fp[5], fp[6], fp[7]
    serial, memtlb = ip[0] != 0, ip[1] != 0
    mshrs, ports, banks = ip[3], ip[5], ip[6]
    zero = jnp.float32(0.0)
    c_hit = c != 0
    accel_mask = _ix(acc_next, acc_next.ndim - 1) == a
    bank_ix = _ix(bank_free, bank_free.ndim - 1)
    nominal = _masked_get(acc_next, accel_mask)

    # --- MSHR admission (slot ids never reach padded columns) ---------------
    slot = _masked_get(mshr_cnt, accel_mask) % jnp.maximum(mshrs, 1)
    mshr_mask = (_ix(mshr_ring, 0) == a) & (_ix(mshr_ring, 1) == slot)
    w_mshr = jnp.maximum(_masked_get(mshr_ring, mshr_mask) - nominal, zero)
    use_mshr = (~c_hit) & (mshrs > 0)
    issue = nominal + jnp.where(use_mshr, w_mshr, zero)

    t0 = issue + l_cache

    # --- SPARTA port queue (poisoned columns lose every argmin) -------------
    # The earliest-free port of the partition's row, first index on ties
    # (argmin's rule); its free time is the row minimum itself.
    arr = t0 + t_net
    in_row = _ix(port_free, 0) == part
    row_min = jnp.min(jnp.where(in_row, port_free, jnp.float32(jnp.inf)))
    col = _ix(port_free, 1)
    pslot = jnp.min(jnp.where(in_row & (port_free == row_min), col,
                              port_free.shape[1]))
    w_port = jnp.where(ports > 0, jnp.maximum(row_min - arr, zero), zero)
    do_port = memtlb & (~c_hit) & (ports > 0)
    port_free = _masked_set(port_free, in_row & (col == pslot) & do_port,
                            arr + w_port + tlb_occ)
    probe_done = arr + w_port + l_tlb

    # --- translation-path DRAM reference (conv walk / SPARTA PTE read) ------
    walk_arr = t0 + l_tlb + t_net
    trans_arr = jnp.where(serial, walk_arr, probe_done)
    pte_mask = bank_ix == bank_p
    w_tr = jnp.where(banks > 0, jnp.maximum(
        _masked_get(bank_free, pte_mask) - trans_arr, zero), zero)
    do_tr = (~c_hit) & (banks > 0) & jnp.where(
        serial, th == 0, memtlb & (mh == 0))
    bank_free = _masked_set(bank_free, pte_mask & do_tr,
                            trans_arr + w_tr + dram_occ)

    walk = walk2 + w_tr + l_dram
    trans_conv = l_tlb + jnp.where(th != 0, zero, walk)
    trans_sparta = w_port + l_tlb + jnp.where(mh != 0, zero, w_tr + l_dram)
    trans = jnp.where(serial, trans_conv, jnp.where(memtlb, trans_sparta, pen))
    data_arr = jnp.where(serial, t0 + trans_conv + t_net,
                         jnp.where(memtlb, arr + trans_sparta, arr))
    pen_eff = jnp.where(serial | memtlb, zero, pen)

    # --- data DRAM access (all designs) -------------------------------------
    data_mask = bank_ix == bank_d
    w_data = jnp.where(banks > 0, jnp.maximum(
        _masked_get(bank_free, data_mask) - data_arr, zero), zero)
    bank_free = _masked_set(bank_free, data_mask & (~c_hit) & (banks > 0),
                            data_arr + w_data + dram_occ + pen_eff)

    lat_conv = l_cache + trans_conv + t_net + w_data + l_dram + t_net
    lat_sparta = l_cache + t_net + trans_sparta + w_data + l_dram + t_net
    lat_over = l_cache + t_net + w_data + l_dram + pen_eff + t_net
    lat_miss = jnp.where(serial, lat_conv,
                         jnp.where(memtlb, lat_sparta, lat_over))
    latency = jnp.where(c_hit, l_cache, lat_miss)
    overhead = jnp.where(c_hit, zero, trans)
    done = issue + latency

    # --- state updates -------------------------------------------------------
    mshr_ring = _masked_set(mshr_ring, mshr_mask & use_mshr, done)
    mshr_cnt = mshr_cnt + jnp.where(accel_mask & use_mshr, 1, 0)
    acc_next = _masked_set(acc_next, accel_mask, issue + issue_iv)
    return (acc_next, mshr_ring, mshr_cnt, port_free, bank_free), (
        latency, overhead, done)


@functools.partial(jax.jit, static_argnames=("envelope",))
def timeline_scan_batched_ref(
    accel: jnp.ndarray,      # int32 [B, N]
    part: jnp.ndarray,       # int32 [B, N]
    bank_data: jnp.ndarray,  # int32 [B, N]
    bank_pte: jnp.ndarray,   # int32 [B, N]
    cache_hit: jnp.ndarray,  # int32 [B, N]
    tlb_hit: jnp.ndarray,    # int32 [B, N]
    mem_hit: jnp.ndarray,    # int32 [B, N]
    pen: jnp.ndarray,        # f32   [B, N]
    fparams: jnp.ndarray,    # f32   [B, 8]  (FP_COLS)
    iparams: jnp.ndarray,    # int32 [B, 7]  (IP_COLS)
    envelope: Tuple[int, int, int, int, int],   # (A, M, P, T, D)
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """All B sims advanced per trace element in ONE ``lax.scan``; returns
    (latency, overhead, done), each f32 [B, N]."""
    B = accel.shape[0]
    state0 = timeline_init_state_batched(B, envelope, iparams[:, 5])
    vstep = jax.vmap(timeline_step_dyn, in_axes=(0, 0, 0, 0))

    def step(state, inp):
        return vstep(state, inp, fparams, iparams)

    xs = tuple(x.T for x in (accel, part, bank_data, bank_pte,
                             cache_hit, tlb_hit, mem_hit, pen))
    _, ys = jax.lax.scan(step, state0, xs)
    return tuple(y.T for y in ys)


@jax.jit
def timeline_scan_batched_carry_ref(
    accel: jnp.ndarray,      # int32 [B, L] one trace chunk
    part: jnp.ndarray,
    bank_data: jnp.ndarray,
    bank_pte: jnp.ndarray,
    cache_hit: jnp.ndarray,
    tlb_hit: jnp.ndarray,
    mem_hit: jnp.ndarray,
    pen: jnp.ndarray,        # f32 [B, L]
    fparams: jnp.ndarray,    # f32 [B, 8]
    iparams: jnp.ndarray,    # int32 [B, 7]
    state,                   # 5-tuple: carried queueing state (see
                             # timeline_init_state_batched for layout)
):
    """Chunk-resumable :func:`timeline_scan_batched_ref`: explicit carried
    state.  The queueing state holds *absolute* times, so unlike the LRU
    scans no global access counter is threaded — carrying the five state
    arrays across chunks is bit-identical to one monolithic pass.  Returns
    ``((latency, overhead, done), state')``.
    """
    vstep = jax.vmap(timeline_step_dyn, in_axes=(0, 0, 0, 0))

    def step(carry, inp):
        return vstep(carry, inp, fparams, iparams)

    xs = tuple(x.T for x in (accel, part, bank_data, bank_pte,
                             cache_hit, tlb_hit, mem_hit, pen))
    state, ys = jax.lax.scan(step, tuple(state), xs)
    return tuple(y.T for y in ys), state


@functools.partial(jax.jit, static_argnames=("params",))
def timeline_scan_ref(
    accel: jnp.ndarray,      # int32 [N] issuing accelerator id
    part: jnp.ndarray,       # int32 [N] memory-side TLB partition id
    bank_data: jnp.ndarray,  # int32 [N] DRAM bank of the data line
    bank_pte: jnp.ndarray,   # int32 [N] DRAM bank of the PTE
    cache_hit: jnp.ndarray,  # int32 [N] 1 = cache hit
    tlb_hit: jnp.ndarray,    # int32 [N] accel-TLB hit (conventional)
    mem_hit: jnp.ndarray,    # int32 [N] memory-side TLB hit (SPARTA)
    pen: jnp.ndarray,        # f32   [N] serialized penalty (DIPTA)
    params: TimelineParams,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sequential timeline simulation; returns (latency, overhead, done)."""

    def step(state, inp):
        return timeline_step(state, inp, params)

    _, ys = jax.lax.scan(
        step, timeline_init_state(params),
        (accel, part, bank_data, bank_pte, cache_hit, tlb_hit, mem_hit, pen),
    )
    return ys
