"""The benchmark's own trace generators and the one general generator that
turns a traffic file into the traces of each job.

The index-structure generators are a copy of the paper-workload models
(Table 2 of arXiv:2001.07045: hash table, internal and external BST, skip
list, RocksDB-like lookups) as the simulator repository had them when the
benchmark was defined.  They live here so that a change to the program's
own trace code cannot move the yardstick: the program under test receives
only the generated arrays of 64-byte cache-line addresses.

A traffic file (``bench/traffic/<name>.json``) lists jobs; each job lists
trace specs:

``workload``      one of :data:`WORKLOADS`
``n_ops``         operations (lookups) per thread
``threads``       1 for one stream; T > 1 for T range-partitioned threads
                  over the shared dataset, interleaved round-robin
``length``        the exact number of accesses the job receives: longer
                  traces are cut to it, and a shorter one is an error, so
                  every seed gives the same shapes
``footprint_gib`` dataset size (default 128, the paper's)
``zipf_keys``     optional Zipf exponent of hash-table keys (> 1)

Each trace's seed is drawn from ``(seed, job index, trace index)``, so one
``--seed`` fixes every input of a run and any whole number is accepted.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

LINE_SHIFT = 6  # 64-byte cache lines
LINES_PER_4K = 1 << (12 - LINE_SHIFT)
GIB = 1 << 30

WORKLOADS = ("hash_table", "bst_internal", "bst_external", "skip_list",
             "rocksdb")

# Instructions executed per memory access (paper §6.3): pointer chases run a
# handful of compare/branch instructions between loads.
INSTR_PER_ACCESS: Dict[str, float] = {
    "hash_table": 6.0,
    "bst_internal": 5.0,
    "bst_external": 5.0,
    "skip_list": 4.0,
    "rocksdb": 8.0,
}


def _splitmix64(x: np.ndarray) -> np.ndarray:
    x = (x + np.int64(-7046029254386353131)).astype(np.uint64)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def _scatter(ids: np.ndarray, space_lines: int, salt: int) -> np.ndarray:
    """Structured ids -> pseudo-random line addresses in [0, space)."""
    return (_splitmix64(ids.astype(np.int64) + np.int64(salt * 0x51_7C_C1))
            % np.uint64(space_lines)).astype(np.int64)


def _op_reuse(rng, rows, p: float = 0.3, window: int = 64) -> None:
    """With probability ``p`` an op repeats one of the last ``window`` ops
    (in place, on parallel [n_ops, ...] matrices)."""
    n = rows[0].shape[0]
    reuse = rng.random(n) < p
    back = rng.integers(1, window + 1, size=n)
    src = np.maximum(np.arange(n) - back, 0)
    idx = np.where(reuse, src, np.arange(n))
    for r in rows:
        r[reuse] = r[idx[reuse]]


def _tree_levels(total_nodes: int) -> int:
    return max(1, int(np.ceil(np.log2(total_nodes + 1))))


def _hash_table(rng, n_ops, footprint_lines, zipf_keys=0.0, tslice=(0.0, 1.0)):
    """Bucket array (25% of the footprint) + chained nodes (75%)."""
    bucket_lines = footprint_lines // 4
    heap_lines = footprint_lines - bucket_lines
    lo_b, hi_b = int(tslice[0] * bucket_lines), max(int(tslice[1] * bucket_lines), 1)
    if zipf_keys > 1.0:
        ranks = rng.zipf(zipf_keys, size=n_ops).astype(np.int64) - 1
        buckets = lo_b + _scatter(ranks.clip(max=bucket_lines - 1), hi_b - lo_b, salt=23)
        hot_nodes = True
    else:
        buckets = rng.integers(lo_b, hi_b, size=n_ops, dtype=np.int64)
        hot_nodes = False
    chain = 1 + rng.geometric(0.67, size=n_ops).astype(np.int64).clip(max=4) - 1
    max_chain = int(chain.max(initial=1))
    lo_h = int(tslice[0] * heap_lines)
    hi_h = max(int(tslice[1] * heap_lines), lo_h + 1)
    if hot_nodes:
        node_probe = lo_h + _scatter(
            (buckets[:, None] * 7 + np.arange(max_chain)[None, :]).ravel(),
            hi_h - lo_h, salt=29,
        ).reshape(n_ops, max_chain) + bucket_lines
    else:
        node_probe = rng.integers(lo_h, hi_h, size=(n_ops, max_chain),
                                  dtype=np.int64) + bucket_lines
    b2 = buckets[:, None]
    _op_reuse(rng, [b2, node_probe, chain[:, None]])
    chain = chain.copy()
    cols = np.arange(max_chain)[None, :]
    keep = cols < np.maximum(chain, 1)[:, None]
    seq = np.concatenate([b2, np.where(keep, node_probe, -1)], axis=1).ravel()
    return seq[seq >= 0]


def _bst(rng, n_ops, footprint_lines, *, external, tslice=(0.0, 1.0),
         scatter_nodes=False):
    """Level-ordered binary tree pointer chase: one node per level."""
    if external:
        internal_lines = footprint_lines // 4
        leaf_lines = footprint_lines - internal_lines
        n_internal = internal_lines
    else:
        n_internal = footprint_lines
        internal_lines = footprint_lines
        leaf_lines = 0
    depth = _tree_levels(n_internal)
    level_sizes = np.minimum(np.int64(1) << np.arange(depth, dtype=np.int64),
                             np.int64(n_internal))
    level_base = np.concatenate([[0], np.cumsum(level_sizes)[:-1]])
    level_base = np.minimum(level_base, internal_lines - 1)
    u = rng.random(size=(n_ops, depth))
    lo, hi = tslice
    wide = level_sizes >= 64
    base_f = np.where(wide, lo * level_sizes, 0.0)
    span_f = np.where(wide, (hi - lo) * level_sizes, level_sizes.astype(float))
    idx = (base_f[None, :] + u * span_f[None, :]).astype(np.int64)
    path = np.minimum(level_base[None, :] + idx, internal_lines - 1)
    if scatter_nodes:
        path = _scatter(path.ravel(), internal_lines, salt=41).reshape(path.shape)
    _op_reuse(rng, [path])
    if external:
        leaf_lo = int(lo * max(leaf_lines - 4, 1))
        leaf_hi = max(int(hi * max(leaf_lines - 4, 1)), leaf_lo + 1)
        leaf = internal_lines + rng.integers(leaf_lo, leaf_hi, size=(n_ops, 1),
                                             dtype=np.int64)
        path = np.concatenate([path, leaf, leaf + 1], axis=1)
    return path.ravel()


def _skip_list(rng, n_ops, footprint_lines, tslice=(0.0, 1.0)):
    """Skip-list tower traversal over allocation-order scattered nodes."""
    space = int(footprint_lines * 1.02)
    n_nodes = footprint_lines
    max_level = _tree_levels(n_nodes)
    levels = np.arange(max_level - 1, -1, -1, dtype=np.int64)
    nodes_at = np.maximum(n_nodes >> (max_level - 1 - np.arange(max_level)), 1)[::-1].copy()
    u = rng.random(size=(n_ops, max_level, 2))
    lo, hi = tslice
    counts = nodes_at[::-1].astype(float)
    wide = counts >= 64
    base_f = np.where(wide, lo * counts, 0.0)
    span_f = np.where(wide, (hi - lo) * counts, counts)
    ids = (base_f[None, :, None] + u * span_f[None, :, None]).astype(np.int64)
    _op_reuse(rng, [ids])
    return _scatter((ids * np.int64(64) + levels[None, :, None]).ravel(), space,
                    salt=11)


def _rocksdb(rng, n_ops, footprint_lines):
    """Zipf point lookups over SST blocks + memtable probes + range scans."""
    mem_lines = max(footprint_lines // 50, 1)
    idx_lines = max(footprint_lines // 50, 1)
    data_base = mem_lines + idx_lines
    data_lines = footprint_lines - data_base
    n_blocks = max(data_lines // LINES_PER_4K, 1)
    ranks = rng.zipf(1.2, size=n_ops).astype(np.int64)
    blocks = _scatter((ranks - 1).clip(max=n_blocks - 1), n_blocks, salt=3)
    mt = _scatter(rng.integers(0, 1 << 40, size=(n_ops, 4), dtype=np.int64).ravel(),
                  mem_lines, salt=5).reshape(n_ops, 4)
    ix = mem_lines + _scatter(blocks, idx_lines, salt=7)
    off = rng.integers(0, LINES_PER_4K - 1, size=n_ops, dtype=np.int64)
    d0 = data_base + blocks * LINES_PER_4K + off
    seq = np.stack([mt[:, 0], mt[:, 1], mt[:, 2], mt[:, 3], ix, d0, d0 + 1],
                   axis=1).ravel()
    n_scan = n_ops // 20
    scan_start = data_base + rng.integers(0, max(data_lines - 32, 1),
                                          size=n_scan, dtype=np.int64)
    scans = scan_start[:, None] + np.arange(32)[None, :]
    n_b, blen = scans.shape
    n = seq.shape[0]
    if n_b == 0:
        return seq
    ip = np.sort(rng.integers(0, n + 1, size=n_b))
    out = np.empty(n + n_b * blen, seq.dtype)
    shift = np.searchsorted(ip, np.arange(n), side="right")
    out[np.arange(n) + blen * shift] = seq
    out[(ip + blen * np.arange(n_b))[:, None] + np.arange(blen)] = scans
    return out


def single_stream(workload: str, *, n_ops: int, seed: int,
                  footprint_bytes: int = 128 * GIB, zipf_keys: float = 0.0,
                  thread_slice=(0.0, 1.0), scatter_nodes: bool = False):
    """Line addresses (int64) of one thread of ``workload``."""
    rng = np.random.default_rng(seed)
    f = footprint_bytes >> LINE_SHIFT
    if workload == "hash_table":
        lines = _hash_table(rng, n_ops, f, zipf_keys, thread_slice)
    elif workload in ("bst_internal", "bst_external"):
        lines = _bst(rng, n_ops, f, external=workload == "bst_external",
                     tslice=thread_slice, scatter_nodes=scatter_nodes)
    elif workload == "skip_list":
        lines = _skip_list(rng, n_ops, f, tslice=thread_slice)
    elif workload == "rocksdb":
        lines = _rocksdb(rng, n_ops, f)
    else:
        raise ValueError(f"unknown workload {workload!r}; options: {WORKLOADS}")
    return lines.astype(np.int64)


def interleave(streams: Sequence[np.ndarray]) -> np.ndarray:
    """Round-robin interleave, truncated to the shortest stream."""
    n = min(s.shape[0] for s in streams)
    return np.stack([s[:n] for s in streams], axis=1).reshape(-1)


def thread_streams(workload: str, threads: int, *, n_ops: int, seed: int,
                   footprint_bytes: int = 128 * GIB) -> List[np.ndarray]:
    """``threads`` range-partitioned threads over one shared dataset: thread
    ``t`` walks its own slice of the structure below the shared top levels
    and has its own seed ``seed + 997 t``."""
    out = []
    for t in range(threads):
        tslice = (t / threads, (t + 1) / threads) if threads > 1 else (0.0, 1.0)
        out.append(single_stream(workload, n_ops=n_ops, seed=seed + 997 * t,
                                 footprint_bytes=footprint_bytes,
                                 thread_slice=tslice, scatter_nodes=True))
    return out


def trace_seed(seed: int, job: int, trace: int) -> int:
    """The seed of one trace of a run, drawn from the run's ``--seed``."""
    return int(np.random.SeedSequence([int(seed) % 2**64, job, trace])
               .generate_state(1, np.uint32)[0])


def make_trace(spec: dict, seed: int) -> np.ndarray:
    """The line addresses of one trace spec (see the module docstring)."""
    footprint = int(spec.get("footprint_gib", 128)) * GIB
    threads = int(spec.get("threads", 1))
    if threads > 1:
        lines = interleave(thread_streams(spec["workload"], threads,
                                          n_ops=int(spec["n_ops"]), seed=seed,
                                          footprint_bytes=footprint))
    else:
        lines = single_stream(spec["workload"], n_ops=int(spec["n_ops"]),
                              seed=seed, footprint_bytes=footprint,
                              zipf_keys=float(spec.get("zipf_keys", 0.0)))
    length = int(spec["length"])
    if lines.shape[0] < length:
        raise ValueError(
            f"{spec['workload']} trace of seed {seed} has {lines.shape[0]} "
            f"accesses, fewer than the traffic's fixed length {length}")
    return lines[:length]


def job_traces(traffic: dict, seed: int) -> List[List[dict]]:
    """Every job of a traffic file: a list of ``{"workload", "lines",
    "instr_per_access"}`` per job."""
    jobs = []
    for j, job in enumerate(traffic["jobs"]):
        jobs.append([{"workload": sp["workload"],
                      "lines": make_trace(sp, trace_seed(seed, j, k)),
                      "instr_per_access": INSTR_PER_ACCESS[sp["workload"]]}
                     for k, sp in enumerate(job["traces"])])
    return jobs
