"""Plain reference of the joint accelerator pipeline (paper §6.2-6.3, Fig. 3):
a virtual data cache, an accelerator-side TLB and the memory-side TLBs of
``P`` partitions, and the figure numbers reduced from their hit bits.

Per access: the cache is probed with the line address.  On a cache miss the
accelerator-side TLB (conventional designs only) and the memory-side TLB of
partition ``vpn % P`` are probed with the virtual page number; a structure
that is not probed keeps its state.  Reported bits: ``cache_hit``;
``accel_tlb_hit`` True on a cache hit, the probe's result on a miss, False
for designs without that TLB; ``mem_tlb_hit`` True on a cache hit, the
probe's result on a miss.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.reference.lru import lru_hits, set_keys

LINE_SHIFT = 6


def _geometry(s: dict):
    ways = min(int(s["ways"]), int(s["entries"]))
    return max(1, int(s["entries"]) // ways), ways


def system_hits(lines: np.ndarray, config: dict, *, tag_bits: int = 32):
    """(cache_hit, accel_tlb_hit, mem_tlb_hit), each bool [designs, N], for
    every design of ``config`` on one line trace."""
    lines = np.asarray(lines, np.int64)
    designs = config["designs"]
    c_sets, c_ways = _geometry(config["cache"])
    (cache,) = lru_hits([set_keys(lines, c_sets)], c_ways, tag_bits=tag_bits)
    miss = ~cache
    miss_ix = np.flatnonzero(miss)
    # Structures of equal geometry fed the same probes share one simulation.
    seen = set()
    streams: Dict[int, list] = {}
    for d in designs:
        vpn = lines[miss_ix] >> (int(d["page_shift"]) - LINE_SHIFT)
        for kind, parts in (("accel_tlb", 1), ("mem_tlb", int(d["partitions"]))):
            if kind == "accel_tlb" and d["design"] != "conventional":
                continue
            sets, ways = _geometry(config[kind])
            key = (sets, ways, parts, int(d["page_shift"]))
            if key not in seen:
                seen.add(key)
                streams.setdefault(ways, []).append(
                    (key, set_keys(vpn, sets, parts)))
    probed = {}
    for ways, group in streams.items():
        hits = lru_hits([k for _, k in group], ways, tag_bits=tag_bits)
        probed.update({key: h for (key, _), h in zip(group, hits)})
    n = lines.shape[0]
    out = np.zeros((3, len(designs), n), bool)
    for b, d in enumerate(designs):
        out[0, b] = cache
        mem = np.ones(n, bool)
        sets, ways = _geometry(config["mem_tlb"])
        mem[miss_ix] = probed[(sets, ways, int(d["partitions"]),
                               int(d["page_shift"]))]
        out[2, b] = mem
        if d["design"] == "conventional":
            sets, ways = _geometry(config["accel_tlb"])
            acc = np.ones(n, bool)
            acc[miss_ix] = probed[(sets, ways, 1, int(d["page_shift"]))]
            out[1, b] = acc
    return out[0], out[1], out[2]


# --- figure numbers (paper §6.3, Fig. 3 timelines, Fig. 10) -----------------

def t_net(lat: dict) -> float:
    """Mean one-way network latency: a share (1 - 1/sockets) of accesses
    crosses sockets."""
    return lat["l_noc"] + (1.0 - 1.0 / lat["n_sockets"]) * lat["l_offchip"]


def _warm(x: np.ndarray, warmup_frac: float) -> np.ndarray:
    return x[int(x.shape[0] * warmup_frac):]


def design_numbers(design: dict, c, a, m, lat: dict, *, instr_per_access: float,
                   way_accuracy: float, warmup_frac: float) -> dict:
    """Cycles per instruction and translation overhead cycles per access of
    one design from its post-warm-up hit bits."""
    c, a, m = (_warm(x, warmup_frac) for x in (c, a, m))
    hc = float(c.mean())
    miss = ~c
    tn = t_net(lat)
    data_path = 2.0 * tn + lat["l_dram"]
    fetch = lat["l_cache"] + (1.0 - hc) * data_path
    kind = design["design"]
    if kind == "conventional":
        ht = float(a[miss].mean()) if miss.any() else 1.0
        overhead = (1.0 - hc) * (lat["l_tlb"] + (1.0 - ht) * data_path)
    elif kind == "sparta":
        hm = float(m[miss].mean()) if miss.any() else 1.0
        overhead = (1.0 - hc) * (lat["l_tlb"] + (1.0 - hm) * lat["l_dram"])
    elif kind == "dipta":
        overhead = (1.0 - hc) * (1.0 - way_accuracy) * 2.0 * lat["l_dram"]
    elif kind == "ideal":
        overhead = 0.0
    else:
        raise ValueError(f"unknown design {kind!r}")
    cpi = 1.0 + (fetch + overhead) / instr_per_access
    return {"cpi": cpi, "overhead": overhead}


def figure_numbers(config: dict, hits, *, workload: str,
                   instr_per_access: float) -> List[float]:
    """Per design: speed-up over the baseline design and translation
    overhead cycles per access, in design order."""
    lat = config["latencies"]
    per = [design_numbers(d, hits[0][b], hits[1][b], hits[2][b], lat,
                          instr_per_access=instr_per_access,
                          way_accuracy=config["dipta_way_accuracy"].get(workload, 0.75),
                          warmup_frac=config["warmup_frac"])
           for b, d in enumerate(config["designs"])]
    base = per[[d["label"] for d in config["designs"]].index(config["baseline"])]
    return ([base["cpi"] / p["cpi"] for p in per]
            + [p["overhead"] for p in per])
