"""Serving launcher: the SPARTA paged engine on a decoder-only config.

  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-12b \
      --requests 8 --max-new 16
  PYTHONPATH=src python -m repro.launch.serve --arch internvl2-2b --full \
      --prompt-len 128,512 --max-new 32

Without ``--full`` the arch's smoke config runs in float32 with 8-token KV
pages (a CPU-sized demo).  ``--full`` serves the published widths in the
config's own dtype and page size.  The weights are random; the KV pool holds
exactly the pages the submitted requests can reach.
"""
import argparse
import time

import jax
import numpy as np

from repro import models
from repro.configs import registry
from repro.configs.base import ModelConfig
from repro.serve.engine import SpartaEngine


def engine_config(arch: str, *, full: bool = False) -> ModelConfig:
    """The published config (``full``) or the arch's smoke config in float32
    with 8-token pages.  Only decoder-only families can be served."""
    if full:
        cfg = registry.get_config(arch)
    else:
        base = registry.get_smoke(arch).__dict__.copy()
        base.update(dtype="float32", kv_page_size=8)
        cfg = ModelConfig(**base)
    if cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(
            f"the engine serves decoder-only archs, not {cfg.family}")
    return cfg


def slots_per_partition(cfg: ModelConfig, *, requests: int, max_tokens: int,
                        partitions: int) -> int:
    """KV page slots each partition needs to hold ``requests`` sequences of
    up to ``max_tokens`` tokens: logical page l lives on partition
    l % partitions, and finished requests keep their pages."""
    pages = -(-max_tokens // cfg.kv_page_size)
    return requests * -(-pages // partitions)


def build_engine(cfg: ModelConfig, *, seed: int = 0, kernel_mode: str = "auto",
                 partitions: int = 4, slots_per_partition: int = 128,
                 max_batch: int = 4) -> SpartaEngine:
    """A :class:`SpartaEngine` over random weights made from ``seed``."""
    params = models.init(jax.random.PRNGKey(seed), cfg)
    return SpartaEngine(cfg, params, num_partitions=partitions,
                        slots_per_partition=slots_per_partition,
                        max_batch=max_batch, kernel_mode=kernel_mode)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-12b")
    ap.add_argument("--full", action="store_true",
                    help="published widths and dtype instead of the smoke config")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", default="4,16", metavar="LO,HI",
                    help="prompt lengths are drawn from [LO, HI)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--partitions", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=4)
    args = ap.parse_args()

    cfg = engine_config(args.arch, full=args.full)
    lo, hi = (int(x) for x in args.prompt_len.split(","))
    eng = build_engine(
        cfg, partitions=args.partitions, max_batch=args.max_batch,
        slots_per_partition=slots_per_partition(
            cfg, requests=args.requests, max_tokens=hi + args.max_new,
            partitions=args.partitions))
    rng = np.random.default_rng(0)
    for _ in range(args.requests):
        eng.submit(list(rng.integers(0, cfg.vocab, rng.integers(lo, hi))),
                   max_new_tokens=args.max_new)
    t0 = time.time()
    eng.run_to_completion()
    dt = time.time() - t0
    toks = sum(len(r.generated) for r in eng.finished.values())
    print(f"{len(eng.finished)} requests, {toks} tokens, {dt:.1f}s wall "
          f"on {jax.devices()[0].device_kind}, compilation included")
    eng.kv.check_invariants()


if __name__ == "__main__":
    main()
