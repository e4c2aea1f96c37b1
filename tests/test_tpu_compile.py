"""Every Pallas kernel of the main path compiles for a TPU v5e at real size.

Nothing runs: each test lowers one kernel for a *described* v5e chip (the
TPU compiler ships with jaxlib's TPU plugin) and asserts that Mosaic emitted
a ``tpu_custom_call``.  Interpret mode accepts things Mosaic refuses
(unaligned blocks, scalar VMEM stores, integer arg-reductions, SMEM and VMEM
over budget), so these compiles are what keep the kernels chip-ready.

Shapes are the ones the figure sweeps and the served model use: the
8-config TLB sweep and the 9-config fig10 system sweep through their
streams' carried state, the fig11 40-sim timeline matrix, the stack-distance
scan of a full fig4 sweep, and decode/prefill attention at internvl2-2b
widths.  The topology is described inside a fixture, never at import time:
only one process may load the TPU library, and the test workers all import
this module.
"""
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

# One orchestrator chunk of trace accesses (SweepRunConfig.chunk_accesses).
CHUNK = 65_536


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler plugin in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(one_chip, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _state_specs(one_chip, stream):
    state = stream.export_state()
    return [_spec(one_chip, state[k].shape, state[k].dtype)
            for k in state if k != "now"]


# Compiles the fig10 system kernel, with the stream's share map, for a
# described v5e with Mosaic's dumps on; exits 77 where no v5e can be
# described.
_FIG10_DUMP = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from benchmarks import fig10_performance as fig10
    from repro.core.sweep import SystemSweepStream
    from repro.kernels.system_sim.kernel import system_sim_batched_pallas_carry

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception:
        raise SystemExit(77)
    jax.config.update("jax_enable_compilation_cache", False)
    one_chip = SingleDeviceSharding(topo.devices[0])
    spec = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(
        tuple(shape), dtype, sharding=one_chip)
    cfgs = fig10.configs()
    stream = SystemSweepStream(cfgs)
    state = stream.export_state()
    keys = spec((len(cfgs), %d))
    jax.jit(lambda *a: system_sim_batched_pallas_carry(
        *a[:7], tuple(a[7:13]), a[13], a[14], share=stream._shares[0],
        block=stream.block)).lower(
        *[keys] * 6, spec((len(cfgs), 3)),
        *[spec(state[k].shape, state[k].dtype) for k in state if k != "now"],
        spec(()), spec(())).compile()
""" % CHUNK)


@pytest.fixture(scope="module")
def fig10_llo(tmp_path_factory):
    """The final LLO of the fig10 system kernel, compiled for a v5e.

    The dump flag is read when the TPU library loads, so the compile runs in
    a child process, and the tests that use this come first in the file:
    before the ``one_chip`` fixture has loaded the library into this one.
    Where the child cannot load it or describe a v5e, they skip."""
    out = tmp_path_factory.mktemp("fig10_dump")
    root = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
               PYTHONPATH=os.pathsep.join([str(root / "src"), str(root)]))
    env["LIBTPU_INIT_ARGS"] = (env.get("LIBTPU_INIT_ARGS", "")
                               + f" --xla_mosaic_dump_to={out}").strip()
    run = subprocess.run([sys.executable, "-c", _FIG10_DUMP], cwd=root,
                         env=env, capture_output=True, text=True, timeout=300)
    llo = sorted(out.glob("*system_sim_carry-post-finalize-llo.txt"))
    if run.returncode != 0 or not llo:
        pytest.skip(f"no Mosaic dump of the fig10 kernel (exit "
                    f"{run.returncode}): {run.stderr[-500:]}")
    return llo[-1].read_text()


def test_system_sim_probe_keeps_to_the_vector_unit(fig10_llo):
    """The fig10 system kernel's compiled access loop moves no value from
    the vector unit to the scalar unit: its final LLO has no ``llo.vtos``.
    (A probe that reduced the way choice to scalars had nine per design.)"""
    assert "llo.vmin.xlane" in fig10_llo   # the probe's way choice is here
    assert fig10_llo.count("llo.vtos") == 0


def test_system_sim_probes_each_distinct_structure_once(fig10_llo):
    """The fig10 batch's nine designs hold 27 structures, of which 10 are
    distinct: one cache, two accelerator TLBs and seven memory TLBs.  The
    compiled access loop probes each of the 10 once, in straight-line code:
    three cross-lane ``min`` reductions per probe (hit way, LRU stamp, LRU
    way), 30 in all, where a loop over the designs had 9 in its body and
    ran them 27 times."""
    assert fig10_llo.count("llo.vmin.xlane") == 3 * 10


def test_tlb_sim_carry_compiles_for_the_8_config_sweep(one_chip):
    from repro.core.sparta import TLBConfig
    from repro.core.sweep import TLBSweepSpec, TLBSweepStream
    from repro.kernels.tlb_sim.kernel import tlb_sim_batched_pallas_carry

    specs = [TLBSweepSpec(TLBConfig(entries=e, ways=4), num_partitions=p,
                          page_shift=12)
             for p in (1, 128) for e in (64, 128, 256, 512)]
    stream = TLBSweepStream(specs)
    assert len(stream.groups) == 1
    tags, last = _state_specs(one_chip, stream)
    keys = _spec(one_chip, (len(specs), CHUNK))
    _assert_kernel(
        lambda s, t, a, b, n: tlb_sim_batched_pallas_carry(
            s, t, a, b, n, block=stream.block),
        keys, keys, tags, last, _spec(one_chip, ()))


def test_system_sim_carry_compiles_for_the_fig10_sweep(one_chip):
    from benchmarks import fig10_performance as fig10
    from repro.core.sweep import SystemSweepStream
    from repro.core.tlbsim import SystemSimConfig
    from repro.kernels.system_sim.kernel import system_sim_batched_pallas_carry

    cfgs = fig10.configs()
    stream = SystemSweepStream(cfgs)
    assert len(stream.groups) == 1 and len(cfgs) == 9
    state = _state_specs(one_chip, stream)
    keys = _spec(one_chip, (len(cfgs), CHUNK))
    _assert_kernel(
        lambda *a: system_sim_batched_pallas_carry(
            *a[:7], tuple(a[7:13]), a[13], a[14], share=stream._shares[0],
            block=stream.block),
        *[keys] * 6, _spec(one_chip, (len(cfgs), 3)), *state,
        _spec(one_chip, ()), _spec(one_chip, ()))


def test_system_device_keys_compile_for_the_fig10_chunk(one_chip):
    """The fig10 stream's key streams, derived on the chip from one uint32
    chunk of lines, compile at the chunk's length."""
    from benchmarks import fig10_performance as fig10
    from repro.core import sweep

    stream = sweep.SystemSweepStream(fig10.configs())
    rules = sweep._rows(sweep._system_key_rules(stream.cfgs), stream.groups[0])
    text = jax.jit(lambda lines, n: sweep._device_system_keys(
        lines, n, rules=rules, parked=stream._parked[0])
    ).lower(_spec(one_chip, (CHUNK,), jnp.uint32),
            _spec(one_chip, ())).compile().as_text()
    assert "u32[%d]" % CHUNK in text


def test_timeline_carry_compiles_for_the_fig11_matrix(one_chip):
    from benchmarks import fig11_tail_latency as fig11
    from repro.kernels.timeline.kernel import timeline_sim_batched_pallas_carry
    from repro.kernels.timeline.ref import timeline_init_state_batched

    q = fig11.QUEUES
    sims = len(fig11.W4) * 5 * 2   # workloads x accel counts x designs
    envelope = (16, q.mshrs, fig11.PARTITIONS, q.tlb_ports, q.dram_banks)
    state = jax.eval_shape(
        lambda: timeline_init_state_batched(
            sims, envelope, jnp.ones((sims,), jnp.int32)))
    ints = _spec(one_chip, (sims, CHUNK))
    _assert_kernel(
        lambda *a: timeline_sim_batched_pallas_carry(*a[:10], tuple(a[10:])),
        *[ints] * 7, _spec(one_chip, (sims, CHUNK), jnp.float32),
        _spec(one_chip, (sims, 8), jnp.float32), _spec(one_chip, (sims, 7)),
        *[_spec(one_chip, s.shape, s.dtype) for s in state])


@pytest.mark.parametrize("lanes", [32 * 1024, 1000])
def test_stack_scan_compiles_for_the_fig4_depth_pass(one_chip, lanes):
    """32 mappings x 1024 lanes of 1024 accesses (a full fig4 chunk), and a
    ragged lane count that is no multiple of 128."""
    from repro.kernels.stackdist.kernel import stack_scan_pallas

    _assert_kernel(stack_scan_pallas, _spec(one_chip, (lanes, 1024)),
                   _spec(one_chip, (lanes, 1024), jnp.bool_),
                   _spec(one_chip, (lanes, 4)))


def test_paged_attention_compiles_at_internvl2_widths(one_chip):
    from repro.configs import registry
    from repro.kernels.paged_attention.kernel import paged_attention_pallas

    cfg = registry.get_config("internvl2-2b")
    batch, slots, pages = 4, 32, 3
    pool = _spec(one_chip, (slots, cfg.kv_page_size, cfg.num_kv_heads,
                            cfg.head_dim), jnp.float32)
    _assert_kernel(
        paged_attention_pallas,
        _spec(one_chip, (batch, cfg.num_heads, cfg.head_dim), jnp.bfloat16),
        pool, pool, _spec(one_chip, (batch, pages)), _spec(one_chip, (batch,)))


@pytest.mark.parametrize("tokens", [512, 333])
def test_flash_attention_compiles_at_internvl2_widths(one_chip, tokens):
    """Prefill attention; 333 tokens is no multiple of the 128-row block."""
    from repro.configs import registry
    from repro.kernels.flash_attention.kernel import flash_attention_pallas

    cfg = registry.get_config("internvl2-2b")
    q = _spec(one_chip, (1, cfg.num_heads, tokens, cfg.head_dim), jnp.bfloat16)
    kv = _spec(one_chip, (1, cfg.num_kv_heads, tokens, cfg.head_dim),
               jnp.bfloat16)
    _assert_kernel(flash_attention_pallas, q, kv, kv)



def _lru_case(one_chip, name):
    """(function, argument specs) of one LRU kernel at a small size: 2
    configurations, 16 sets (+1 parked row) x 4 ways, 512 accesses."""
    from repro.kernels.system_sim import kernel as system_sim
    from repro.kernels.tlb_sim import kernel as tlb_sim

    keys, state = _spec(one_chip, (2, 512)), _spec(one_chip, (2, 17, 4))
    now, flags = _spec(one_chip, ()), _spec(one_chip, (2, 3))
    geom, valid = (17, 4) * 3, ((4, 4),) * 3
    share = ((0, 1),) * 3
    return {
        "tlb_sim": (lambda s, t: tlb_sim.tlb_sim_pallas(s, t, 16, 4, block=128),
                    [_spec(one_chip, (512,))] * 2),
        "tlb_sim_batched": (
            lambda s, t: tlb_sim.tlb_sim_batched_pallas(s, t, 17, 4, (4, 4),
                                                        block=128),
            [keys] * 2),
        "tlb_sim_carry": (
            lambda s, t, a, b, n: tlb_sim.tlb_sim_batched_pallas_carry(
                s, t, a, b, n, block=128),
            [keys, keys, state, state, now]),
        "system_sim_batched": (
            lambda *a: system_sim.system_sim_batched_pallas(
                *a, geom, valid, share=share, block=128),
            [keys] * 6 + [flags]),
        "system_sim_carry": (
            lambda *a: system_sim.system_sim_batched_pallas_carry(
                *a[:7], tuple(a[7:13]), a[13], a[14], share=share, block=128),
            [keys] * 6 + [flags] + [state] * 6 + [now, now]),
    }[name]


def _timeline_case(one_chip, name):
    """(function, argument specs) of one timeline kernel: 2 sims, 512
    accesses, a small resource envelope."""
    from repro.kernels.timeline.kernel import (
        timeline_sim_batched_pallas, timeline_sim_batched_pallas_carry)
    from repro.kernels.timeline.ref import timeline_init_state_batched

    envelope = (2, 4, 8, 1, 4)
    args = ([_spec(one_chip, (2, 512))] * 7
            + [_spec(one_chip, (2, 512), jnp.float32),
               _spec(one_chip, (2, 8), jnp.float32), _spec(one_chip, (2, 7))])
    if name == "timeline_batched":
        return (lambda *a: timeline_sim_batched_pallas(*a, envelope, block=128),
                args)
    state = jax.eval_shape(lambda: timeline_init_state_batched(
        2, envelope, jnp.ones((2,), jnp.int32)))
    return (lambda *a: timeline_sim_batched_pallas_carry(
                *a[:10], tuple(a[10:]), block=128),
            args + [_spec(one_chip, s.shape, s.dtype) for s in state])


@pytest.mark.parametrize("name", [
    "tlb_sim", "tlb_sim_batched", "tlb_sim_carry", "system_sim_batched",
    "system_sim_carry", "timeline_batched", "timeline_carry"])
def test_pallas_calls_carry_stable_names(one_chip, name):
    """Each simulator kernel lowers for the chip under its own ``name=``,
    which names its device operation in a profiler trace."""
    case = _timeline_case if name.startswith("timeline") else _lru_case
    fn, args = case(one_chip, name)
    text = jax.jit(fn).lower(*args).as_text()
    assert f'kernel_name = "{name}"' in text
