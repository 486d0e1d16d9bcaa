"""Compile the monitored serving path's Pallas counter kernel for a TPU.

The TPU compiler is installed even where no chip is attached, so these
tests lower the counter kernel with Mosaic (``interpret=False``) for a
described TPU v5e chip at the widths qwen1.5-0.5b serving streams through
it with the default ``MonitorConfig``. Interpret mode cannot see what
Mosaic refuses (block tiling, vector shapes, 16-bit compares); this file
can, at no chip time. Nothing runs, so results are checked elsewhere
(``tests/test_power_counter_kernels.py`` in interpret mode, and
``chip_smoke.py`` on the chip).

The decode steps of qwen1.5-0.5b and of DeepSeek-V2-Lite's benchmark
share are compiled here too, the way the serving engine jits them, to
check the layout of their caches (full-attention K/V, the latent rows)
in the optimized program: what the TPU compiler does with a cache
layout shows only in a compile for a TPU.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.deepseek_v2_lite import CONFIG as DSV2
from repro.configs.qwen1_5_0_5b import CONFIG as QWEN
from repro.core import bic, monitor
from repro.design.evaluate import menu_args
from repro.kernels.power_counters.kernel import fused_counters_pallas
from repro.kernels.power_counters.spec import CounterSpec
from repro.models import lm
from repro.models import matmul as mm

#: qwen1.5-0.5b's d_model (the K of both monitored layer-0 weights) and
#: the decode batch chip_smoke.py serves
D_MODEL = 1024
DECODE_BATCH = 8


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 topology, with JAX's persistent compilation
    cache off while it is in use: a compile for a described chip is
    written to the cache but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _serving_specs():
    """West and north CounterSpecs of the default monitor's menu, the
    ones ``repro.serve.power`` streams."""
    ((_, _), kw), = menu_args(monitor.DEFAULT_MONITOR.design_list).items()
    return (CounterSpec(bic_variants=kw["west_bic"], zvg=kw["west_zvg"]),
            CounterSpec(bic_variants=kw["north_bic"], zvg=kw["north_zvg"]))


def _cases():
    """``(shape, spec, vmapped)`` per stream, shaped as serving passes
    them: the north stream is the weight subsampled to ``max_depth`` x
    ``max_cols``; each decode row streams through an R-lane west edge;
    a prefill streams up to ``max_rows`` prompt rows."""
    mcfg = monitor.DEFAULT_MONITOR
    geom = mcfg.design_list[0].geometry
    west, north = _serving_specs()
    depth = min(D_MODEL, mcfg.max_depth)
    full = CounterSpec(bic_variants=tuple(bic.NAMED_SEGMENTS.values()),
                       zvg=True, hist=True)
    return {
        "north": ((depth, mcfg.max_cols), north, False),
        "west-decode-vmap": ((DECODE_BATCH, depth, geom.rows), west, True),
        "west-prefill": ((depth, mcfg.max_rows), west, False),
        "hist-full-menu": ((depth, mcfg.max_cols), full, False),
    }


@pytest.mark.parametrize("case", sorted(_cases()))
def test_counter_kernel_compiles_for_v5e(case, one_chip):
    shape, spec, vmapped = _cases()[case]

    def fn(x):
        run = lambda s: fused_counters_pallas(s, spec, interpret=False)
        return jax.vmap(run)(x) if vmapped else run(x)

    x = jax.ShapeDtypeStruct(shape, jnp.uint16, sharding=one_chip)
    compiled = jax.jit(fn).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


#: the serving benchmark's slot cache: 32 slots of 2048 positions
SLOTS, HORIZON = 32, 2048

#: an HLO instruction's result shape and opcode, fused or not
_INSTR = re.compile(r"= \w+\[([\d,]*)\]\{[^}]*\} ([\w-]+)\(")


def _big_moves(hlo: str, min_elems: int) -> list[str]:
    """Every copy and dynamic-update-slice of the program, top level or
    inside a fusion, whose result holds at least ``min_elems`` elements."""
    out = []
    for line in hlo.splitlines():
        m = _INSTR.search(line)
        if m is None or m.group(2) not in (
                "copy", "copy-start", "dynamic-update-slice"):
            continue
        n = 1
        for dim in filter(None, m.group(1).split(",")):
            n *= int(dim)
        if n >= min_elems:
            out.append(line.strip()[:160])
    return out


def _decode_hlo(cfg, one_chip, slots: int, horizon: int) -> str:
    """The optimized program of ``cfg``'s decode step, jitted as the
    serving engine jits it (the ``ref`` backend bound by
    ``functools.partial``, the cache donated), compiled for one v5e."""
    def spec(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = spec(jax.eval_shape(
        lambda: lm.init_model(jax.random.key(0), cfg)))
    states = spec(jax.eval_shape(
        lambda: lm.make_decode_state(cfg, slots, horizon)))
    col = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    step = jax.jit(functools.partial(mm.with_backend, "ref",
                                     lm.make_decode_step(cfg)),
                   donate_argnums=(1,))
    return step.lower(params, states,
                      {"tokens": col, "positions": col}).compile().as_text()


def test_decode_step_keeps_kv_cache_in_place(one_chip):
    """qwen1.5-0.5b's decode step at full width (two layers) neither
    copies a layer's K or V cache nor writes a whole layer slice back
    into the stack: the step's rows are scattered into the stacked cache
    in place and the attention reads the layer's slice inside its
    products."""
    cfg = QWEN.with_(n_layers=2)
    hlo = _decode_hlo(cfg, one_chip, SLOTS, HORIZON)
    layer = SLOTS * HORIZON * cfg.n_kv_heads * cfg.hd
    assert _big_moves(hlo, layer) == []


#: the DeepSeek-V2-Lite benchmark configuration's slot cache and share:
#: 64 slots of 4096 positions, 8 of the 64 experts, 1/8 of the vocabulary
DS_SLOTS, DS_HORIZON = 64, 4096


def test_decode_step_keeps_latent_cache_in_place(one_chip):
    """DeepSeek-V2-Lite's decode step at full width (the dense layer 0
    and two scanned expert layers, 8 held experts) moves no whole layer
    of either latent-cache leaf: neither the 512-wide latent nor the
    64-wide rope key, the half-lane-tile shape that cost full attention
    its relayout copies, is copied or written back per layer."""
    cfg = DSV2.with_(n_layers=3, vocab=12800,
                     moe=dataclasses.replace(DSV2.moe, held=8))
    hlo = _decode_hlo(cfg, one_chip, DS_SLOTS, DS_HORIZON)
    rope_layer = DS_SLOTS * DS_HORIZON * cfg.mla.qk_rope_head_dim
    assert _big_moves(hlo, rope_layer) == []
