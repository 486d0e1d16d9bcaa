"""Compile the monitored serving path's Pallas counter kernel for a TPU.

The TPU compiler is installed even where no chip is attached, so these
tests lower the counter kernel with Mosaic (``interpret=False``) for a
described TPU v5e chip at the widths qwen1.5-0.5b serving streams through
it with the default ``MonitorConfig``. Interpret mode cannot see what
Mosaic refuses (block tiling, vector shapes, 16-bit compares); this file
can, at no chip time. Nothing runs, so results are checked elsewhere
(``tests/test_power_counter_kernels.py`` in interpret mode, and
``chip_smoke.py`` on the chip).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import bic, monitor
from repro.design.evaluate import menu_args
from repro.kernels.power_counters.kernel import fused_counters_pallas
from repro.kernels.power_counters.spec import CounterSpec

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
