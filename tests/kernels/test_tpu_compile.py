"""The device programs of the main path compile for a TPU v5e chip.

Nothing runs: each program is lowered and compiled for a v5e chip that
is described, not attached, so what the chip's compiler refuses (an
unaligned block, too much VMEM, an unsupported dot precision) fails
here instead of on the chip.  Every compile happens inside a test: the
topology is described by a module fixture, never at import, and the
persistent compilation cache is off while these compiles run (their
entries could not be read back without a chip).
"""
from __future__ import annotations

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.api.batched import _grid_fn, _sweep_fn
from repro.core.reuse.batched import _BLOCK, _multi_scan_fn
from repro.core.reuse.distance import _window_scan_fn
from repro.kernels.reuse_hist.reuse_hist import reuse_hist_moments_pallas_2d
from repro.kernels.sdcm.sdcm import sdcm_pallas_2d


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        was_enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                desc = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2"
                )
            except Exception as exc:  # noqa: BLE001 — any failure skips
                pytest.skip(f"no v5e:2x2 topology can be described: {exc}")
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was_enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_reuse_hist_moments_kernel_compiles(spec):
    compiled = _compile(
        lambda d, w: reuse_hist_moments_pallas_2d(d, w, interpret=False),
        spec((8192, 128)), spec((8192, 128)),
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("assoc,blocks", [(8, 512), (20, 327680)])
def test_sdcm_kernel_compiles(spec, assoc, blocks):
    compiled = _compile(
        lambda d: sdcm_pallas_2d(d, assoc, blocks, interpret=False),
        spec((8192, 128)),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_batched_grid_compiles(spec):
    g, m = 64, 4096
    _grid_fn(64).lower(
        spec((g, m)), spec((g, m)), spec((g,)), spec((g,))
    ).compile()


def test_config_sweep_compiles(spec):
    c, levels, m = 1024, 3, 256
    _sweep_fn((8, 8, 32), 2, "throughput", True).lower(
        spec((m,)), spec((m,)), spec((m,)), spec((m,)),
        spec((c, 4 * levels + 6)),
    ).compile()


def test_per_set_fenwick_scan_compiles(spec):
    rows, cap, window = 64, 1 << 16, 512
    _multi_scan_fn(cap, _BLOCK).lower(
        spec((rows, cap), jnp.int32), spec((rows, cap), jnp.int32),
        spec((rows, window), jnp.int32), spec((rows, window), jnp.bool_),
        spec((rows,), jnp.int32),
    ).compile()


def test_streaming_window_scan_compiles(spec):
    cap, ids, window = 1 << 16, 1 << 15, 1 << 14
    _window_scan_fn(cap).lower(
        spec((cap,), jnp.int32), spec((ids,), jnp.int32),
        spec((window,), jnp.int32), spec((), jnp.int32),
    ).compile()
