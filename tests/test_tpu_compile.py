"""Compile every Pallas kernel of the main path for a TPU v5e, without one.

The TPU compiler is installed with jax; it compiles for a *described*
``v5e:2x2`` topology, so Mosaic's lowering rules (tiling, layouts, VMEM
limits) are checked here at the widths of the published-size tasks —
rules that interpret mode never applies.  Each case passes
``interpret=False`` explicitly: the kernels' default picks interpret mode
from ``jax.default_backend()``, which is the CPU here, and an interpreted
kernel contains no ``tpu_custom_call``.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ddmm import ddmm
from repro.kernels.knn import knn
from repro.kernels.sddmm import sddmm
from repro.kernels.shift_conv import shift_conv2d
from repro.kernels.spdmm import spdmm


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables are written to the persistent cache
    # but can never be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (name, kernel with interpret=False, [(shape, dtype), ...])
F32, I32 = jnp.float32, jnp.int32
CASES = [
    # b3-r50 ResNet-50 widest GEMMs; b4 1x1 theta conv; an odd shape
    ("ddmm_r50", functools.partial(ddmm, interpret=False),
     [((12544, 512), F32), ((512, 2048), F32)]),
    ("ddmm_odd", functools.partial(ddmm, interpret=False),
     [((150, 75), F32), ((75, 64), F32)]),
    # b4 spatial message passing: ELL (25, 5) over (V=25, C*T=9600)
    ("spdmm_b4", functools.partial(spdmm, interpret=False),
     [((25, 5), I32), ((25, 5), F32), ((25, 9600), F32)]),
    # graph adjacencies: an ELL slot loop unrolled (L=11) and rolled (L=40)
    ("spdmm_wide", functools.partial(spdmm, interpret=False),
     [((1024, 11), I32), ((1024, 11), F32), ((1024, 256), F32)]),
    ("spdmm_deep", functools.partial(spdmm, interpret=False),
     [((1024, 40), I32), ((1024, 40), F32), ((1024, 256), F32)]),
    # b4 at serving batch 8: the kernel under vmap (weights unbatched)
    ("spdmm_b4_batched",
     jax.vmap(functools.partial(spdmm, interpret=False),
              in_axes=(None, None, 0)),
     [((25, 5), I32), ((25, 5), F32), ((8, 25, 9600), F32)]),
    # b3-r50 channel affinity (512 nodes x 196 features) and b4-size mask
    ("sddmm_r50", functools.partial(sddmm, interpret=False),
     [((512, 784), F32), ((784, 512), F32), ((512, 512), F32)]),
    ("sddmm_25", functools.partial(sddmm, interpret=False),
     [((25, 64), F32), ((64, 25), F32), ((25, 25), F32)]),
    # b6-dyn point cloud (1024 x 3, k=20) and ViG-size patches (k=9)
    ("knn_b6", functools.partial(knn, k=20, interpret=False),
     [((1024, 3), F32)]),
    ("knn_vig", functools.partial(knn, k=9, interpret=False),
     [((3136, 192), F32)]),
    # ResNet-50 stage-1 3x3 conv and the 7x7 stride-2 stem
    ("shift_conv_r50_3x3", functools.partial(shift_conv2d, interpret=False),
     [((64, 56, 56), F32), ((3, 3, 64, 64), F32)]),
    ("shift_conv_r50_stem",
     functools.partial(shift_conv2d, stride=2, interpret=False),
     [((3, 224, 224), F32), ((7, 7, 3, 64), F32)]),
]


@pytest.mark.parametrize("name,fn,args", CASES, ids=[c[0] for c in CASES])
def test_kernel_compiles_for_v5e(one_chip, name, fn, args):
    specs = [_spec(s, one_chip, d) for s, d in args]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name}: no Mosaic kernel in the compiled program"
