"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret mode (every other kernel test) accepts unaligned slices, dynamic
lane indexing and VMEM overuse that the TPU compiler refuses; these tests
run the real compiler on a chip that is described, not attached, at the
widths production uses: a 2000-row cell padded to 2048, d in {54, 90, 768}
padded to the 128-lane width, P in {1, 7, 21} decision columns.

The topology is described inside a module fixture (never at import: only
one process may hold the TPU library, and every test worker imports this
file), and JAX's persistent compilation cache is off around the compiles,
since a compile for a described chip cannot be read back without one.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.kernel_matrix.kernel_matrix import (gram_from_d2_pallas,
                                                       sq_dists_pallas)
from repro.kernels.svm_predict.svm_predict import svm_predict_cells_pallas
from repro.pipeline.assign import _assign_pallas_padded

N = 2048                      # cell_size 2000 padded to the 128 tile
D_PAD = {54: 128, 90: 128, 768: 768}
SLOTS = 4                     # a vmapped wave of cells


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    """Lower + compile ``fn`` for the described chip; returns its HLO text."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "kernel lowered without Mosaic"
    return text


F32 = jnp.float32


@pytest.mark.parametrize("d", sorted(D_PAD))
def test_sq_dists_symmetric(one_chip, d):
    dp = D_PAD[d]
    _compile(lambda x: sq_dists_pallas(x, x, symmetric=True, interpret=False),
             one_chip, ((N, dp), F32))


@pytest.mark.parametrize("d", sorted(D_PAD))
def test_sq_dists_symmetric_vmapped(one_chip, d):
    dp = D_PAD[d]
    fn = jax.vmap(lambda x: sq_dists_pallas(x, x, symmetric=True,
                                            interpret=False))
    _compile(fn, one_chip, ((SLOTS, N, dp), F32))


@pytest.mark.parametrize("d", sorted(D_PAD))
def test_sq_dists_cross(one_chip, d):
    dp = D_PAD[d]
    _compile(lambda x, z: sq_dists_pallas(x, z, interpret=False),
             one_chip, ((256, dp), F32), ((N, dp), F32))


@pytest.mark.parametrize("out_dtype", ["f32", "bf16"])
@pytest.mark.parametrize("vmapped", [False, True], ids=["cell", "wave"])
def test_gram_from_d2(one_chip, out_dtype, vmapped):
    fn = functools.partial(gram_from_d2_pallas, out_dtype=out_dtype,
                           interpret=False)
    if vmapped:
        _compile(jax.vmap(fn), one_chip, ((SLOTS, N, N), F32), ((SLOTS,), F32))
    else:
        _compile(fn, one_chip, ((N, N), F32), ((), F32))


@pytest.mark.parametrize("p", [1, 7, 21])
@pytest.mark.parametrize("d", [54, 768])
def test_svm_predict_cells(one_chip, p, d):
    dp, cells = D_PAD[d], 8
    _compile(functools.partial(svm_predict_cells_pallas, interpret=False),
             one_chip, ((cells, 128, dp), F32), ((cells, N, dp), F32),
             ((cells, N, p), F32), ((cells, p), F32))


@pytest.mark.parametrize("d", [54, 768])
def test_assign(one_chip, d):
    dp = D_PAD[d]
    _compile(functools.partial(_assign_pallas_padded, interpret=False),
             one_chip, ((4096, dp), F32), ((384, dp), F32))
