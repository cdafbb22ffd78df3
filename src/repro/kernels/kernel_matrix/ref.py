"""Pure-jnp oracles for the Gram-matrix kernels (fused and split-D² paths)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def sq_dists_ref(x: Array, z: Array, symmetric: bool = False) -> Array:
    x = x.astype(jnp.float32)
    z = z.astype(jnp.float32)
    # full f32 cross term: the TPU's default one-pass bf16 matmul makes the
    # GEMM-form D² cancel badly for nearby points
    cross = jnp.matmul(x, z.T, precision=jax.lax.Precision.HIGHEST)
    d2 = jnp.maximum(
        jnp.sum(x * x, -1)[:, None] + jnp.sum(z * z, -1)[None, :] - 2.0 * cross, 0.0
    )
    if symmetric:
        # match the Pallas upper-triangle + mirror contract bitwise
        d2 = 0.5 * (d2 + d2.T)
    return d2


def gram_from_d2_ref(d2: Array, gamma: Array, kind: str = "gauss_rbf",
                     out_dtype: str = "f32") -> Array:
    g = jnp.asarray(gamma, jnp.float32)
    d2 = d2.astype(jnp.float32)
    if kind == "gauss_rbf":
        k = jnp.exp(-d2 / jnp.maximum(g * g, 1e-12))
    elif kind == "laplacian":
        k = jnp.exp(-jnp.sqrt(d2 + 1e-12) / jnp.maximum(g, 1e-12))
    else:
        raise ValueError(kind)
    return k.astype(jnp.bfloat16) if out_dtype == "bf16" else k


def kernel_matrix_ref(x: Array, z: Array, gamma: Array, kind: str = "gauss_rbf") -> Array:
    return gram_from_d2_ref(sq_dists_ref(x, z), gamma, kind)
