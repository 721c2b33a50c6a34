"""Plain CP-ALS reference for the benchmark's correctness check.

Independent of the program: it imports nothing of ``repro`` and takes
nothing the program made. From the tensor's COO and the initial factors
(both drawn by the benchmark from the seed) it runs ALS sweeps the
textbook way:

    M_d = X_(d) (⊙_{w≠d} F_w)            MTTKRP, summed over nonzeros
    V_d = ∘_{w≠d} F_wᵀ F_w               Hadamard product of Grams
    F_d = M_d V_d⁻¹,  λ = ‖columns of F_d‖,  F_d /= λ
    fit = 1 − ‖X − X̂‖ / ‖X‖,  with ‖X̂‖² = λᵀ(∘_w F_wᵀF_w)λ and
          ⟨X, X̂⟩ = Σ (M_last ∘ F_last) λ

The MTTKRP runs on the device in blocks of nonzeros (a scan over blocks,
each a gather, a product and a scatter-add into the float32 output), so
it fits beside nothing else; ``ec_dtype`` sets the precision of the
gathered rows and of their products. Everything dense (Grams, the solve,
λ, the fit) runs on the host in float64.

``ec_dtype=float32`` is the reference. ``ec_dtype=bfloat16`` is the
control: the same computation one precision step down, where a later
change would be tempted to go (bf16 factor rows halve the bytes the
bandwidth-bound MTTKRP moves). The check must fail it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["SweepOut", "DeviceCOO", "als_sweeps", "init_factors"]

BLOCK_NNZ = 1 << 22


class DeviceCOO:
    """The tensor's nonzeros on the device, padded to whole blocks (pad
    entries have value 0 and coordinates 0: exact no-ops)."""

    def __init__(self, indices: np.ndarray, values: np.ndarray, shape,
                 block: int = BLOCK_NNZ):
        nnz, nmodes = indices.shape
        block = max(1, min(block, nnz))
        nb = -(-nnz // block)
        pad = nb * block - nnz
        idx = np.concatenate([indices, np.zeros((pad, nmodes), np.int32)])
        val = np.concatenate([values, np.zeros(pad, np.float32)])
        self.indices = jax.device_put(idx.reshape(nb, block, nmodes))
        self.values = jax.device_put(val.reshape(nb, block))
        self.shape = tuple(int(s) for s in shape)
        self.norm_sq = float(np.sum(values.astype(np.float64) ** 2))


@functools.partial(jax.jit, static_argnames=("mode", "rows", "ec_dtype"))
def _mttkrp(indices, values, factors, *, mode: int, rows: int, ec_dtype):
    rank = factors[0].shape[1]

    def block(acc, blk):
        idx, val = blk
        prod = val.astype(ec_dtype)[:, None]
        for w, f in enumerate(factors):
            if w != mode:
                prod = prod * f.astype(ec_dtype)[idx[:, w]]
        return acc.at[idx[:, mode]].add(prod.astype(jnp.float32)), None

    out, _ = jax.lax.scan(block, jnp.zeros((rows, rank), jnp.float32),
                          (indices, values))
    return out


def mttkrp(coo: DeviceCOO, factors, mode: int, ec_dtype=jnp.float32):
    """Mode-``mode`` MTTKRP of ``coo`` with host factors; float64 result."""
    dev = tuple(jnp.asarray(f, jnp.float32) for f in factors)
    out = _mttkrp(coo.indices, coo.values, dev, mode=mode,
                  rows=coo.shape[mode], ec_dtype=ec_dtype)
    return np.asarray(out, np.float64)


class SweepOut:
    """One ALS sweep's outputs: per mode the MTTKRP ``m``, the normalized
    factor ``f`` and its column norms ``lam``; and the fit."""

    def __init__(self, m, f, lam, fit):
        self.m, self.f, self.lam, self.fit = m, f, lam, fit


def init_factors(seed: int, shape, rank: int) -> list[np.ndarray]:
    """Initial factors U(0.1, 1) from the seed (float32), the usual positive
    CP-ALS start."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    return [rng.uniform(0.1, 1.0, size=(int(s), rank)).astype(np.float32)
            for s in shape]


def als_sweeps(coo: DeviceCOO, factors, sweeps: int,
               ec_dtype=jnp.float32) -> list[SweepOut]:
    """``sweeps`` ALS sweeps from ``factors`` (not modified)."""
    f = [np.asarray(x, np.float64) for x in factors]
    grams = [x.T @ x for x in f]
    n = len(f)
    outs = []
    for _ in range(sweeps):
        ms, lams = [], []
        for d in range(n):
            m = mttkrp(coo, f, d, ec_dtype)
            v = functools.reduce(np.multiply,
                                 [grams[w] for w in range(n) if w != d])
            fd = np.linalg.solve(v, m.T).T
            lam = np.linalg.norm(fd, axis=0)
            lam = np.where(lam > 0, lam, 1.0)
            f[d] = fd / lam
            grams[d] = f[d].T @ f[d]
            ms.append(m)
            lams.append(lam)
        inner = float(np.sum(np.sum(ms[-1] * f[-1], axis=0) * lams[-1]))
        model_sq = float(lams[-1] @ functools.reduce(np.multiply, grams)
                         @ lams[-1])
        resid = max(coo.norm_sq - 2.0 * inner + model_sq, 0.0)
        fit = float(1.0 - np.sqrt(resid) / np.sqrt(coo.norm_sq))
        outs.append(SweepOut(ms, [x.copy() for x in f], lams, fit))
    return outs
