"""Mesh-sharded quantized matmul — bit-exact by construction.

Every registered backend's integer core runs partitioned over a mesh and
produces accumulators (and hence dequantized outputs) **bitwise identical**
to the single-device call. No tolerance is involved; the argument is
structural (docs/sharding.md, proven per backend in
tests/test_sharded_backends.py):

  M/N sharding   each int32 accumulator out[m, n] is computed by exactly
                 one device from the full K contraction — the same integer
                 op sequence as single-device. Per-token activation scales
                 sx[m] live with their row on the M ('data') shard,
                 per-channel weight scales sw[n] with their column on the
                 N ('model') shard; dequant is element-wise, so sharded
                 dequant is the identical float op per element.
  K sharding     each device computes an int32 partial sum over its K
                 slice; `jax.lax.psum` adds int32 values, and integer
                 addition is associative and commutative, so the total is
                 the single-device accumulator bit for bit. The rank-R
                 correction GEMMs of approx_rank1 stay f32-exact under any
                 K split because every partial sum over <= k_exact_f32
                 terms is an exact integer below 2^24 and a K-shard only
                 shrinks chunks (`quant.matmul.k_chunk_plan`); chunk
                 results are accumulated in int32 before the psum.
  quantization   scale reductions (row max over K, column max over K) are
                 max-reductions — order-invariant — so quantize outside
                 the shard_map is bitwise regardless of operand sharding.

The Pallas backends run under shard_map with ``check_vma=False`` (pallas
calls define no varying-axes rule); correctness is carried by the specs.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as PS

from repro.quant.matmul import (_pin, _resolve_backend,  # noqa: F401
                                k_chunk_plan, quantized_matmul)
from repro.quant.quantize import QuantConfig, abs_max_scale, quantize


def _axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _usable(axis: Optional[str], dim: int, mesh: Mesh) -> Optional[str]:
    """The axis if it exists on the mesh and divides `dim`, else None —
    the same divisibility fallback as `parallel.sharding.prune_spec`."""
    if axis is None or axis not in mesh.axis_names:
        return None
    return axis if dim % _axis_sizes(mesh)[axis] == 0 else None


def shard_plan(m: int, k: int, n: int, mesh: Mesh,
               m_axis: Optional[str] = "data",
               n_axis: Optional[str] = "model",
               k_axis: Optional[str] = None
               ) -> Tuple[Optional[str], Optional[str], Optional[str]]:
    """Resolve the (m_axis, n_axis, k_axis) partition actually used for an
    (M, K) x (K, N) integer matmul: requested axes that are absent from
    the mesh or do not divide their dim degrade to replication, and one
    mesh axis shards at most one dim (k wins over n if both ask for it —
    K sharding is the memory-bound case the ISSUE partitions for)."""
    k_ax = _usable(k_axis, k, mesh)
    n_ax = _usable(n_axis, n, mesh)
    m_ax = _usable(m_axis, m, mesh)
    if k_ax is not None and k_ax == n_ax:
        n_ax = None
    if m_ax is not None and m_ax in (k_ax, n_ax):
        m_ax = None
    return m_ax, n_ax, k_ax


def sharded_integer_matmul(x_q: jax.Array, w_q: jax.Array, cfg: QuantConfig,
                           mesh: Mesh, *,
                           m_axis: Optional[str] = "data",
                           n_axis: Optional[str] = "model",
                           k_axis: Optional[str] = None) -> jax.Array:
    """Pre-dequant int32 matmul via cfg.backend, partitioned over `mesh`.

    x_q (M, K) int8, w_q (K, N) int8 -> (M, N) int32, bitwise identical
    to `integer_matmul(x_q, w_q, cfg)` for every registered backend and
    any admissible (m_axis, n_axis, k_axis) assignment.
    """
    m, k = x_q.shape
    n = w_q.shape[1]
    m_ax, n_ax, k_ax = shard_plan(m, k, n, mesh, m_axis, n_axis, k_axis)
    backend = _resolve_backend(cfg)

    def body(a, b):
        part = backend.fn(a, b, cfg)
        if k_ax is not None:
            part = jax.lax.psum(part, k_ax)   # int32: exact in any order
        return part

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(PS(m_ax, k_ax), PS(k_ax, n_ax)),
                       out_specs=PS(m_ax, n_ax), check_vma=False)
    return fn(x_q, w_q)


def sharded_quantized_matmul(x: jax.Array, w: jax.Array, cfg: QuantConfig,
                             mesh: Optional[Mesh] = None,
                             bias: Optional[jax.Array] = None,
                             activation: Optional[str] = None, *,
                             m_axis: Optional[str] = "data",
                             n_axis: Optional[str] = "model",
                             k_axis: Optional[str] = None) -> jax.Array:
    """Shard-aware `quantized_matmul`: float operands in, float out,
    bitwise identical to the single-device call for every backend.

    Quantization runs outside the shard_map (row/column max-reductions are
    order-invariant; per-token scales partition along the batch with x's
    rows, per-channel weight scales along N with w's columns), the integer
    core runs partitioned, and the element-wise dequant/bias/activation
    epilogue runs on the already-sharded int32 output. mesh=None (or an
    empty/1-device mesh) falls back to the stock `quantized_matmul`.
    Inference path: no custom_vjp — serving and the parity suites drive
    the forward only.
    """
    if mesh is None or mesh.devices.size <= 1:
        return quantized_matmul(x, w, cfg, bias, activation)
    if activation not in (None, "relu"):
        raise ValueError(f"unsupported activation {activation!r}")
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[1]
    per_token = cfg.act_scale == "per_token"
    if not per_token and cfg.act_scale != "per_tensor":
        raise ValueError(f"unknown act_scale {cfg.act_scale!r}; "
                         "choose 'per_tensor' or 'per_token'")
    if cfg.per_channel:
        sw = abs_max_scale(w, axis=0, keepdims=True)      # (1, n)
    else:
        sw = abs_max_scale(w)
    w_q = quantize(w, sw)
    x2 = x.reshape(-1, k)
    sx = abs_max_scale(x2, axis=-1 if per_token else None,
                       keepdims=per_token)                # (M, 1) | scalar
    x_q = quantize(x2, sx)
    acc = sharded_integer_matmul(x_q, w_q, cfg, mesh, m_axis=m_axis,
                                 n_axis=n_axis, k_axis=k_axis)
    if per_token:
        # Mirror the single-device rounding order exactly, barriers
        # included: `_qmm_forward` pins the per-token dequant to
        # (acc * sw) then * sx so the epilogue rounds identically at
        # every shape (quant/matmul._pin — the speculative-decoding
        # acceptance contract rests on it), and (acc*sw)*sx rounds
        # differently from acc*(sx*sw). Fused kernels apply sw in-kernel;
        # the explicit multiply here is the same f32 product bit for bit.
        y = _pin(_pin(acc.astype(jnp.float32) * sw) * sx)
    else:
        y = acc.astype(jnp.float32) * (sx * sw)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if activation == "relu":
        y = jnp.maximum(y, 0.0)
    return y.reshape(*lead, n).astype(x.dtype)


def make_sharded_matmul(cfg: QuantConfig, mesh: Mesh, **axes):
    """Jitted closure over (cfg, mesh, axis assignment) — the benchmark
    and test harness entry point."""
    return jax.jit(partial(sharded_quantized_matmul, cfg=cfg, mesh=mesh,
                           **axes), static_argnames=("activation",))
