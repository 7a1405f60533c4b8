"""Fused BN-train forward/backward Pallas kernels.

The boundary is ``fused_bn_act_train`` (nn/conf/convolutional.py): a
custom-VJP whose forward computes train-mode batch stats + normalize +
activation (+ optional residual add) over the conv output ``z``, and
whose backward recomputes x̂ from ``z`` plus the saved O(C) mean/inv-std
— the In-Place Activated BatchNorm recipe. On stock XLA that region is
the profile's villain: the stats, normalize and activation each cross
the full activation set through HBM separately, and the backward's
recompute re-reads it again (tools/PROFILE_r5.md counts ~4.7 extra
crossings). These kernels express each direction as ONE ``pallas_call``
whose channel-tile blocks stay VMEM-resident across stats → normalize →
activation (+ residual) → write, so the activation set crosses HBM once
per direction.

Numerics mirror the jnp reference EXACTLY, branch for branch:
single-pass f32-accumulated stats for bf16/f16 inputs, two-pass
mean/var otherwise; the same cast points; the same activation
implementation (``get_activation``) — the CPU interpret-mode parity
tests in tests/test_zz_pallas.py hold both paths to tight tolerance
through the full custom-VJP (forward AND backward).

Grid: one program per channel tile (128 channels when the channel count
is a multiple of 128, the whole axis otherwise); per-channel stats make
tiles independent, so no cross-program reduction is needed. Every row of
a tile is VMEM-resident at once, so the kernels only fit activation sets
of a few thousand rows: of ResNet50's batch-128 stages the v5e compiler
accepts the 7x7 one and refuses the rest (``(128,56,56,256)`` bf16:
"RESOURCE_EXHAUSTED: ... input window allocation ... bf16[401408,128]",
205 MB against 128 MiB of VMEM). :func:`supported` refuses what cannot
fit, and the family is outside the automatic TPU rule
(``perf.pallas.TPU_AUTO_FAMILIES``) until it is row-blocked (ROADMAP
Speed 3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.activations import get_activation
from deeplearning4j_tpu.perf import pallas as _pk

__all__ = ["supported", "bn_act_fwd", "bn_act_bwd"]


# Mosaic's default scoped-VMEM limit on a v5e. The estimate below charges
# every streamed (rows, tile) window double-buffered plus the kernel's f32
# temporaries, lanes padded to 128 — conservative against the compiler's
# own accounting: the largest row count it accepts compiled for a
# described v5e in every fwd/bwd x dtype x residual x tile combination
# (tests/test_chip_compile.py keeps one shape each way).
_VMEM_BUDGET = 16 * 1024 * 1024


def _fits_vmem(z, streams: int, temps: int) -> bool:
    c = z.shape[-1]
    rows = z.size // c
    per_elem = 2 * streams * z.dtype.itemsize + 4 * temps
    return rows * max(_cblk(c), 128) * per_elem <= _VMEM_BUDGET


def supported(z, has_res: bool = False, backward: bool = False) -> bool:
    """Shapes this kernel family handles: channels-last with at least one
    leading axis and a non-empty channel axis, whose whole-row channel
    tile (with the residual / cotangent streams riding along) fits VMEM."""
    if not (z.ndim >= 2 and z.shape[-1] > 0 and z.size > 0):
        return False
    if backward:    # z, dout, dz (+ res, dpre)
        return _fits_vmem(z, 5 if has_res else 3, temps=4)
    return _fits_vmem(z, 3 if has_res else 2, temps=2)


def _cblk(c: int) -> int:
    # lane-width tiles when the channel axis allows, one tile otherwise
    return 128 if (c % 128 == 0 and c > 128) else c


def _low_precision(dtype) -> bool:
    return dtype in (jnp.bfloat16, jnp.float16)


def _fwd_kernel(act, eps, lowp, n_rows, has_res, *refs):
    if has_res:
        z_ref, g_ref, b_ref, r_ref, out_ref, mean_ref, var_ref, inv_ref = refs
    else:
        z_ref, g_ref, b_ref, out_ref, mean_ref, var_ref, inv_ref = refs
    z = z_ref[...]
    if lowp:
        zf = z.astype(jnp.float32)
        mean = jnp.sum(zf, axis=0, keepdims=True) / n_rows
        var = jnp.maximum(
            jnp.sum(zf * zf, axis=0, keepdims=True) / n_rows - mean * mean,
            0.0)
    else:
        mean = jnp.mean(z, axis=0, keepdims=True)
        var = jnp.var(z, axis=0, keepdims=True)
    sdt = var.dtype
    inv = lax.rsqrt(var + jnp.asarray(eps, sdt))
    scale = g_ref[...].astype(sdt) * inv
    shift = b_ref[...].astype(sdt) - mean * scale
    pre = z * scale.astype(z.dtype) + shift.astype(z.dtype)
    if has_res:
        pre = pre + r_ref[...]
    out_ref[...] = act(pre)
    mean_ref[...] = mean
    var_ref[...] = var
    inv_ref[...] = inv


def bn_act_fwd(act_name: str, eps: float, z, gamma, beta, res):
    """Pallas forward for ``fused_bn_act_train``: returns
    ``(out, mean, var, inv)`` with ``_bn_act_fwd_math``'s exact output
    contract (mean/var/inv are O(C) vectors in the stats dtype)."""
    from jax.experimental import pallas as pl

    shape = z.shape
    c = shape[-1]
    n = z.size // c
    lowp = _low_precision(z.dtype)
    sdt = jnp.float32 if lowp else z.dtype
    z2 = z.reshape(n, c)
    has_res = res is not None
    cblk = _cblk(c)
    act = get_activation(act_name)
    kernel = functools.partial(_fwd_kernel, act, float(eps), lowp, n,
                               has_res)
    in_specs = [
        pl.BlockSpec((n, cblk), lambda j: (0, j)),
        pl.BlockSpec((1, cblk), lambda j: (0, j)),
        pl.BlockSpec((1, cblk), lambda j: (0, j)),
    ]
    args = [z2, gamma.reshape(1, c), beta.reshape(1, c)]
    if has_res:
        in_specs.append(pl.BlockSpec((n, cblk), lambda j: (0, j)))
        args.append(res.reshape(n, c))
    out, mean, var, inv = pl.pallas_call(
        kernel,
        grid=(c // cblk,),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((n, cblk), lambda j: (0, j)),
            pl.BlockSpec((1, cblk), lambda j: (0, j)),
            pl.BlockSpec((1, cblk), lambda j: (0, j)),
            pl.BlockSpec((1, cblk), lambda j: (0, j)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n, c), z.dtype),
            jax.ShapeDtypeStruct((1, c), sdt),
            jax.ShapeDtypeStruct((1, c), sdt),
            jax.ShapeDtypeStruct((1, c), sdt),
        ),
        interpret=_pk.interpret(),
    )(*args)
    return (out.reshape(shape), mean.reshape(c), var.reshape(c),
            inv.reshape(c))


def _bwd_kernel(act, eps, n_rows, has_res, *refs):
    if has_res:
        (z_ref, g_ref, b_ref, r_ref, mean_ref, inv_ref, dout_ref,
         dz_ref, dg_ref, db_ref, dpre_ref) = refs
    else:
        (z_ref, g_ref, b_ref, mean_ref, inv_ref, dout_ref,
         dz_ref, dg_ref, db_ref) = refs
    z = z_ref[...]
    mean = mean_ref[...]
    inv = inv_ref[...]
    sdt = mean.dtype
    scale = g_ref[...].astype(sdt) * inv
    shift = b_ref[...].astype(sdt) - mean * scale
    pre = z * scale.astype(z.dtype) + shift.astype(z.dtype)
    if has_res:
        pre = pre + r_ref[...]
    # activation backward through the SAME implementation the forward
    # used, on the recomputed pre-image (no activation-sized saves). In
    # f32 for bf16/f16: the v5e has no low-precision vector compare
    # (relu's vjp: "Target does not support this comparison")
    cdt = jnp.float32 if _low_precision(z.dtype) else z.dtype
    _, act_vjp = jax.vjp(act, pre.astype(cdt))
    dpre = act_vjp(dout_ref[...].astype(cdt))[0].astype(z.dtype)
    zf = z.astype(sdt)
    xhat = (zf - mean) * inv
    dpre32 = dpre.astype(sdt)
    dgamma = jnp.sum(dpre32 * xhat, axis=0, keepdims=True)
    dbeta = jnp.sum(dpre32, axis=0, keepdims=True)
    dz_ref[...] = (scale * (dpre32 - dbeta / n_rows
                            - xhat * (dgamma / n_rows))).astype(z.dtype)
    dg_ref[...] = dgamma
    db_ref[...] = dbeta
    if has_res:
        dpre_ref[...] = dpre


def bn_act_bwd(act_name: str, eps: float, z, gamma, beta, res, mean, inv,
               dout):
    """Pallas backward for ``fused_bn_act_train``: ``(dz, dgamma, dbeta,
    dpre)`` with ``_fused_bn_act_bwd``'s exact math — x̂ recomputed from
    ``z`` + O(C) saves, full train-mode BN backward through the batch
    stats. ``dpre`` (the residual-input cotangent before its dtype cast)
    is None when ``res`` is None."""
    from jax.experimental import pallas as pl

    shape = z.shape
    c = shape[-1]
    n = z.size // c
    sdt = mean.dtype
    has_res = res is not None
    cblk = _cblk(c)
    act = get_activation(act_name)
    kernel = functools.partial(_bwd_kernel, act, float(eps), n, has_res)
    in_specs = [
        pl.BlockSpec((n, cblk), lambda j: (0, j)),
        pl.BlockSpec((1, cblk), lambda j: (0, j)),
        pl.BlockSpec((1, cblk), lambda j: (0, j)),
    ]
    args = [z.reshape(n, c), gamma.reshape(1, c), beta.reshape(1, c)]
    if has_res:
        in_specs.append(pl.BlockSpec((n, cblk), lambda j: (0, j)))
        args.append(res.reshape(n, c))
    in_specs += [
        pl.BlockSpec((1, cblk), lambda j: (0, j)),
        pl.BlockSpec((1, cblk), lambda j: (0, j)),
        pl.BlockSpec((n, cblk), lambda j: (0, j)),
    ]
    args += [mean.reshape(1, c), inv.reshape(1, c), dout.reshape(n, c)]
    out_specs = [
        pl.BlockSpec((n, cblk), lambda j: (0, j)),
        pl.BlockSpec((1, cblk), lambda j: (0, j)),
        pl.BlockSpec((1, cblk), lambda j: (0, j)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((n, c), z.dtype),
        jax.ShapeDtypeStruct((1, c), sdt),
        jax.ShapeDtypeStruct((1, c), sdt),
    ]
    if has_res:
        out_specs.append(pl.BlockSpec((n, cblk), lambda j: (0, j)))
        out_shape.append(jax.ShapeDtypeStruct((n, c), z.dtype))
    outs = pl.pallas_call(
        kernel,
        grid=(c // cblk,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        interpret=_pk.interpret(),
    )(*args)
    dz, dgamma, dbeta = outs[0], outs[1], outs[2]
    dpre = outs[3].reshape(shape) if has_res else None
    return (dz.reshape(shape), dgamma.reshape(c).astype(gamma.dtype),
            dbeta.reshape(c).astype(beta.dtype), dpre)
