"""Depthwise convolution Pallas kernel (VPU path).

Depthwise conv is one of the paper's memory-bound showcases (Fig. 14): there
is no C_in reduction, so arithmetic intensity is ~kh*kw MACs/element and the
op lives on the HBM roofline.  The kernel keeps the whole row-tile resident
in VMEM (same halo trick as ``im2col_conv``) and does the kh*kw
multiply-accumulates on the VPU -- no MXU detour, no im2col expansion.
Strided convs read unit-stride views of the stride phases
``im2col_conv.phase_split`` builds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.im2col_conv import (PhaseTiling, phase_split, phase_specs,
                                       phase_tile, tap_view)


def _dw_kernel(*refs, t: PhaseTiling):
    n_x = 2 if t.hb else 1
    w_ref, o_ref = refs[n_x:]
    tile = phase_tile(refs[:n_x], t)
    acc = jnp.zeros((t.th, t.W_out, tile.shape[-1]), jnp.float32)
    for tap in t.taps:
        view = tap_view(tile, tap, t)
        acc += view.astype(jnp.float32) * w_ref[tap[3], tap[4]][None, None, :]
    o_ref[...] = acc[None].astype(o_ref.dtype)


def dwconv(
    x: jax.Array,            # (N, H, W, C)
    w: jax.Array,            # (kh, kw, C)
    *,
    stride=1,                # int or (sh, sw)
    padding=0,               # int, (ph, pw), or ((pt, pb), (pl, pr))
    block_rows: int = 8,
    block_c: int = 128,
    out_dtype: jnp.dtype | None = None,
    interpret: bool = False,
) -> jax.Array:
    N, H, W, C = x.shape
    kh, kw, C2 = w.shape
    assert C == C2
    out_dtype = out_dtype or x.dtype
    bc = min(block_c, C)
    x_ph, t = phase_split(x, kh, kw, stride, padding, block_rows, bc)
    w_p = jnp.pad(w, ((0, 0), (0, 0), (0, (-C) % bc)))
    n_c = w_p.shape[2] // bc

    grid = (N, t.n_h, n_c)
    x_specs = phase_specs(t, bc, lambda b, h, c: (b, h, c))
    out = pl.pallas_call(
        functools.partial(_dw_kernel, t=t),
        grid=grid,
        in_specs=[
            *x_specs,
            pl.BlockSpec((kh, kw, bc), lambda b, h, c: (0, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, t.th, t.W_out, bc),
                               lambda b, h, c: (b, h, 0, c)),
        out_shape=jax.ShapeDtypeStruct((N, t.n_h * t.th, t.W_out, n_c * bc),
                                       out_dtype),
        interpret=interpret,
    )(*[x_ph] * len(x_specs), w_p)
    return out[:, :t.H_out, :, :C]
