"""Attack on the 3x3-conv ceiling: pallas kernels vs XLA's conv lowering.

Round-2 analysis (BASELINE.md) showed ResNet-50 on v5e is bound by XLA's
3x3-conv lowering (21-40 TFLOP/s vs ~58 for 1x1 convs and ~145-172 matmul
roofline). This probes kernel variants at ResNet-50's four dominant
stride-1 3x3 shapes (batch 256, NHWC, bf16):

- xla:       jax.lax.conv_general_dilated (the incumbent)
- shiftmm:   pure-XLA 9-shift-matmul decomposition (conv = sum of 9
             shifted 1x1 convs, each a (N*H*W, Cin)@(Cin, Cout) matmul)
- pallas9:   pallas kernel, one image per program, padded image resident
             in VMEM, 9 tap dot_generals accumulated in f32
- pallas_i2c: pallas kernel, in-VMEM im2col — builds the (H*W, 9*Cin)
             patch matrix in VMEM (never HBM) and runs ONE matmul with
             K=9*Cin, maximizing MXU occupancy for small Cin

Usage: python scripts/perf_pallas_conv.py [variant ...] [--bwd]
"""

from __future__ import annotations

import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

# ResNet-50 dominant stride-1 3x3 shapes at batch 256 (NHWC)
SHAPES = [
    (256, 56, 56, 64, 64),
    (256, 28, 28, 128, 128),
    (256, 14, 14, 256, 256),
    (256, 7, 7, 512, 512),
]


def conv_xla(x, w):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))


def conv_shiftmm(x, w):
    """9-shift-matmul at the XLA level: pad once, slice 9 views, matmul."""
    n, h, ww, cin = x.shape
    cout = w.shape[-1]
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    acc = jnp.zeros((n, h, ww, cout), jnp.float32)
    for dy in range(3):
        for dx in range(3):
            xs = jax.lax.slice(xp, (0, dy, dx, 0), (n, dy + h, dx + ww, cin))
            acc = acc + jax.lax.dot_general(
                xs, w[dy, dx], (((3,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    return acc.astype(x.dtype)


# ------------------------------------------------------------------ pallas --

def _k9_kernel(x_ref, w_ref, o_ref, *, h, ww, cin, cout):
    """One padded image in VMEM; accumulate 9 tap dot_generals in f32."""
    acc = jnp.zeros((h, ww, cout), jnp.float32)
    for dy in range(3):
        for dx in range(3):
            xs = x_ref[0, dy:dy + h, dx:dx + ww, :]
            acc = acc + jax.lax.dot_general(
                xs, w_ref[dy, dx], (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    o_ref[0] = acc.astype(o_ref.dtype)


def conv_pallas9(x, w, imgs_per_prog=1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, h, ww, cin = x.shape
    cout = w.shape[-1]
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    kern = functools.partial(_k9_kernel, h=h, ww=ww, cin=cin, cout=cout)
    return pl.pallas_call(
        kern,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, h + 2, ww + 2, cin), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((3, 3, cin, cout), lambda i: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, ww, cout), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h, ww, cout), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(xp, w)


def _i2c_kernel(x_ref, w_ref, o_ref, *, h, ww, cin, cout):
    """In-VMEM im2col: patches (H*W, 9*Cin), one K=9*Cin matmul."""
    cols = []
    for dy in range(3):
        for dx in range(3):
            cols.append(x_ref[0, dy:dy + h, dx:dx + ww, :]
                        .reshape(h * ww, cin))
    patches = jnp.concatenate(cols, axis=-1)          # (H*W, 9*Cin)
    out = jax.lax.dot_general(
        patches, w_ref[:].reshape(9 * cin, cout),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    o_ref[0] = out.reshape(h, ww, cout).astype(o_ref.dtype)


def conv_pallas_i2c(x, w):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, h, ww, cin = x.shape
    cout = w.shape[-1]
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    kern = functools.partial(_i2c_kernel, h=h, ww=ww, cin=cin, cout=cout)
    return pl.pallas_call(
        kern,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, h + 2, ww + 2, cin), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((3, 3, cin, cout), lambda i: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, ww, cout), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h, ww, cout), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(xp, w)


VARIANTS = {"xla": conv_xla, "shiftmm": conv_shiftmm,
            "pallas9": conv_pallas9, "pallas_i2c": conv_pallas_i2c}


def bench(fn, x, w, chain=16, iters=3):
    """Time ``chain`` back-to-back applications inside ONE jit, so that the
    per-call dispatch cost is amortized over the chain and does not drown a
    single op's time. Cin == Cout for all probed shapes so the output
    feeds the next application."""
    def chained(x, w):
        for _ in range(chain):
            x = fn(x, w).astype(x.dtype)
        return jnp.sum(x.astype(jnp.float32))

    f = jax.jit(chained)
    float(f(x, w))
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            s = f(x, w)
        float(s)
        dt = (time.perf_counter() - t0) / iters / chain
        best = dt if best is None else min(best, dt)
    return best


def main():
    names = [a for a in sys.argv[1:] if not a.startswith("-")] or \
        list(VARIANTS)
    rng = np.random.default_rng(0)
    for n, h, w_, cin, cout in SHAPES:
        x = jnp.asarray(rng.standard_normal((n, h, w_, cin)), jnp.bfloat16)
        wt = jnp.asarray(rng.standard_normal((3, 3, cin, cout)) * 0.05,
                         jnp.bfloat16)
        flops = 2 * n * h * w_ * 9 * cin * cout
        ref = np.asarray(conv_xla(x, wt), np.float32)
        line = [f"({n},{h},{w_},{cin})->{cout}:"]
        for name in names:
            try:
                out = np.asarray(VARIANTS[name](x, wt), np.float32)
                err = np.max(np.abs(out - ref)) / max(np.max(np.abs(ref)),
                                                      1e-6)
                assert err < 2e-2, f"mismatch {err}"
                dt = bench(VARIANTS[name], x, wt)
                line.append(f"{name}={dt * 1e3:.2f}ms "
                            f"({flops / dt / 1e12:.0f}TF/s)")
            except Exception as e:
                line.append(f"{name}=FAIL({type(e).__name__}: "
                            f"{str(e)[:80]})")
        print("  ".join(line), flush=True)


if __name__ == "__main__":
    main()
