"""The low-precision control: operands rounded to 8-bit floats.

The configurations state bfloat16, so the step that would tempt a later
PR is fp8.  ``fake_fp8`` rounds a tensor to ``float8_e4m3fn`` under one
scale for the whole tensor (its largest magnitude maps to the format's
largest, 448) and passes the gradient straight through; the products
still accumulate in float32.  The reference run with this quantiser on
every matmul and convolution operand is the control that has to come
out as not correct.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0


def fake_fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(q - x)
