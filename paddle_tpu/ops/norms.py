"""Norms over packed rows, as plain ``jax.numpy``: what a served trunk or an
op applies to ``[..., E]`` rows inside a traced program."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["layer_norm"]

F32 = jnp.float32


def layer_norm(x, gain, bias, eps):
    """LayerNorm over the last axis (the mean taken out) in float32, back in
    x's dtype; ``bias`` None: a gain alone."""
    xf = x.astype(F32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * gain.astype(F32)
    return (y if bias is None else y + bias.astype(F32)).astype(x.dtype)
