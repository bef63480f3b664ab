"""A short causal depthwise convolution over a PACKED ragged buffer against
state a ROW (LFM2's gated short convolution, ``conv_L_cache`` taps; the gate
around it is the model's: models/lfm2_moe.py).

    c_t = sum_{j < L} k[:, j] * v_{t - (L - 1) + j}        (a channel)

The serving engine packs, in one buffer ``[T, E]``, rows that feed ONE token
(decoding) and rows that feed a CHUNK of a prompt, and keeps for every row
(batch slot) the ``L - 1`` inputs before its next position: ``state[b] =
(v_{dec - (L-1)}, .., v_{dec - 1})``, whatever the context's length.  A tap of
token ``t`` is taken

* from the buffer, where the earlier token was fed with it (same row, the
  chunk's own tokens),
* from the row's state, where it lies before the row's first fed token,
* as ZERO, where its position is under 0: by POSITION, not by what the state
  holds, so a slot's next tenant and a request recomputed from position 0
  never read what the last one left, and no state is ever reset.

Every fed row writes back the last ``L - 1`` inputs up to its new end; a row
that feeds nothing keeps its state.  ``jax.numpy`` alone: at 3 taps the op is
two gathers and three multiply-adds over ``[T, E]``, a few microseconds beside
the projections around it."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["short_conv"]


@jax.named_scope("short_conv")
def short_conv(v, kernel, state, row, pos, dec, now, cu):
    """v [T, E] packed inputs; kernel [E, L]; state [B, L - 1, E]; row, pos
    [T]: each token's row and position in its sequence (``token_coords``);
    dec, now [B], cu [B + 1]: the rows' cached lengths, tokens fed and
    offsets in the buffer.  -> (c [T, E] in v's type, new state).  A token
    past its row's ``now`` gets a value nobody reads."""
    T, E = v.shape
    B, H, _ = state.shape                     # H = L - 1 inputs of history
    tok = jnp.arange(T, dtype=jnp.int32)
    local = tok - cu[row]
    src = jnp.concatenate([v, state.reshape(B * H, E).astype(v.dtype)])
    acc = v.astype(jnp.float32) * kernel[:, H].astype(jnp.float32)
    for back in range(1, H + 1):              # the input ``back`` positions earlier
        at = jnp.where(local >= back, tok - back, T + row * H + (H - back + local))
        tap = jnp.where((pos >= back)[:, None], src[jnp.clip(at, 0, T + B * H - 1)], 0)
        acc = acc + tap.astype(jnp.float32) * kernel[:, H - back].astype(jnp.float32)
    # the last H inputs up to each fed row's new end: from the buffer where
    # the row fed that many, else shifted out of its old state
    j = jnp.arange(H, dtype=jnp.int32)[None, :]                    # [1, H]
    behind = H - 1 - j                                              # inputs after this one
    fed = now[:, None] > behind
    at = jnp.where(fed, (cu[:-1] + now)[:, None] - 1 - behind,
                   T + jnp.arange(B, dtype=jnp.int32)[:, None] * H + j + now[:, None])
    new = src[jnp.clip(at, 0, T + B * H - 1)].astype(state.dtype)   # [B, H, E]
    return acc.astype(v.dtype), jnp.where((now > 0)[:, None, None], new, state)
