"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ref_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """[M,K] x [K,N] in f32 accumulation."""
    return jnp.dot(x.astype(jnp.float32),
                   w.astype(jnp.float32)).astype(x.dtype)


def ref_attention(q, k, v, *, causal: bool = True,
                  scale=None) -> jax.Array:
    """q/k/v: (B, S, H, D) (same head count); plain softmax attention."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    scale = scale if scale is not None else D ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    if causal:
        mask = jnp.arange(Sk)[None, :] <= jnp.arange(Sq)[:, None]
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def ref_ssd(x, dt, A, B, C) -> tuple[jax.Array, jax.Array]:
    """Naive sequential SSD recurrence (the ground truth).

    x: (BH, S, P); dt: (BH, S); A: (BH,); B/C: (BH, S, N).
    h_{t} = exp(dt_t A) h_{t-1} + dt_t * B_t (outer) x_t ;  y_t = C_t . h_t
    Returns y: (BH, S, P) and final state (BH, P, N).
    """
    BH, S, P = x.shape
    N = B.shape[-1]

    def step(h, inp):
        xt, dtt, bt, ct = inp
        decay = jnp.exp(dtt * A)  # (BH,)
        h = h * decay[:, None, None] + (dtt[:, None] * xt)[:, :, None] \
            * bt[:, None, :]
        y = jnp.einsum("bpn,bn->bp", h, ct)
        return h, y

    h0 = jnp.zeros((BH, P, N), jnp.float32)
    xs = (x.astype(jnp.float32).transpose(1, 0, 2),
          dt.astype(jnp.float32).T,
          B.astype(jnp.float32).transpose(1, 0, 2),
          C.astype(jnp.float32).transpose(1, 0, 2))
    h, ys = jax.lax.scan(step, h0, xs)
    return ys.transpose(1, 0, 2).astype(x.dtype), h
