"""Pure-jnp oracle for the FPS tile kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.fps import axis_distance


def fps_tiles_ref(points: jax.Array, k: int, *, metric: str = "l1") -> jax.Array:
    """points: (T, 3, P) -> (T, k) int32.  Matches the kernel's tie-breaking
    (first index of the max) and start convention (index 0)."""

    def one_tile(pts):  # (3, P)
        p = pts.shape[-1]

        def body(carry, _):
            dmin, last = carry
            ref = jax.lax.dynamic_slice(pts, (0, last), (3, 1))
            diff = pts - ref
            d = axis_distance(diff[0], diff[1], diff[2], metric)
            new_dmin = jnp.minimum(dmin, d)
            nxt = jnp.argmax(new_dmin).astype(jnp.int32)  # first max index
            return (new_dmin, nxt), last

        init = (jnp.full((p,), 1e30, jnp.float32), jnp.int32(0))
        _, sampled = jax.lax.scan(body, init, None, length=k)
        return sampled

    return jax.vmap(one_tile)(points)
