"""Procedural point clouds: eight rotated, scaled and jittered shape classes.

Sphere, cube surface, cylinder, cone, torus, plane, helix and cross, each
drawn in its canonical frame, rotated uniformly at random, scaled by 0.7-1.3
and jittered by 0.02. Every point is drawn independently, so the first n
points of a cloud are a cloud of n points of the same shape. Per-point
features (`features`) are colour-like channels drawn from a key of their
own, so that adding them leaves every cloud's xyz as it was.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

N_CLASSES = 8


def _unit(x, eps=1e-9):
    return x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + eps)


def _make_shape(cls_id, key, n: int):
    k1, k2, k3 = jax.random.split(key, 3)
    u = jax.random.uniform(k1, (n, 3), minval=-1.0, maxval=1.0)
    t = jax.random.uniform(k2, (n,), minval=0.0, maxval=1.0)
    rows = jnp.arange(n)
    sphere = _unit(jax.random.normal(k3, (n, 3)))
    m = jnp.argmax(jnp.abs(u), axis=1)
    cube = u.at[rows, m].set(jnp.sign(u[rows, m]))
    theta = 2 * jnp.pi * t
    cylinder = jnp.stack([jnp.cos(theta), jnp.sin(theta), u[:, 2]], axis=1)
    r_cone = 1.0 - t
    cone = jnp.stack([r_cone * jnp.cos(theta), r_cone * jnp.sin(theta), 2 * t - 1], axis=1)
    phi = 2 * jnp.pi * u[:, 0]
    ring = 0.7 + 0.3 * jnp.cos(phi)
    torus = jnp.stack([ring * jnp.cos(theta), ring * jnp.sin(theta), 0.3 * jnp.sin(phi)], axis=1)
    plane = jnp.stack([u[:, 0], u[:, 1], 0.05 * u[:, 2]], axis=1)
    hz = 2 * t - 1
    helix = jnp.stack([jnp.cos(3 * jnp.pi * hz), jnp.sin(3 * jnp.pi * hz), hz], axis=1) + 0.05 * u
    bar = jnp.stack([u[:, 0], 0.15 * u[:, 1], 0.15 * u[:, 2]], axis=1)
    cross = jnp.where((u[:, 2] > 0)[:, None], bar[:, jnp.array([1, 0, 2])], bar)
    shapes = jnp.stack([sphere, cube, cylinder, cone, torus, plane, helix, cross])
    return shapes[cls_id]


def _rotation(key):
    a = jax.random.normal(key, (3, 3))
    q, r = jnp.linalg.qr(a)
    q = q * jnp.sign(jnp.diagonal(r))[None, :]
    return q.at[:, 0].multiply(jnp.sign(jnp.linalg.det(q)))


@functools.partial(jax.jit, static_argnames=("count", "n_points"))
def clouds(key, count: int, n_points: int):
    """`count` clouds of `n_points` points each: (count, n_points, 3) float32."""
    def one(k):
        kc, ks, kr, kj, kscale = jax.random.split(k, 5)
        cls_id = jax.random.randint(kc, (), 0, N_CLASSES)
        canon = _make_shape(cls_id, ks, n_points)
        scale = jax.random.uniform(kscale, (), minval=0.7, maxval=1.3)
        pts = jnp.matmul(canon * scale, _rotation(kr).T, precision=jax.lax.Precision.HIGHEST)
        return (pts + 0.02 * jax.random.normal(kj, pts.shape)).astype(jnp.float32)

    return jax.vmap(one)(jax.random.split(key, count))


@functools.partial(jax.jit, static_argnames=("count", "n_points", "channels"))
def features(key, count: int, n_points: int, channels: int):
    """Colour-like features in [0, 1]: (count, n_points, channels) float32.

    Each cloud draws one base colour; each point varies it by a normal of
    0.1 and is clipped into [0, 1].
    """
    def one(k):
        kb, kp = jax.random.split(k)
        base = jax.random.uniform(kb, (channels,))
        return jnp.clip(base + 0.1 * jax.random.normal(kp, (n_points, channels)), 0.0, 1.0)

    return jax.vmap(one)(jax.random.split(key, count)).astype(jnp.float32)
