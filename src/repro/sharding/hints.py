"""Activation-sharding hints (Megatron-style sequence parallelism).

Models are mesh-agnostic; the launcher activates a hint context and the
model calls `hint_residual(h)` at block boundaries.  Inside the context,
residual-stream activations (B, S, D) are constrained to
P(data_axes, 'model', None): the sequence dim shards over the TP axis
between blocks, which divides saved-for-backward activation memory by the
TP degree (the difference between 205 GB and ~13 GB per device for the
104B train cell).  GSPMD inserts the matching all-gather/reduce-scatter
pairs at attention/MLP boundaries — same collective volume as plain TP
all-reduces, lower live memory.

Without an active context every hint is a no-op, so smoke tests and
single-device examples run untouched.
"""

from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_STATE = threading.local()

# The one mesh axis a serving replica's device group is laid out over
# (launch.mesh.make_replica_mesh).  Model code never names the axis
# directly: `nn.linear` asks `replica_axis_active()` and the accelerator's
# sharded artifacts map over it — keeping the axis name a single shared
# constant is what lets the ExecutionPolicy.sharding knob stay inert under
# plain jit (the axis is simply unbound there).
REPLICA_AXIS = "shard"


def replica_axis_active() -> bool:
    """True iff tracing inside a computation mapped over REPLICA_AXIS.

    Inside `shard_map(..., mesh=make_replica_mesh(devs))` the axis is bound
    and policy-driven sharded code paths activate; under plain jit (or
    eager) the axis is unbound and every sharding knob is a no-op, so one
    policy object is safe to thread through both worlds.
    """
    try:
        jax.lax.axis_size(REPLICA_AXIS)
    except NameError:
        return False
    return True


@contextlib.contextmanager
def activation_sharding(mesh: Mesh, *, mode: str = "sp"):
    """mode: 'sp' (Megatron sequence parallel: batch->data, seq->model) |
    'fsdp2d' (batch over BOTH axes, weights gathered per layer: no
    activation collectives at all) | 'off'."""
    axes = tuple(mesh.axis_names)
    daxes = ("pod", "data") if "pod" in axes else ("data",)
    prev = getattr(_STATE, "ctx", None)
    _STATE.ctx = {"mesh": mesh, "daxes": daxes, "mode": mode}
    try:
        yield
    finally:
        _STATE.ctx = prev


def _ctx():
    return getattr(_STATE, "ctx", None)


def hint_residual(x: jax.Array) -> jax.Array:
    """(B, S, D) residual-stream constraint per the active mode."""
    c = _ctx()
    if c is None or c["mode"] == "off" or x.ndim != 3:
        return x
    mesh = c["mesh"]
    b, s, _ = x.shape
    daxes = c["daxes"]
    dtotal = 1
    for a in daxes:
        dtotal *= mesh.shape[a]
    msize = mesh.shape["model"]
    if c["mode"] == "fsdp2d":
        all_axes = daxes + ("model",)
        if b % (dtotal * msize) == 0:
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(all_axes, None, None))
            )
        # batch too small for 2D: fall through to SP
    bspec = daxes if b % dtotal == 0 else None
    sspec = "model" if (s % msize == 0 and s >= msize) else None
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(bspec, sspec, None))
    )


def hint_batch_only(x: jax.Array) -> jax.Array:
    """Constrain only the leading batch dim (decode-path activations)."""
    c = _ctx()
    if c is None or x.ndim < 1:
        return x
    mesh = c["mesh"]
    daxes = c["daxes"]
    dtotal = 1
    for a in daxes:
        dtotal *= mesh.shape[a]
    if x.shape[0] % dtotal != 0:
        return x
    spec = [None] * x.ndim
    spec[0] = daxes
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))
