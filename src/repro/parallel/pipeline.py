"""GPipe-style pipeline parallelism over a mesh axis (optional alternative to
pure DP across pods, for deeper-than-HBM models).

shard_map over the 'stage' axis: each device group holds one contiguous
layer block; microbatches stream through with collective_permute between
stages.  Schedule: standard GPipe fill-drain over M microbatches and P
stages — M + P - 1 ticks; each tick every stage runs its block on its
current microbatch and permutes activations forward.

Numerics match the single-device stack exactly (test-asserted): only the
execution order changes.
"""

from __future__ import annotations

import queue as queue_mod
import threading
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def two_stage_schedule(
    stage_a: Callable,
    stage_b: Callable,
    items: Sequence,
    *,
    depth: int = 2,
) -> list:
    """GPipe's fill-drain schedule for two stages, expressed at the host level.

    A producer thread runs ``stage_a`` over ``items`` in order, feeding a
    bounded hand-off queue of ``depth`` slots (double buffering by default);
    the caller's thread drains it and runs ``stage_b``.  While item k sits in
    stage B, item k+1 is already inside stage A — with jax's asynchronous
    dispatch this overlaps the two stages' device work even on ONE device
    (neither thread calls ``block_until_ready``), and when the stage
    callables pin their computations to different devices it is true
    two-device pipeline parallelism, the software analogue of
    ``pipeline_forward``'s collective-permute schedule.

    Returns ``[stage_b(stage_a(item)) for item in items]`` in item order.
    The first exception from either stage propagates to the caller; the
    bounded queue caps live stage-A output at ``depth + 2`` items (``depth``
    queued, one being produced, one being consumed), so a long stream never
    accumulates unbounded intermediates.
    """
    items = list(items)
    if not items:
        return []
    handoff: queue_mod.Queue = queue_mod.Queue(maxsize=max(1, depth))
    stop = threading.Event()

    def produce():
        for i, item in enumerate(items):
            if stop.is_set():
                return
            try:
                out = stage_a(item)
            except Exception as e:  # noqa: BLE001 — relayed to the consumer
                handoff.put((i, None, e))
                return
            handoff.put((i, out, None))

    producer = threading.Thread(
        target=produce, name="two-stage-pipeline-a", daemon=True
    )
    producer.start()

    results: list = [None] * len(items)
    error: Exception | None = None
    for _ in range(len(items)):
        i, val, err = handoff.get()
        if err is not None:
            error = err
            break
        try:
            results[i] = stage_b(val)
        except Exception as e:  # noqa: BLE001 — drain the producer, then raise
            error = e
            break
    if error is not None:
        stop.set()
        while producer.is_alive():  # unblock a producer stuck on a full queue
            try:
                handoff.get(timeout=0.01)
            except queue_mod.Empty:
                pass
        producer.join()
        raise error
    producer.join()
    return results


def pipeline_forward(
    mesh: Mesh,
    axis: str,
    stage_fn: Callable,  # (stage_params, x, stage_idx) -> x
    params_stacked,  # pytree with leading dim = n_stages
    x: jax.Array,  # (n_micro, mb, ...) microbatched input
):
    """Run x through n_stages sequential blocks laid out on `axis`.

    params_stacked leaves: (n_stages, ...) — stage s's slice lives on its
    own shard.  x: (n_micro, mb, D...) replicated; output identical layout.
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]

    def per_stage(params_local, x_all):
        # params_local: (1, ...) this stage's block; x_all: (n_micro, mb, ...)
        stage = jax.lax.axis_index(axis)
        params_here = jax.tree.map(lambda a: a[0], params_local)
        ticks = n_micro + n_stages - 1

        buf = jnp.zeros_like(x_all[0])  # current activation holding slot
        outs = jnp.zeros_like(x_all)

        def tick(carry, t):
            buf, outs = carry
            micro_idx = t - stage  # which microbatch this stage sees at tick t
            # stage 0 ingests fresh microbatches while available
            fresh = jax.lax.dynamic_index_in_dim(
                x_all, jnp.clip(t, 0, n_micro - 1), axis=0, keepdims=False
            )
            inp = jnp.where(stage == 0, fresh, buf)
            active = (micro_idx >= 0) & (micro_idx < n_micro)
            y = stage_fn(params_here, inp, stage)
            y = jnp.where(active, y, inp)
            # last stage writes its completed microbatch
            outs = jax.lax.cond(
                active & (stage == n_stages - 1),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(micro_idx, 0, n_micro - 1), axis=0
                ),
                lambda o: o,
                outs,
            )
            # permute activations forward one stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # only the last stage filled `outs` (zeros elsewhere): psum collects it
        outs = jax.lax.psum(outs, axis)
        return outs

    pspec_params = jax.tree.map(lambda _: P(axis), params_stacked)
    fn = jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(pspec_params, P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(params_stacked, x)
