"""Minimal functional NN utilities (no flax): params are plain dicts of arrays.

Every dense layer routes through `linear(...)`, which takes the numeric /
backend decision as an explicit `ExecutionPolicy` — the paper's C4 (SC
W16A16) exposed to all architectures with no hidden state:

    policy = ExecutionPolicy(quant="sc_w16a16")
    y = nn.linear(params, x, policy=policy)

`policy=None` (the default) is the float path.  The quantized path goes
through the kernel registry (`kernels/sc_matmul`) exactly like the FPS and
lattice kernels, honouring `policy.backend` / `policy.interpret`.
"""

from __future__ import annotations

import contextlib
import warnings

import jax
import jax.numpy as jnp

from repro.core.policy import ExecutionPolicy
from repro.core.quant import quantize_symmetric
from repro.kernels.sc_matmul.ops import sc_matmul_op, sc_quantized_linear
from repro.sharding.hints import REPLICA_AXIS, replica_axis_active


@contextlib.contextmanager
def quant_mode(mode: str):
    """DEPRECATED, BEHAVIOR-CHANGING shim for the removed thread-local API.

    This shim keeps legacy `with nn.quant_mode(...)` code importable and
    callable for one release, but it CANNOT preserve the old semantics:
    quantization is no longer applied implicitly, so a caller that ignores
    the yielded value now gets FLOAT results where it used to get SC-CIM
    quantized ones.  The yielded `ExecutionPolicy` must be passed onward:

        with nn.quant_mode("sc_w16a16") as policy:   # deprecated
            y = nn.linear(params, x, policy=policy)

    New code should construct an `ExecutionPolicy` directly (or use
    `PC2IMAccelerator`, which owns one policy for the whole pipeline).
    Will be removed one release after the ExecutionPolicy API landed.
    """
    # FutureWarning (shown by default, unlike DeprecationWarning): legacy
    # callers that ignore the yielded policy now get FLOAT math — that
    # numeric change must be loud, not filtered.
    warnings.warn(
        "nn.quant_mode no longer applies quantization implicitly: linears "
        "run the SC path ONLY where the yielded ExecutionPolicy is passed, "
        "e.g. `with nn.quant_mode(m) as pol: nn.linear(p, x, policy=pol)`. "
        "Callers that ignore the yielded value get float results. Construct "
        "an ExecutionPolicy explicitly instead (repro.core.policy).",
        FutureWarning,
        stacklevel=3,
    )
    yield ExecutionPolicy(quant=mode)


def linear_init(key, d_in: int, d_out: int, *, bias: bool = True, scale: float | None = None, dtype=jnp.float32):
    wkey, _ = jax.random.split(key)
    std = scale if scale is not None else (1.0 / jnp.sqrt(d_in))
    p = {"w": (jax.random.normal(wkey, (d_in, d_out)) * std).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def _shard_mode(policy: ExecutionPolicy | None) -> str | None:
    """The policy's sharding mode, but ONLY inside a mapped replica mesh.

    Outside `shard_map` over REPLICA_AXIS the axis is unbound and every
    sharded code path deactivates, so a sharded policy traces identically
    to its unsharded twin under plain jit — the knob selects a different
    cached artifact, never different single-device math.
    """
    mode = getattr(policy, "sharding", None) if policy is not None else None
    if mode is None:
        return None
    return mode if replica_axis_active() else None


def _linear_tensor_sharded(p, x: jax.Array, policy: ExecutionPolicy) -> jax.Array:
    """Column-split linear across the replica mesh (split-concatenate).

    Each device multiplies against its slice of the weight columns and the
    partial products are concatenated with a tiled all_gather — the paper's
    SC dataflow lifted to a device group.  Bitwise-equal to the replicated
    linear: fp32 columns are independent (on a backend whose dot gives a
    column the same bits at any width; the CPU's does not, see
    tests/test_sharded_replica.py); the quantized path quantizes the FULL weight first
    (global per-tensor scale) and slices the integer planes, whose matmul is
    exact, so column subsets match the unsharded product exactly.  N is
    zero-padded up to a multiple of the group size; the pad columns are
    dropped after the gather.
    """
    w = p["w"]
    k, n = w.shape
    group = jax.lax.axis_size(REPLICA_AXIS)  # static axis size
    idx = jax.lax.axis_index(REPLICA_AXIS)
    cols = -(-n // group)  # ceil: last shard may hold zero-pad columns
    bits = policy.quant_bits
    if bits is None:
        wp = jnp.pad(w, ((0, 0), (0, cols * group - n)))
        wl = jax.lax.dynamic_slice_in_dim(wp, idx * cols, cols, axis=1)
        y = x @ wl
    else:
        lead = x.shape[:-1]
        xq = quantize_symmetric(x.reshape(-1, k), bits)
        wq = quantize_symmetric(w, bits)  # full-tensor scale: replicated, global
        wqp = jnp.pad(wq.q, ((0, 0), (0, cols * group - n)))
        wl = jax.lax.dynamic_slice_in_dim(wqp, idx * cols, cols, axis=1)
        y = sc_matmul_op(
            xq.q, wl, bits=bits,
            backend=policy.resolved_backend(), interpret=policy.interpret,
        )
        y = (y * (xq.scale * wq.scale)).reshape(lead + (cols,)).astype(x.dtype)
    y = jax.lax.all_gather(y, REPLICA_AXIS, axis=-1, tiled=True)[..., :n]
    if "b" in p:
        y = y + p["b"]
    return y


def linear(p, x: jax.Array, policy: ExecutionPolicy | None = None) -> jax.Array:
    """Dense layer.  policy=None or policy.quant="none": float matmul;
    otherwise the SC-CIM integer path via the kernel registry.  Under an
    active replica mesh (accelerator sharded artifacts), policy.sharding
    routes to the split-concatenate column sharding ("tensor") or
    globalizes the activation quant scale over the batch shards ("batch")."""
    mode = _shard_mode(policy)
    if mode == "tensor":
        return _linear_tensor_sharded(p, x, policy)
    bits = None if policy is None else policy.quant_bits
    if bits is None:
        y = x @ p["w"]
    else:
        y = sc_quantized_linear(
            x, p["w"], bits=bits,
            backend=policy.resolved_backend(), interpret=policy.interpret,
            amax_axis=REPLICA_AXIS if mode == "batch" else None,
        ).astype(x.dtype)
    if "b" in p:
        y = y + p["b"]
    return y


def layernorm_init(d: int, dtype=jnp.float32):
    return {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}


def tree_sum(x: jax.Array) -> jax.Array:
    """Sum over the last axis as a balanced tree of elementwise adds (keepdims).

    A reduce's association order is the compiler's choice and may differ
    between otherwise equal programs.  Elementwise adds over halves fix the
    order, so the sum has the same bits in every program and on every
    backend.  That matters where it feeds a rounding step: one ulp in a
    LayerNorm statistic can flip SC activation codes downstream.  Zero
    padding up to a power of two is exact.
    """
    c = x.shape[-1]
    width = 1 << max(c - 1, 0).bit_length()
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - c)])
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x


def layernorm(p, x: jax.Array, eps: float = 1e-5, *, fixed_order: bool = False) -> jax.Array:
    # stats in f32, normalisation applied in the input dtype (see rmsnorm).
    # fixed_order: sum the stats with tree_sum, for a LayerNorm whose output
    # is quantized next (mlp_apply under an SC policy)
    x32 = x.astype(jnp.float32)
    if fixed_order:
        c = x.shape[-1]
        mu = tree_sum(x32) / c
        var = tree_sum(jnp.square(x32 - mu)) / c
    else:
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x - mu.astype(x.dtype)) * jax.lax.rsqrt(var + eps).astype(x.dtype)
    return y * p["g"] + p["b"]


def mlp_init(key, channels: list[int], *, bias: bool = True, norm: bool = True, dtype=jnp.float32):
    """Per-point MLP stack: [linear -> LN -> relu] per layer (LN in place of
    the original BatchNorm — documented deviation, stats-free)."""
    keys = jax.random.split(key, len(channels) - 1)
    layers = []
    for i, (cin, cout) in enumerate(zip(channels[:-1], channels[1:])):
        lay = {"lin": linear_init(keys[i], cin, cout, bias=bias, dtype=dtype)}
        if norm:
            lay["ln"] = layernorm_init(cout, dtype)
        layers.append(lay)
    return {"layers": layers}


def mlp_apply(
    p, x: jax.Array, *, final_act: bool = True, policy: ExecutionPolicy | None = None
) -> jax.Array:
    n = len(p["layers"])
    # under an SC policy every LayerNorm output reaches a quantized linear
    quantized = policy is not None and policy.quant_bits is not None
    for i, lay in enumerate(p["layers"]):
        x = linear(lay["lin"], x, policy=policy)
        if "ln" in lay:
            x = layernorm(lay["ln"], x, fixed_order=quantized)
        if final_act or i < n - 1:
            x = jax.nn.relu(x)
    return x


def count_params(tree) -> int:
    return sum(x.size for x in jax.tree.leaves(tree))
