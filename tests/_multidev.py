"""Reusable multi-device (forced host platform) subprocess substrate.

jax fixes the device count at first backend initialization, so a test that
wants N CPU devices must set ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
BEFORE importing jax — impossible in the main pytest process, which already
holds the single real CPU device (and must keep it: the dry-run isolation
rule).  The pattern, extracted from test_pipeline_multidev.py:

  * ``run_in_child(body, n_devices=8)`` runs a Python snippet in a child
    process whose jax sees N host devices.  The snippet is prefixed with the
    XLA_FLAGS export and an ``emit(name, array)`` helper; everything emitted
    comes back to the parent as a dict of numpy arrays (via an .npz file),
    so parity assertions can live in the TEST, next to the other asserts,
    instead of being squeezed into the child's stdout.
  * ``assert_bitwise(payload, a, b)`` — the standard check: two emitted
    arrays are bitwise-identical (exact equality, not allclose).

A child that raises exits nonzero and the parent surfaces its stderr tail.
"""

from __future__ import annotations

import os
import subprocess
import sys
import tempfile
import textwrap

import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Runs before the test body in the child: force the device count (before any
# jax import!), then expose emit().  The payload is flushed by an explicit
# call appended AFTER the body, so a failing child never ships half a payload.
# Single-threaded Eigen: with multi-threaded CPU matmuls a row's bits depend
# on how many rows share the dot, and B=1 references and 1-row batch shards
# need them not to (pinned in tests/test_sharded_replica.py).
_PRELUDE = """\
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=%d --xla_cpu_multi_thread_eigen=false"
)
import numpy as np
_PAYLOAD = {}
def emit(name, value):
    _PAYLOAD[str(name)] = np.asarray(value)
def _flush_payload():
    out = os.environ.get("PC2IM_MULTIDEV_OUT")
    if out and _PAYLOAD:
        np.savez(out, **_PAYLOAD)
"""


def run_in_child(
    body: str, *, n_devices: int = 8, timeout_s: float = 600
) -> dict[str, np.ndarray]:
    """Run `body` in a subprocess with `n_devices` forced host CPU devices.

    Returns {name: array} for every emit(name, value) the body performed.
    Raises AssertionError (with the child's stderr tail) on nonzero exit.
    """
    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(td, "payload.npz")
        script = _PRELUDE % n_devices + textwrap.dedent(body) + "\n_flush_payload()\n"
        env = dict(os.environ)
        # CPU-only by design: forced host devices exist only on the CPU
        # backend, and the parent may hold the accelerator
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = "src"
        env["PC2IM_MULTIDEV_OUT"] = out
        res = subprocess.run(
            [sys.executable, "-c", script],
            capture_output=True,
            text=True,
            timeout=timeout_s,
            env=env,
            cwd=REPO_ROOT,
        )
        assert res.returncode == 0, (
            f"multi-device child failed (rc={res.returncode})\n"
            f"--- stdout tail ---\n{res.stdout[-2000:]}\n"
            f"--- stderr tail ---\n{res.stderr[-4000:]}"
        )
        payload: dict[str, np.ndarray] = {}
        if os.path.exists(out):
            with np.load(out) as z:
                payload = {k: z[k] for k in z.files}
        return payload


def assert_bitwise(payload: dict[str, np.ndarray], a: str, b: str) -> None:
    """Assert two emitted arrays are bitwise-identical (exact, not allclose)."""
    assert a in payload and b in payload, (
        f"payload missing {a!r} or {b!r}; has {sorted(payload)}"
    )
    np.testing.assert_array_equal(payload[a], payload[b])
