"""Model stages as named scopes change the program's op metadata alone.

The served program is compiled on the CPU at the smoke sizes, with the
scopes of `models/pointnet2.py` and `core/engine.py` and with them taken
out: stripped of op metadata, the two programs are one.  That the scopes
reach the metadata, and the map a trace reader builds from it, are tested
with the reader (`bench/tests/test_bench_scopes.py`).
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_config
from repro.core.accelerator import PC2IMAccelerator

jax.config.update("jax_platform_name", "cpu")

BATCH = 4
TASKS = {"cls": "pointnet2-cls", "seg": "pointnet2-seg"}


def _compiled_text(task: str) -> str:
    cfg = get_config(TASKS[task], smoke=True)
    accel = PC2IMAccelerator(cfg)
    params = jax.eval_shape(accel.init, jax.random.PRNGKey(0))
    spec = jax.ShapeDtypeStruct((BATCH, cfg.n_points, 3), jnp.float32)
    return accel.infer_program.lower(params, spec).compile().as_text()


def _strip(text: str) -> str:
    """The HLO text without op metadata and the debug tables it refers to."""
    out, skip = [], False
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            skip = True
        elif line.startswith(("%", "ENTRY")):
            skip = False
        if not skip:
            out.append(re.sub(r",? metadata=\{[^}]*\}", "", line))
    return "\n".join(out)


@pytest.mark.parametrize("task", TASKS)
def test_scopes_change_the_metadata_only(monkeypatch, task):
    text = _compiled_text(task)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = _compiled_text(task)
    assert "/sa1/" in text and "/sa1/" not in bare
    assert _strip(bare) == _strip(text)


def test_infer_program_is_the_artifact_infer_runs():
    """`infer_program` serves: its output is `infer`'s, bit for bit."""
    cfg = get_config(TASKS["cls"], smoke=True)
    accel = PC2IMAccelerator(cfg)
    params = accel.init(jax.random.PRNGKey(0))
    points = jax.random.uniform(jax.random.PRNGKey(1), (BATCH, cfg.n_points, 3))
    assert accel.infer_program is accel.infer_program
    assert (accel.infer_program(params, points) == accel.infer(params, points)).all()
