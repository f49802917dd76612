"""Device time by model stage, and the host's share of each device gap.

Two readings of a traced run that need the program's own marks:

* The model runs each stage under a `jax.named_scope`, so the served
  program's op metadata names the stage of each instruction. `op_scopes`
  compiles the served artifact again at the run's shapes and maps every
  instruction name to its scope path ("fusion.11" -> "sa1/group"), by the
  rules of `hlo_op_scopes`; `stage_ms` looks each op event of the trace up
  in that map and sums the device time of the ops under one stage.
* The serving stack opens a profiler span at each stage of a batch
  (`serve/trace.py`). `split_gaps` cuts each gap between consecutive
  programs on a chip at the end of the first `batch.complete` span that
  ends after the first program: before the cut the finished program's
  results are fetched and handed to the clients, after it the next batch
  is copied to the chip and launched.

A program without these marks gives nothing to read: the readers then
return None and raise nothing.
"""

from __future__ import annotations

import bisect
import re
import sys

COMPLETE = "batch.complete"
METADATA_KEY = "jax_compilation_cache_include_metadata_in_key"

_HLO_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_WRAPPED = re.compile(r"^([\w-]+)\((.*)\)$")
_OPERAND = re.compile(r"%([^\s,()=]+)")


def scope_path(op_name: str) -> str:
    """Named scopes of one `op_name`: "jit(f)/sa1/vmap(group)/gather" -> "sa1/group".

    Of ops XLA merged ("a;b") the first is taken. Function boundaries
    (`jit(...)`) are dropped, transforms (`vmap(x)`) unwrapped, and the
    last component, the primitive, left out.
    """
    parts = op_name.split(";")[0].split("/")
    path = []
    for part in parts[:-1]:
        m = _WRAPPED.match(part)
        while m and m.group(1) not in ("jit", "pjit"):
            part = m.group(2)
            m = _WRAPPED.match(part)
        if m is None and part:
            path.append(part)
    return "/".join(path)


def hlo_op_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name -> scope path for every instruction of an HLO module text.

    An instruction takes the scopes of its `op_name` (`scope_path`). One
    that has none, because the compiler made it without metadata or it
    lies outside every named scope, takes the path of its first operand
    that has one: the relayouts, sorts and selects of a TPU top-k take the
    path of the distances they sort. Operands precede their users in the
    text, so one pass in order carries a path down a chain of such
    instructions. An instruction with no path by either rule maps to "".
    """
    out: dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _HLO_INSTR.match(line)
        if m is None:
            continue
        op = _OP_NAME.search(line)
        path = scope_path(op.group(1)) if op else ""
        if not path:
            path = next((out[a] for a in _OPERAND.findall(line[m.end():]) if out.get(a)), "")
        out[m.group(1)] = path
    return out


def compiled_text(accel, points) -> str | None:
    """HLO text of the program `accel.infer` serves, compiled at `points`' shape.

    The parameters are abstract. The compile's persistent-cache key takes
    the op metadata in: with the default key, an executable cached from a
    build with other scopes (or none) would come back with its metadata.
    The key is a process-wide setting, so this runs only once the serving
    runtime has stopped and nothing else compiles. A program that does not
    expose its artifact gives None.
    """
    import jax

    program = getattr(accel, "infer_program", None)
    if program is None:
        return None
    params = jax.eval_shape(accel.init, jax.random.PRNGKey(0))
    spec = jax.ShapeDtypeStruct(points.shape, points.dtype)
    lowered = program.lower(params, spec)
    prev = getattr(jax.config, METADATA_KEY)
    jax.config.update(METADATA_KEY, True)
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update(METADATA_KEY, prev)


def op_scopes(ctx) -> dict[str, str] | None:
    """Instruction name -> scope path of the served program, kept on `ctx`.

    The program is compiled again at the run's model (through the
    configuration's program adapter), policy, batch and cloud width; the
    instruction names are those of the program that ran.
    """
    cached = getattr(ctx, "op_scopes", None)
    if cached is not None:
        return cached
    import jax
    import jax.numpy as jnp

    from repro.core.accelerator import get_accelerator
    from repro.core.policy import ExecutionPolicy

    accel = get_accelerator(ctx.program.config(ctx.model), ExecutionPolicy(quant=ctx.quant))
    points = jax.ShapeDtypeStruct((ctx.batch, ctx.model["n_points"], ctx.width), jnp.float32)
    text = compiled_text(accel, points)
    if text is None:
        print("scopes: the program does not expose its served artifact", file=sys.stderr)
        return None
    ctx.op_scopes = hlo_op_scopes(text)
    return ctx.op_scopes


def stage_ms(ctx, stage: str, metric: str) -> float | None:
    """Device ms per program of the ops whose scope path holds `stage`.

    Prints on standard error how much of the traced op time the scope map
    names, and how much of it lies under a model stage.
    """
    tr = ctx.trace
    if tr is None or not tr.chips:
        return None
    programs = sum(len(tr.modules.get(d, ())) for d in tr.chips)
    if not programs:
        return None
    scopes = op_scopes(ctx)
    if scopes is None:
        return None
    total = named = scoped = hit = 0.0
    for d in tr.chips:
        for e in tr.ops[d]:
            total += e.dur
            path = scopes.get(e.name)
            named += e.dur if path is not None else 0.0
            scoped += e.dur if path else 0.0
            hit += e.dur if path and stage in path.split("/") else 0.0
    print(f"{metric}: {hit * 1e-6:.6f} ms of ops under /{stage} in {programs} programs; "
          f"scope map names {100 * named / total:.2f}% and scopes "
          f"{100 * scoped / total:.2f}% of {total * 1e-9:.6f} s of traced op time",
          file=sys.stderr)
    return hit / programs * 1e-6


def split_gaps(ctx) -> list[tuple[float, float]] | None:
    """(results, inputs) seconds of each gap between consecutive programs on a chip.

    The two parts of a gap sum to the gap that `xtrace.module_gaps_s`
    reads. The host spans carry no chip, so a run with more than one chip
    gives nothing.
    """
    tr = ctx.trace
    if tr is None or ctx.chips != 1:
        return None
    ends = sorted(e.end for e in tr.host if e.name == COMPLETE)
    if not ends:
        return None
    parts = []
    for evs in tr.modules.values():
        for a, b in zip(evs, evs[1:]):
            gap = max(0.0, b.start - a.end)
            i = bisect.bisect_right(ends, a.end)
            results = min(ends[i] - a.end, gap) if i < len(ends) else gap
            parts.append((results * 1e-9, (gap - results) * 1e-9))
    return parts or None


def gap_part_ms(ctx, part: int, metric: str) -> float | None:
    """Mean of one part of the gaps (0: results, 1: inputs), in ms."""
    parts = split_gaps(ctx)
    if parts is None:
        return None
    n_complete = sum(1 for e in ctx.trace.host if e.name == COMPLETE)
    print(f"{metric}: {len(parts)} gaps, {n_complete} {COMPLETE} spans", file=sys.stderr)
    return sum(p[part] for p in parts) / len(parts) * 1e3
