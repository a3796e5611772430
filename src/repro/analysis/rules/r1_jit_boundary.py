"""R1 — jit-boundary hygiene.

Invariant: functions reachable from a jitted dispatch entry point (the
``@jax.jit`` impls in core/executor.py, the semiring rounds they call,
the mesh step fns and shard_map bodies in distributed/executor.py) must
never force a device→host sync or branch Python control flow on a tracer.
A single ``.item()`` / ``np.asarray`` / ``float(tracer)`` inside the
traced region either raises a ``TracerConversionError`` at trace time or
— worse, when it happens on a concrete leak — silently serializes the
async dispatch pipeline the whole executor design exists to keep full.

Flagged inside jit-reachable functions:

* ``x.item()`` — unconditional host sync
* ``np.asarray`` / ``np.array`` / ``np.ascontiguousarray`` — numpy pulls
  the operand to host; traced values must stay ``jnp``
* ``float(x)`` / ``int(x)`` / ``bool(x)`` on non-static expressions
  (shape/ndim/len arithmetic stays legal — those are Python ints at
  trace time)
* ``len(x.attr)`` — ``len()`` of device state (carried arrays); ``len``
  of tuples/lists by name stays legal
* Python ``if`` whose test calls into ``jnp.*`` — a tracer boolean;
  inside jit this must be ``lax.cond``/``jnp.where``
* ``telemetry.*`` calls (the host recorder, ``repro.telemetry``): under
  trace a span times the tracing, once, never the device's work — name
  a traced phase with ``jax.named_scope`` instead

The call graph is described in :mod:`repro.analysis.analyzer`; attribute
calls (backend method dispatch) are not traversed.
"""
from __future__ import annotations

import ast
from typing import Iterator

from ..analyzer import Finding, Project, dotted, is_static_expr, scan_region

RULE = "R1"
TITLE = "jit-boundary hygiene (host syncs inside traced dispatch)"

_NP_NAMES = ("np", "numpy", "onp")
_NP_SYNC_FUNCS = ("asarray", "array", "ascontiguousarray")
_CAST_FUNCS = ("float", "int", "bool")


def _finding(mod, node, qual, msg) -> Finding:
    return Finding(RULE, mod.relpath, node.lineno, node.col_offset,
                   f"{msg} inside jit-reachable function `{qual}`")


def _test_touches_jnp(test: ast.AST) -> bool:
    for n in ast.walk(test):
        if isinstance(n, ast.Call):
            d = dotted(n.func)
            if d.startswith("jnp.") or d.startswith("jax.numpy."):
                return True
    return False


def check(project: Project) -> Iterator[Finding]:
    graph = project.callgraph()
    for mod, qual, fn in graph.reachable_functions():
        for n in scan_region(fn):
            if isinstance(n, ast.If) and _test_touches_jnp(n.test):
                yield _finding(
                    mod, n, qual,
                    "Python `if` on a jnp (tracer) value — use lax.cond/"
                    "jnp.where")
                continue
            if not isinstance(n, ast.Call):
                continue
            func = n.func
            if (isinstance(func, ast.Attribute) and func.attr == "item"
                    and not n.args):
                yield _finding(mod, n, qual, "host sync `.item()`")
            elif dotted(func).startswith("telemetry."):
                yield _finding(
                    mod, n, qual,
                    f"host recorder call `{dotted(func)}` runs once at "
                    "trace time — use jax.named_scope")
            elif (isinstance(func, ast.Attribute)
                  and isinstance(func.value, ast.Name)
                  and func.value.id in _NP_NAMES
                  and func.attr in _NP_SYNC_FUNCS):
                yield _finding(
                    mod, n, qual,
                    f"`{func.value.id}.{func.attr}` forces device->host; "
                    "traced values must stay jnp")
            elif (isinstance(func, ast.Name) and func.id in _CAST_FUNCS
                  and len(n.args) == 1):
                arg = n.args[0]
                if is_static_expr(arg):
                    continue
                # Name args are unknowable statically — only flag
                # attribute chains (device state) and call results
                if isinstance(arg, (ast.Attribute, ast.Call)) or (
                        isinstance(arg, ast.Subscript)
                        and isinstance(arg.value, ast.Attribute)):
                    yield _finding(
                        mod, n, qual,
                        f"`{func.id}()` of a non-static value is a host "
                        "sync under trace")
            elif (isinstance(func, ast.Name) and func.id == "len"
                  and len(n.args) == 1
                  and isinstance(n.args[0], ast.Attribute)
                  and not is_static_expr(n.args[0])):
                yield _finding(
                    mod, n, qual,
                    "`len()` of device state — use a static `.shape` dim")
