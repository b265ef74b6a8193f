"""async-discipline: the event loop is the data plane — don't block it,
don't drop task exceptions.

Two checks:

* **blocking call in async def** (gateway/, engine/, disagg/, wire/,
  obs/): ``time.sleep``, sync HTTP (``requests.*``,
  ``urllib.request.*``, ``http.client``), ``subprocess.run``/
  ``check_*``/``call``, ``socket.create_connection`` and builtin
  ``open()`` inside a coroutine stall every connection multiplexed on
  the loop.  Use the async equivalent, ``run_in_executor``, or — for a
  provably sub-millisecond call — annotate
  ``# sct: async-discipline-ok <why it cannot block>``.

* **fire-and-forget create_task** (whole package): a task whose result
  is never retained silently swallows its exception at GC time — the
  classic lost-crash.  Keep the handle (assign it, await it, or attach
  ``add_done_callback``); assigning to ``self.<attr>`` counts as
  retained (close() paths own it).
"""

from __future__ import annotations

import ast
from typing import Iterable

from seldon_core_tpu.tools.sctlint.core import Context, Finding, Rule, dotted

BLOCKING_PREFIXES = (
    "time.sleep",
    "requests.",
    "urllib.request.",
    "http.client.",
    "socket.create_connection",
    "subprocess.run",
    "subprocess.call",
    "subprocess.check_call",
    "subprocess.check_output",
    "subprocess.Popen",
)

BLOCKING_SCOPE = (
    "seldon_core_tpu/gateway/",
    "seldon_core_tpu/engine/",
    "seldon_core_tpu/disagg/",
    "seldon_core_tpu/wire/",
    "seldon_core_tpu/obs/",
)


def _async_blocking(src, fn) -> Iterable[Finding]:
    for n in ast.walk(fn):
        if not isinstance(n, ast.Call):
            continue
        d = dotted(n.func)
        if d == "open" or any(
            d == p.rstrip(".") or d.startswith(p) for p in BLOCKING_PREFIXES
        ):
            yield Finding(
                "async-discipline", src.rel, n.lineno,
                f"blocking call {d}(...) inside async def "
                f"'{fn.name}' stalls the event loop — use the async "
                "equivalent or run_in_executor",
                src.snippet(n.lineno),
            )


def _fire_and_forget(src, fn) -> Iterable[Finding]:
    # statements whose value is a bare create_task call
    for stmt in ast.walk(fn):
        if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Call):
            call = stmt.value
            f = call.func
            if isinstance(f, ast.Attribute) and f.attr == "create_task" \
                    or isinstance(f, ast.Name) and f.id == "create_task":
                yield Finding(
                    "async-discipline", src.rel, stmt.lineno,
                    "fire-and-forget create_task: the task's exception "
                    "is silently dropped at GC — keep the handle and "
                    "add_done_callback (or await it)",
                    src.snippet(stmt.lineno),
                )
        # task = create_task(...) where the name never appears again
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name) \
                and isinstance(stmt.value, ast.Call):
            f = stmt.value.func
            is_ct = (isinstance(f, ast.Attribute) and f.attr == "create_task"
                     ) or (isinstance(f, ast.Name) and f.id == "create_task")
            if not is_ct:
                continue
            name = stmt.targets[0].id
            uses = [
                n for n in ast.walk(fn)
                if isinstance(n, ast.Name) and n.id == name
                and isinstance(n.ctx, ast.Load)
            ]
            if not uses:
                yield Finding(
                    "async-discipline", src.rel, stmt.lineno,
                    f"task handle '{name}' is never used after "
                    "create_task — its exception is dropped; "
                    "add_done_callback or await it",
                    src.snippet(stmt.lineno),
                )


def check(ctx: Context) -> Iterable[Finding]:
    out: list[Finding] = []
    seen: set[tuple[str, str, int]] = set()
    for src in ctx.py:
        if src.tree is None or not src.rel.startswith("seldon_core_tpu/"):
            continue
        if "/tools/" in src.rel:
            continue
        for n in ast.walk(src.tree):
            if isinstance(n, ast.AsyncFunctionDef) \
                    and src.rel.startswith(BLOCKING_SCOPE):
                out.extend(_async_blocking(src, n))
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.extend(_fire_and_forget(src, n))
    # ast.walk visits nested defs both on their own and inside their
    # enclosing function's walk — keep one finding per site
    uniq = []
    for f in out:
        k = (f.rule, f.path, f.line)
        if k not in seen:
            seen.add(k)
            uniq.append(f)
    return uniq


RULE = Rule(
    id="async-discipline",
    summary="no blocking calls in coroutines; no dropped task handles",
    explain=__doc__,
    check=check,
)
