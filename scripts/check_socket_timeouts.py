#!/usr/bin/env python
"""Repo lint: no DCN (or any) socket call path may block without a deadline.

The multi-host fault-tolerance layer turns a wedged peer into a DETECTED
failure — but only if every blocking socket operation carries a timeout
(a smoke run once hung on a `recv` with none). This script fails
on:

- ``socket.create_connection(...)`` / ``create_connection(...)`` calls that
  do not pass a ``timeout=`` keyword (or pass ``timeout=None``);
- functions that call ``<sock>.recv(...)`` or ``<sock>.accept(...)``
  without arming or asserting a deadline in the same scope — i.e. no
  ``.settimeout(...)`` call and no ``.gettimeout(...)`` guard
  (``tpu/dcn.py``'s ``_recv_exact`` raises when a caller hands it an
  undeadlined socket; that guard satisfies the lint because it *proves*
  the invariant instead of assuming it). ``accept`` rides the same rule
  because an undeadlined accept loop never observes its stop flag — the
  procmesh worker/lane-shard serve loops (ISSUE 16) poll accept under
  ``_ACCEPT_POLL_S`` for exactly this reason.

The whole package is in scope — ``tpu/dcn.py``'s data plane, ``core/io``
socket sources, and the ``procmesh/`` control plane (worker server,
supervisor client, lane-pool shards) alike.

Usage: ``python scripts/check_socket_timeouts.py [paths...]`` (default:
``siddhi_tpu/``). Exit code 1 on findings. Run by
``tests/test_dcn_resilience.py`` so it gates CI (the ``check_excepts.py``
pattern).
"""

from __future__ import annotations

import ast
import os
import sys

DEFAULT_PATHS = ["siddhi_tpu"]


def _call_attr(node: ast.Call) -> str:
    """Trailing attribute name of a call (``x.y.recv(...)`` → ``recv``)."""
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def _has_timeout_kw(node: ast.Call) -> bool:
    for kw in node.keywords:
        if kw.arg == "timeout":
            return not (isinstance(kw.value, ast.Constant)
                        and kw.value.value is None)
        if kw.arg is None:          # **kwargs: cannot prove, accept
            return True
    # create_connection's timeout is its 2nd positional argument
    if len(node.args) >= 2:
        arg = node.args[1]
        return not (isinstance(arg, ast.Constant) and arg.value is None)
    return False


def _scan_scope(node):
    """(recv calls, deadline armed?) for ONE scope: walks ``node``'s
    subtree but stops at nested function defs — each function is linted as
    its own scope (a deadline armed in an outer function does not cover an
    inner one that escapes it)."""
    recvs, armed = [], False
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if isinstance(n, ast.Call):
            attr = _call_attr(n)
            if attr in ("recv", "accept"):
                recvs.append(n)
            elif attr in ("settimeout", "gettimeout"):
                armed = True
        stack.extend(ast.iter_child_nodes(n))
    return recvs, armed


def check_file(path: str) -> list[str]:
    with open(path, encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [f"{path}:{e.lineno}: syntax error: {e.msg}"]
    problems = []

    for node in ast.walk(tree):
        if isinstance(node, ast.Call) \
                and _call_attr(node) == "create_connection" \
                and not _has_timeout_kw(node):
            problems.append(
                f"{path}:{node.lineno}: create_connection without a "
                f"timeout — a dead peer would hang the connect forever")

    scopes = [("<module>", tree)]
    scopes += [(n.name, n) for n in ast.walk(tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    for name, scope in scopes:
        recv_calls, armed = _scan_scope(scope)
        if recv_calls and not armed:
            for c in recv_calls:
                problems.append(
                    f"{path}:{c.lineno}: blocking {_call_attr(c)} in "
                    f"'{name}' with no deadline — call settimeout(...) or "
                    f"guard with gettimeout()")
    return problems


def main(argv: list[str]) -> int:
    paths = argv[1:] or DEFAULT_PATHS
    files = []
    for p in paths:
        if os.path.isfile(p):
            files.append(p)
        else:
            for root, _dirs, names in os.walk(p):
                files.extend(os.path.join(root, n) for n in names
                             if n.endswith(".py"))
    problems = []
    for f in sorted(files):
        problems.extend(check_file(f))
    for p in problems:
        print(p)
    if problems:
        print(f"\n{len(problems)} problem(s) found.")
        return 1
    print(f"OK: {len(files)} file(s) clean.")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
