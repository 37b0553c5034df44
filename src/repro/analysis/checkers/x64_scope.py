"""SIM002 — 64-bit precision is scoped, never process-global.

PR 6 settled the precision discipline: the compiled cores run in
float32/int32 by default and opt into doubles only under a scoped
``with jax.enable_x64(True):`` block, so one import can never flip dtype
semantics for the rest of the process (and with it, the bit-for-bit
equivalence grid).  This checker flags the three escape hatches:
``jax.config.update("jax_enable_x64", ...)``, assignment to
``config.jax_enable_x64``, and a bare ``enable_x64(...)`` call used as a
statement instead of a ``with`` context.
"""
from __future__ import annotations

import ast
from typing import List

from repro.analysis.core import Checker, SourceFile, dotted_name
from repro.analysis.diagnostics import Diagnostic


class X64Scope(Checker):
    code = "SIM002"
    name = "x64-scope"

    def check_file(self, src: SourceFile) -> List[Diagnostic]:
        diags: List[Diagnostic] = []
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Call):
                fname = dotted_name(node.func)
                if fname.endswith("config.update") and node.args and \
                        isinstance(node.args[0], ast.Constant) and \
                        node.args[0].value == "jax_enable_x64":
                    diags.append(src.diag(
                        "SIM002", node,
                        "process-global `config.update(\"jax_enable_x64\""
                        ", ...)`; use a scoped "
                        "`with jax.enable_x64(True):` block"))
                elif fname.rsplit(".", 1)[-1] == "enable_x64":
                    parent = getattr(node, "parent", None)
                    in_with = isinstance(parent, ast.withitem)
                    if not in_with:
                        diags.append(src.diag(
                            "SIM002", node,
                            "`enable_x64(...)` outside a `with` statement "
                            "leaks 64-bit mode; use "
                            "`with jax.enable_x64(True):`"))
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    if isinstance(t, ast.Attribute) and \
                            t.attr == "jax_enable_x64":
                        diags.append(src.diag(
                            "SIM002", node,
                            "direct assignment to `config.jax_enable_x64`"
                            "; use a scoped "
                            "`with jax.enable_x64(True):` block"))
        return diags
