"""PAL: Pallas TPU kernel invariants.

The hand-rolled DMA chains (KV page streaming, the double-buffered
int8 expert-weight slabs) are the exact code where a
missing ``.wait()`` deadlocks a semaphore or races a slot overwrite, and
where an int8 tiling that doesn't divide the page silently corrupts the
byte splice.  These rules pin the structural invariants a numerics test
can miss:

  PAL001  a kernel function issues manual DMA ``.start()`` calls but
          contains no ``.wait()`` — some control path leaves the copy
          unconsumed (semaphore leak; the next grid step's start on the
          same semaphore deadlocks or tears the slot).
  PAL002  an int8 kernel module with no divisibility gate (an ``assert``
          / ``if``-guard containing ``%``): int8 rows pack 32-wide, and
          an ungated block size corrupts the packed splice off-device
          where no exception will ever surface.
  PAL003  a kernel module no ``--interpret`` parity test references —
          directly, or through a glue entry point (a function in an
          importing module that calls the kernel) named by a test file
          that exercises interpret mode.  CPU interpret parity is the
          only pre-chip numerics gate this repo has.

Coverage extends beyond ``ops/pallas``: int8 *wire-format* modules
(``INT8_WIRE_MODULES`` — round 10 adds the quantized-collective helpers)
carry the same PAL002/PAL003 obligations.  A chunked int8 exchange with
an ungated split corrupts rows off-device exactly like an ungated page
splice, and the CPU parity suite is likewise its only pre-chip gate —
coverage is counted through glue entry points such as
``expert_ffn_a2a`` the same way kernel glue is.
"""

from __future__ import annotations

import ast
import pathlib
import re
from typing import Dict, List, Set

from llm_d_tpu.analysis.core import Context, Finding, Pass

KERNEL_DIR = "llm_d_tpu/ops/pallas"
# Non-Pallas modules holding int8 wire formats: same divisibility-gate
# and parity-test obligations as the kernels (PAL002/PAL003).
INT8_WIRE_MODULES = ("llm_d_tpu/parallel/quant_collectives.py",)


def _has_mod_gate(tree: ast.Module) -> bool:
    """A ``%`` inside an assert test or if test anywhere in the module."""
    guards = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Assert):
            guards.append(node.test)
        elif isinstance(node, ast.If):
            guards.append(node.test)
    for test in guards:
        for sub in ast.walk(test):
            if isinstance(sub, ast.BinOp) and isinstance(sub.op, ast.Mod):
                return True
    return False


class PallasPass(Pass):
    name = "pallas"
    rules = {
        "PAL001": "manual DMA .start() with no .wait() in the function",
        "PAL002": "int8 kernel module without a divisibility gate",
        "PAL003": "kernel not referenced by an --interpret parity test",
    }

    def _kernel_modules(self, ctx: Context) -> List[str]:
        return [rel for rel in ctx.package_files
                if rel.startswith(KERNEL_DIR + "/")
                and not rel.endswith("__init__.py")
                and "pallas_call" in ctx.source(rel).text]

    def run(self, ctx: Context) -> List[Finding]:
        findings: List[Finding] = []
        kernels = self._kernel_modules(ctx) + [
            rel for rel in INT8_WIRE_MODULES if rel in ctx.package_files]
        interpret_tests = [rel for rel in ctx.test_files
                           if "interpret" in ctx.source(rel).text]
        test_text = "\n".join(ctx.source(rel).text
                              for rel in interpret_tests)

        for rel in kernels:
            src = ctx.source(rel)
            tree = src.tree
            if tree is None:
                continue

            # PAL001 — per top-level function: starts demand waits.
            for fn in tree.body:
                if not isinstance(fn, ast.FunctionDef):
                    continue
                starts: List[int] = []
                waits = 0
                for node in ast.walk(fn):
                    if isinstance(node, ast.Call) \
                            and isinstance(node.func, ast.Attribute):
                        if node.func.attr == "start":
                            starts.append(node.lineno)
                        elif node.func.attr == "wait":
                            waits += 1
                if starts and not waits:
                    findings.append(Finding(
                        "PAL001", rel, starts[0],
                        f"{fn.name!r} starts {len(starts)} DMA(s) but "
                        f"never waits — unconsumed semaphore on some "
                        f"control path"))

            # PAL002 — int8 kernels must gate their tiling.
            if "int8" in src.text and not _has_mod_gate(tree):
                findings.append(Finding(
                    "PAL002", rel, 1,
                    "int8 kernel module has no divisibility gate "
                    "(assert/if with %) for its tiling"))

            # PAL003 — interpret-test coverage, direct or via glue.
            # Word-boundary match: the stem 'moe_routed' must not be
            # credited by a test that only names 'moe_routed_stream'.
            names = self._referenceable_names(ctx, rel, tree)
            if not any(re.search(rf"\b{re.escape(n)}\b", test_text)
                       for n in names):
                findings.append(Finding(
                    "PAL003", rel, 1,
                    f"no --interpret parity test references this kernel "
                    f"(looked for {sorted(names)[:6]}... in interpret "
                    f"tests)"))
        return findings

    def _referenceable_names(self, ctx: Context, rel: str,
                             tree: ast.Module) -> Set[str]:
        """Names whose appearance in an interpret test counts as coverage:
        the module stem, its public entry points, and glue functions in
        importing modules that call those entry points."""
        stem = pathlib.PurePosixPath(rel).stem
        public = {fn.name for fn in tree.body
                  if isinstance(fn, ast.FunctionDef)
                  and not fn.name.startswith("_")}
        names = {stem} | public
        dotted = rel[:-3].replace("/", ".")
        for other in ctx.package_files:
            if other == rel:
                continue
            osrc = ctx.source(other)
            if dotted not in osrc.text or osrc.tree is None:
                continue
            names |= self._glue_entry_points(osrc.tree, public)
        return names

    @staticmethod
    def _glue_entry_points(tree: ast.Module,
                           kernel_fns: Set[str]) -> Set[str]:
        """Top-level functions of an importer whose body references one
        of the kernel's entry points (the tested glue path)."""
        out: Set[str] = set()
        refs: Dict[str, Set[str]] = {}
        for fn in tree.body:
            if isinstance(fn, ast.FunctionDef):
                refs[fn.name] = {n.id for n in ast.walk(fn)
                                 if isinstance(n, ast.Name)}
                refs[fn.name] |= {n.attr for n in ast.walk(fn)
                                  if isinstance(n, ast.Attribute)}
                # function-level ``from ...pallas.X import f`` imports
                for n in ast.walk(fn):
                    if isinstance(n, ast.ImportFrom):
                        refs[fn.name] |= {a.name for a in n.names}
        for name, used in refs.items():
            if used & kernel_fns:
                out.add(name)
        return out
