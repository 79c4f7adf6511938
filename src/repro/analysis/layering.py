"""Layering & purity rules: the AST successors of the CI grep guards.

These three rules replace the hygiene-job ``grep -rn`` lines (and the two
tier-1 tests that mirrored them) with real parses: the greps could not see
``"sssp" == req.kind`` (reversed operands), ``from time import time as
now``, or ``import time as t`` — the AST rules can, so each invariant now
has exactly one source of truth.

LP001  no per-kind / per-channel string branching in ``gserve/`` — the
       PR 4 registry redesign exists so the serving layer never special-
       cases programs; a ``.kind == "sssp"`` comparison reintroduces the
       N-programs × M-call-sites maintenance matrix;
LP002  no wall-clock ``time.time()`` (alias-aware) anywhere in src/repro —
       measured intervals must use the monotonic ``perf_counter`` (NTP
       steps make wall-clock intervals go negative); true timestamps are
       suppressed case by case;
LP003  import layering: ``core`` must not import engine/stream/gserve/obs,
       ``engine`` must not import stream/gserve, ``stream`` must not
       import gserve, ``obs`` must not import gserve, and ``analysis``
       imports no sibling subsystem at all (it must stay runnable with
       zero heavyweight deps).  Relative imports are resolved to absolute
       ``repro.*`` names first.
"""
from __future__ import annotations

import ast
from typing import Iterator

from .base import (Finding, ImportMap, ModuleInfo, Rule, dotted,
                   qualname_at, register_rule)

_BRANCH_ATTRS = {"kind", "channel"}

# subsystem -> subsystems it must never import
LAYERING: dict[str, tuple[str, ...]] = {
    "core": ("engine", "stream", "gserve", "obs"),
    "engine": ("stream", "gserve"),
    "stream": ("gserve",),
    "obs": ("gserve",),
    "analysis": ("core", "engine", "stream", "gserve", "obs", "ckpt",
                 "train", "launch"),
}


class KindBranching(Rule):
    id = "LP001"
    family = "layering"
    name = "kind-string-branching-in-gserve"
    summary = ("no `.kind`/`.channel` == string-constant comparisons in "
               "gserve/ — program dispatch goes through the registry "
               "(PR 4); catches reversed operand order the grep missed")

    def check(self, mod: ModuleInfo) -> Iterator[Finding]:
        if mod.subsystem != "gserve":
            return
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Compare):
                continue
            sides = [node.left] + list(node.comparators)
            has_attr = any(
                isinstance(s, ast.Attribute) and s.attr in _BRANCH_ATTRS
                for s in sides)
            has_str = any(
                isinstance(s, ast.Constant) and isinstance(s.value, str)
                for s in sides)
            if has_attr and has_str:
                yield self.finding(
                    mod, node, qualname_at(mod.tree, node),
                    "per-kind/per-channel string comparison in the "
                    "serving layer: dispatch must go through the program "
                    "registry, not string branching")


class WallClock(Rule):
    id = "LP002"
    family = "layering"
    name = "wall-clock-time"
    summary = ("no time.time() in src/repro (alias-aware: catches `from "
               "time import time as now`) — intervals use the monotonic "
               "time.perf_counter(); genuine timestamps get a suppression")

    def check(self, mod: ModuleInfo) -> Iterator[Finding]:
        imports = ImportMap(mod)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            d = dotted(node.func)
            if not d:
                continue
            if imports.resolve(d) == "time.time" or d == "time.time":
                yield self.finding(
                    mod, node, qualname_at(mod.tree, node),
                    f"wall-clock time.time() (written `{d}()`): intervals "
                    "must use time.perf_counter(); if this is a genuine "
                    "timestamp, suppress with a justification")


class ImportLayering(Rule):
    id = "LP003"
    family = "layering"
    name = "import-layering"
    summary = ("core never imports engine/stream/gserve/obs; engine never "
               "imports stream/gserve; stream/obs never import gserve; "
               "analysis imports no repro sibling (relative imports "
               "resolved first)")

    def check(self, mod: ModuleInfo) -> Iterator[Finding]:
        forbidden = LAYERING.get(mod.subsystem)
        if not forbidden:
            return
        pkg = mod.rel.rsplit("/", 1)[0].replace("/", ".") \
            if "/" in mod.rel else ""
        pkg = f"repro.{pkg}" if pkg else "repro"
        for node in ast.walk(mod.tree):
            targets: list[str] = []
            if isinstance(node, ast.Import):
                targets = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                base = ImportMap.resolve_from(node, pkg)
                targets = [f"{base}.{a.name}" if base else a.name
                           for a in node.names]
            for t in targets:
                parts = t.split(".")
                if "repro" not in parts:
                    continue
                after = parts[parts.index("repro") + 1:]
                if after and after[0] in forbidden and \
                        after[0] != mod.subsystem:
                    yield self.finding(
                        mod, node, qualname_at(mod.tree, node),
                        f"{mod.subsystem!r} must not import "
                        f"repro.{after[0]} (layering: "
                        f"{mod.subsystem} forbids {', '.join(forbidden)})")
                    break


register_rule(KindBranching())
register_rule(WallClock())
register_rule(ImportLayering())
