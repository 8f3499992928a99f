"""Rule ``surface-drift``: the string registries that tie fault plans,
observability and the tests that assert on them must stay reconciled.

These surfaces only work as a system: a ``FaultPlan`` probability field is
chaos coverage only if an injector reads it and a test drives it; a
stats/lane name a test asserts on is a guarantee only while a producer
still emits it (the registry-backed stats view defaults to 0, so producer
renames fail SILENTLY — the assert keeps passing on a dead counter). Each
sub-check below is one edge of that graph:

* ``faultplan``: every ``FaultPlan`` ``*_prob`` field is referenced by
  an injector call site in the package (outside faults.py) and
  mentioned in at least one test.
* ``observability-names``: every ``stats["..."]`` key and
  ``.events("...")`` name a test asserts on has a producer in the
  package (exact literal, or a producer f-string prefix).
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Optional, Set, Tuple

from .core import Finding, RepoCtx, Rule

TRACER_METHODS = {"instant", "span", "counter"}


def _literal_assign(tree: ast.AST, name: str) -> Optional[object]:
    for node in ast.walk(tree):
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
        for t in targets:
            if isinstance(t, ast.Name) and t.id == name:
                try:
                    return ast.literal_eval(
                        node.value if isinstance(node, ast.Assign)
                        else node.value)
                except ValueError:
                    return None
    return None


def _check_faultplan(ctx: RepoCtx) -> Iterator[Finding]:
    fp = ctx.maybe_file("neuronx_distributed_tpu/inference/faults.py")
    if fp is None:
        return
    fields: List[Tuple[str, int]] = []
    for node in ast.walk(fp.tree):
        if isinstance(node, ast.ClassDef) and node.name == "FaultPlan":
            for stmt in node.body:
                if (isinstance(stmt, ast.AnnAssign)
                        and isinstance(stmt.target, ast.Name)
                        and stmt.target.id.endswith("_prob")):
                    fields.append((stmt.target.id, stmt.lineno))
    # an injector call site READS the field — an ast.Attribute access
    # anywhere in the package (faults.py's own FaultInjector methods
    # included; the dataclass definition is an AnnAssign target, not an
    # Attribute, so it never self-satisfies)
    read_attrs: Set[str] = set()
    for fc in ctx.files:
        if "/analysis/" in fc.rel:
            continue
        for node in ast.walk(fc.tree):
            if isinstance(node, ast.Attribute):
                read_attrs.add(node.attr)
    test_src = "\n".join(tc.source for tc in ctx.test_files())
    for name, line in fields:
        if name not in read_attrs:
            yield Finding(
                "surface-drift", fp.rel, line, "FaultPlan",
                f"FaultPlan.{name} has no injector call site in the "
                f"package — a chaos knob nothing reads is dead coverage")
        if name not in test_src:
            yield Finding(
                "surface-drift", fp.rel, line, "FaultPlan",
                f"FaultPlan.{name} is never mentioned in tests — the "
                f"seam has no chaos coverage")


def _names_from_tree(tree: ast.AST) -> Tuple[Set[str], Set[str], List[str]]:
    """(stats keys, event names, event f-string prefixes) produced by one
    file. Producers of a stats key: a ``stats`` subscript (``self.stats``
    or a bare ``stats`` dict), a dict literal assigned/returned as
    ``stats`` (``self.stats = {...}``), or the
    ``_STAT_KEYS`` registry literal."""
    stats: Set[str] = set()
    events: Set[str] = set()
    prefixes: List[str] = []
    keys = _literal_assign(tree, "_STAT_KEYS")
    if isinstance(keys, (list, tuple)):
        stats |= {str(k) for k in keys}
    for node in ast.walk(tree):
        if (isinstance(node, ast.Subscript)
                and isinstance(node.ctx, (ast.Store, ast.Del))
                and isinstance(node.slice, ast.Constant)
                and isinstance(node.slice.value, str)):
            # WRITES only: a read is a consumer, not evidence the key
            # exists (else the consumer check would satisfy itself)
            recv = node.value
            if ((isinstance(recv, ast.Attribute) and recv.attr == "stats")
                    or (isinstance(recv, ast.Name) and recv.id == "stats")):
                stats.add(node.slice.value)
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Dict):
            tgt_names = {t.id for t in node.targets
                         if isinstance(t, ast.Name)}
            tgt_names |= {t.attr for t in node.targets
                          if isinstance(t, ast.Attribute)}
            if "stats" in tgt_names:
                for k in node.value.keys:
                    if isinstance(k, ast.Constant) and isinstance(
                            k.value, str):
                        stats.add(k.value)
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in TRACER_METHODS
                and node.args):
            a0 = node.args[0]
            if isinstance(a0, ast.Constant) and isinstance(a0.value, str):
                events.add(a0.value)
            elif isinstance(a0, ast.JoinedStr) and a0.values:
                head = a0.values[0]
                if (isinstance(head, ast.Constant)
                        and isinstance(head.value, str)
                        and head.value):
                    prefixes.append(head.value)
    return stats, events, prefixes


def _producer_names(ctx: RepoCtx) -> Tuple[Set[str], Set[str], List[str]]:
    stats: Set[str] = set()
    events: Set[str] = set()
    prefixes: List[str] = []
    for fc in ctx.files:
        if "/analysis/" in fc.rel:
            continue
        s, e, p = _names_from_tree(fc.tree)
        stats |= s
        events |= e
        prefixes.extend(p)
    return stats, events, prefixes


def _check_observability_names(ctx: RepoCtx) -> Iterator[Finding]:
    stats, events, prefixes = _producer_names(ctx)
    if not stats and not events:
        return
    for tc in ctx.test_files():
        # a test that writes its own stats key / emits its own event is
        # its own producer (the ad-hoc-key and custom-event unit tests)
        own_stats, own_events, own_prefixes = _names_from_tree(tc.tree)
        for node in ast.walk(tc.tree):
            if (isinstance(node, ast.Subscript)
                    and isinstance(node.value, ast.Attribute)
                    and node.value.attr == "stats"
                    and isinstance(node.slice, ast.Constant)
                    and isinstance(node.slice.value, str)):
                key = node.slice.value
                if key not in stats and key not in own_stats:
                    yield Finding(
                        "surface-drift", tc.rel, node.lineno,
                        tc.qualname_at(node),
                        f"test reads stats[{key!r}] but no package code "
                        f"produces that key — the registry view defaults "
                        f"to 0, so this assert passes on a dead counter")
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "events"
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                name = node.args[0].value
                if name in events or name in own_events:
                    continue
                if any(name.startswith(p)
                       for p in prefixes + own_prefixes):
                    continue
                yield Finding(
                    "surface-drift", tc.rel, node.lineno,
                    tc.qualname_at(node),
                    f"test filters tracer events({name!r}) but no package "
                    f"code emits that event name")


def check(ctx: RepoCtx) -> Iterator[Finding]:
    yield from _check_faultplan(ctx)
    yield from _check_observability_names(ctx)


RULE = Rule(
    id="surface-drift",
    doc="FaultPlan fields and observability names stay consistent with "
        "the tests that assert on them",
    check=check,
)
