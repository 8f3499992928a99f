"""nxdcheck rule engine: stdlib-only (``ast`` + ``tokenize``) static
enforcement of the serving stack's load-bearing invariants.

Every invariant this package checks is one a PR has actually broken (or
nearly broken) at runtime first:

* host syncs inside traced code (the ≤2-host-ops-per-fused-block
  contract, previously only *counted* from tracer spans after the fact);
* cache-returning programs that skip the ``_replicate_out`` boundary pin
  (the PR 3 GSPMD sharding bug class);
* pin/release pairing across the cancel/expire/shed/extract/handoff
  seams (the PR 5 storm page-leak and PR 10/13 unpin-seam classes);
* wall-clock / unseeded-rng / bare-set-iteration in scheduling decisions
  (the virtual-block-clock replay guarantees);
* drift between the fault plan, the observability names and the tests
  that assert on them.

The engine is deliberately boring: each rule is a callable over a
:class:`RepoCtx` yielding :class:`Finding`\\ s; waivers are explicit and
carry justifications; the CLI (``scripts/nxdcheck.py``) exits nonzero on
any unwaived finding. NO jax import anywhere in this package — the
checker must run in a bare container in seconds (it is wired into
tier-1, where it costs one `ast.parse` sweep).

Waiver syntax
-------------

In-file (preferred — the justification lives next to the code):

    something_flagged()  # nxdcheck: waive <rule-id> -- <justification>

or on the line directly above the finding. Repo-level (for findings
whose justification spans files):
``neuronx_distributed_tpu/analysis/waivers.txt`` lines of the form

    <rule-id> <relpath> <qualname-or-*> -- <justification>

Blank lines and ``#`` comments are ignored. A waiver with an empty
justification is itself a finding (``waiver`` pseudo-rule): silencing a
contract checker without saying why defeats the point.
"""

from __future__ import annotations

import ast
import dataclasses
import fnmatch
import io
import re
import tokenize
from pathlib import Path
from typing import Callable, Dict, Iterable, Iterator, List, Optional, Tuple

__all__ = [
    "Finding", "Rule", "FileCtx", "RepoCtx", "run_checks", "load_waivers",
    "parse_inline_waivers", "qualname_map",
]

# comment grammar:  # nxdcheck: waive <rule-id>[,<rule-id>...] -- reason
_WAIVE_RE = re.compile(
    r"#\s*nxdcheck:\s*waive\s+([a-z0-9_,\-]+)\s*(?:--\s*(.*))?$")


@dataclasses.dataclass
class Finding:
    """One contract violation at a source location. ``waived`` findings
    still appear in the JSON report (auditability) but do not gate."""

    rule: str
    path: str                    # repo-relative, forward slashes
    line: int
    qualname: str                # enclosing function/class path, or "<module>"
    message: str
    waived: bool = False
    waiver_reason: str = ""

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def key(self) -> str:
        return f"{self.rule} {self.path}:{self.line} {self.qualname}"


@dataclasses.dataclass(frozen=True)
class Rule:
    """A named contract. ``check`` walks the repo context and yields raw
    findings; the engine applies waivers afterwards so rules never need
    to know about them."""

    id: str
    doc: str
    check: Callable[["RepoCtx"], Iterator[Finding]]
    zero_waiver: bool = False    # rules 1-3: a waiver is itself a failure


class FileCtx:
    """One parsed source file: AST + per-line waiver comments + parent
    links (``node._nxd_parent``) + enclosing-scope qualnames."""

    def __init__(self, root: Path, path: Path):
        self.abspath = path
        self.rel = path.relative_to(root).as_posix()
        self.source = path.read_text()
        self.tree = ast.parse(self.source, filename=self.rel)
        for parent in ast.walk(self.tree):
            for child in ast.iter_child_nodes(parent):
                child._nxd_parent = parent  # type: ignore[attr-defined]
        self.qualnames = qualname_map(self.tree)
        # line -> (rule-ids or {"*"}, reason); an empty-reason waiver is
        # recorded with reason "" and reported by the engine
        self.waivers: Dict[int, Tuple[set, str]] = parse_inline_waivers(
            self.source)

    def qualname_at(self, node: ast.AST) -> str:
        return self.qualnames.get(id(node), "<module>")


def qualname_map(tree: ast.AST) -> Dict[int, str]:
    """id(node) -> dotted enclosing-scope name ("Class.method.inner")."""
    out: Dict[int, str] = {}

    def visit(node: ast.AST, stack: List[str]) -> None:
        name = None
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            name = node.name
        elif isinstance(node, ast.Lambda):
            name = "<lambda>"
        nstack = stack + [name] if name else stack
        label = ".".join(nstack) if nstack else "<module>"
        for child in ast.iter_child_nodes(node):
            out[id(child)] = label
            visit(child, nstack)

    visit(tree, [])
    return out


def parse_inline_waivers(source: str) -> Dict[int, Tuple[set, str]]:
    out: Dict[int, Tuple[set, str]] = {}
    try:
        toks = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in toks:
            if tok.type != tokenize.COMMENT:
                continue
            m = _WAIVE_RE.search(tok.string)
            if m:
                rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
                out[tok.start[0]] = (rules, (m.group(2) or "").strip())
    except tokenize.TokenError:
        pass
    return out


class RepoCtx:
    """Lazy repo view the rules share: parsed package files plus ast
    access to repo-level surfaces (scripts/, tests/). Built once per run;
    building it is the dominant cost."""

    def __init__(self, root: Path, package: str = "neuronx_distributed_tpu"):
        self.root = Path(root)
        self.package = package
        self._files: Optional[List[FileCtx]] = None
        self._cache: Dict[str, FileCtx] = {}

    @property
    def files(self) -> List[FileCtx]:
        if self._files is None:
            pkg = self.root / self.package
            self._files = [self.file(p) for p in sorted(pkg.rglob("*.py"))
                           if "__pycache__" not in p.parts]
        return self._files

    def file(self, path: Path) -> FileCtx:
        key = str(path)
        if key not in self._cache:
            self._cache[key] = FileCtx(self.root, path)
        return self._cache[key]

    def maybe_file(self, rel: str) -> Optional[FileCtx]:
        p = self.root / rel
        if not p.exists():
            return None
        return self.file(p)

    def test_files(self) -> List[FileCtx]:
        tdir = self.root / "tests"
        if not tdir.is_dir():
            return []
        return [self.file(p) for p in sorted(tdir.glob("test_*.py"))]


def load_waivers(path: Path) -> List[Tuple[str, str, str, str]]:
    """waivers.txt -> [(rule, relpath-glob, qualname-glob, reason)]."""
    out: List[Tuple[str, str, str, str]] = []
    if not path.exists():
        return out
    for ln, raw in enumerate(path.read_text().splitlines(), 1):
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        head, sep, reason = line.partition("--")
        parts = head.split()
        if len(parts) != 3 or not sep:
            raise ValueError(
                f"{path}:{ln}: expected '<rule> <path> <qualname> -- "
                f"<reason>', got {raw!r}")
        out.append((parts[0], parts[1], parts[2], reason.strip()))
    return out


def _apply_waivers(findings: List[Finding], ctx: RepoCtx,
                   file_waivers: Dict[str, Dict[int, Tuple[set, str]]],
                   repo_waivers: List[Tuple[str, str, str, str]]) -> None:
    for f in findings:
        per_line = file_waivers.get(f.path, {})
        for ln in (f.line, f.line - 1):
            entry = per_line.get(ln)
            if entry and (f.rule in entry[0] or "*" in entry[0]):
                f.waived = True
                f.waiver_reason = entry[1]
                break
        if f.waived:
            continue
        for rule, pglob, qglob, reason in repo_waivers:
            if (rule in (f.rule, "*")
                    and fnmatch.fnmatch(f.path, pglob)
                    and fnmatch.fnmatch(f.qualname, qglob)):
                f.waived = True
                f.waiver_reason = reason
                break


def run_checks(root: Path, rules: Iterable[Rule],
               waiver_file: Optional[Path] = None,
               package: str = "neuronx_distributed_tpu") -> List[Finding]:
    """Run ``rules`` over the repo at ``root``; returns findings with
    waivers applied (callers filter on ``waived`` to gate). An unparsable
    package file or a malformed waiver file raises — the CLI maps that to
    exit 2 (internal error), never a silent pass."""
    ctx = RepoCtx(Path(root), package=package)
    findings: List[Finding] = []
    rule_ids = set()
    for rule in rules:
        rule_ids.add(rule.id)
        findings.extend(rule.check(ctx))

    file_waivers = {fc.rel: fc.waivers for fc in ctx.files}
    # waiver hygiene: empty justifications and unknown rule ids are
    # themselves findings — a silencer that silences nothing it can name
    # is drift waiting to happen
    for fc in ctx.files:
        for ln, (rids, reason) in fc.waivers.items():
            if not reason:
                findings.append(Finding(
                    "waiver", fc.rel, ln, fc.qualname_at(fc.tree),
                    "waiver without a justification (add '-- <reason>')"))
            unknown = rids - rule_ids - {"*", "waiver"}
            if unknown:
                findings.append(Finding(
                    "waiver", fc.rel, ln, "<module>",
                    f"waiver names unknown rule(s) {sorted(unknown)}"))
    repo_waivers = []
    if waiver_file is not None:
        repo_waivers = load_waivers(waiver_file)
    _apply_waivers(findings, ctx, file_waivers, repo_waivers)
    # zero-waiver rules: a waived finding still gates — report it as a
    # fresh unwaived finding so the CLI exits 1
    for f in list(findings):
        if f.waived:
            rule = next((r for r in rules if r.id == f.rule), None)
            if rule is not None and rule.zero_waiver:
                findings.append(Finding(
                    "waiver", f.path, f.line, f.qualname,
                    f"rule '{f.rule}' is zero-waiver (fix the finding: "
                    f"{f.message})"))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings
