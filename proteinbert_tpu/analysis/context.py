"""Shared parse context for `pbt check` rules.

Every rule consumes the same one-pass artifacts: each scanned file is
read and `ast.parse`d exactly once, `# guarded-by:` / `# lock-held:`
comment annotations are extracted from raw source lines (the AST drops
comments), and a cheap per-file identifier index serves the dead-export
sweep. Rules never touch the filesystem themselves — fixture tests
point a `CheckConfig` at a temp tree and get identical behavior to the
repo run.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

# --- comment annotations -------------------------------------------------
# `self.attr = ...  # guarded-by: _lock` declares that `self.attr` may
# only be touched inside `with self._lock`. `def m(...):  # lock-held:
# _lock` declares a method whose CALLERS hold the lock (the body is
# treated as locked). Both are per-line, next to the code they govern.
GUARDED_BY_RE = re.compile(r"#\s*guarded-by:\s*([A-Za-z_]\w*)")
LOCK_HELD_RE = re.compile(r"#\s*lock-held:\s*([A-Za-z_]\w*)")


@dataclasses.dataclass
class ParsedFile:
    path: str                 # repo-relative, forward slashes
    abspath: str
    source: str
    lines: List[str]
    tree: Optional[ast.AST]   # None when the file failed to parse
    parse_error: Optional[str] = None

    def guarded_by(self, lineno: int) -> Optional[str]:
        m = GUARDED_BY_RE.search(self._line(lineno))
        return m.group(1) if m else None

    def lock_held(self, lineno: int) -> Optional[str]:
        m = LOCK_HELD_RE.search(self._line(lineno))
        return m.group(1) if m else None

    def _line(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ""


@dataclasses.dataclass
class CheckConfig:
    """Everything the rules need to know about one tree. Built for the
    real repo by `runner.default_config`; fixture tests construct it by
    hand against a temp directory."""

    root: str
    # Directories/files (repo-relative) the AST rules scan.
    scan_roots: Tuple[str, ...] = ("proteinbert_tpu", "tools")
    # Files under the tmp→fsync→rename durability contract (rule 3).
    durability_files: Tuple[str, ...] = (
        "proteinbert_tpu/mapper/store.py",
        "proteinbert_tpu/train/checkpoint.py",
    )
    # The event schema's single source of truth (rule 4), parsed by
    # AST — never imported, so the checker stays jax-free even though
    # importing obs pulls the package root (which imports jax).
    events_py: str = "proteinbert_tpu/obs/events.py"
    # The observability reference both drift directions check (rule 5).
    docs_md: str = "docs/observability.md"
    # Extra corpus consulted when deciding whether an export is dead
    # (rule 6) — tests/examples legitimately keep an export alive.
    reference_roots: Tuple[str, ...] = (
        "proteinbert_tpu", "tools", "tests", "examples", "experiments",
    )
    # Functions allowed to read os.environ at trace time (rule 1): the
    # documented trace-time readers, e.g. PBT_FORCE_REFERENCE_KERNEL's.
    sanctioned_env_readers: Tuple[str, ...] = (
        "force_reference_requested",)
    # Metric/event names the doc may mention without a live
    # registration (rule 5) — documented-as-removed history.
    docs_allow: Tuple[str, ...] = ("fused_kernel_fallback_total",)

    def abspath(self, rel: str) -> str:
        return os.path.join(self.root, rel)


class CheckContext:
    def __init__(self, cfg: CheckConfig):
        self.cfg = cfg
        self.errors: List[str] = []
        self._cache: Dict[str, ParsedFile] = {}
        self.files: List[ParsedFile] = []
        for rel in sorted(_walk_py(cfg.root, cfg.scan_roots)):
            pf = self.load(rel)
            if pf is not None:
                self.files.append(pf)

    # ------------------------------------------------------------ loading

    def load(self, rel: str) -> Optional[ParsedFile]:
        """Parse one repo-relative file (cached). Unreadable files are
        context errors (exit 2); unparseable ones carry parse_error and
        become findings in the runner (a syntax error in a scanned file
        must fail the gate, not vanish)."""
        rel = rel.replace(os.sep, "/")
        if rel in self._cache:
            return self._cache[rel]
        abspath = self.cfg.abspath(rel)
        try:
            with open(abspath, encoding="utf-8") as f:
                source = f.read()
        except OSError as e:
            self.errors.append(f"{rel}: unreadable: {e}")
            self._cache[rel] = None  # type: ignore[assignment]
            return None
        tree: Optional[ast.AST] = None
        parse_error: Optional[str] = None
        try:
            tree = ast.parse(source, filename=rel)
        except SyntaxError as e:
            parse_error = f"line {e.lineno}: {e.msg}"
        pf = ParsedFile(path=rel, abspath=abspath, source=source,
                        lines=source.splitlines(), tree=tree,
                        parse_error=parse_error)
        self._cache[rel] = pf
        return pf

    def read_text(self, rel: str) -> Optional[str]:
        try:
            with open(self.cfg.abspath(rel), encoding="utf-8") as f:
                return f.read()
        except OSError:
            return None

    # ------------------------------------------- identifier index (rule 6)

    def identifier_index(self) -> Dict[str, Set[str]]:
        """{repo-relative path: every identifier the file mentions}
        over the reference corpus — Name ids, Attribute attrs, and
        import names. Coarse by design: the dead-export sweep must err
        toward 'used', never flag a live name."""
        index: Dict[str, Set[str]] = {}
        for rel in sorted(_walk_py(self.cfg.root,
                                   self.cfg.reference_roots)):
            pf = self.load(rel)
            if pf is None or pf.tree is None:
                continue
            ids: Set[str] = set()
            for node in ast.walk(pf.tree):
                if isinstance(node, ast.Name):
                    ids.add(node.id)
                elif isinstance(node, ast.Attribute):
                    ids.add(node.attr)
                elif isinstance(node, (ast.Import, ast.ImportFrom)):
                    for alias in node.names:
                        ids.add(alias.name.split(".")[0]
                                if isinstance(node, ast.Import)
                                else alias.name)
                        if alias.asname:
                            ids.add(alias.asname)
            index[rel] = ids
        return index


def _walk_py(root: str, rel_roots: Iterable[str]) -> List[str]:
    out: List[str] = []
    for rel in rel_roots:
        top = os.path.join(root, rel)
        if os.path.isfile(top) and rel.endswith(".py"):
            out.append(rel.replace(os.sep, "/"))
            continue
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = [d for d in dirnames
                           if d != "__pycache__"
                           and not d.startswith(".")]
            for fn in filenames:
                if fn.endswith(".py"):
                    full = os.path.join(dirpath, fn)
                    out.append(os.path.relpath(full, root)
                               .replace(os.sep, "/"))
    return out


# ----------------------------------------------------- small AST helpers

def dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def qualname(stack: List[str], name: str) -> str:
    return ".".join(stack + [name]) if stack else name
