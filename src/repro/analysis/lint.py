"""Source layering linter: AST-based rules that keep the repo's
layering doctrine machine-enforced.

Rules (each independently selectable; ``tools/lint.py`` is the CLI):

  * ``compat-only``   -- version-specific JAX symbols (shard_map,
    mesh_utils, the ``*_with_path`` tree family, optimization_barrier,
    fp8 dtype names) are imported/used ONLY inside ``repro.compat``;
    ``jax.experimental.pallas`` is additionally allowed in the
    ``kernels/`` tier, whose whole job is backend-specific code.
  * ``quant-blockwise`` -- hot paths must go through ``repro.kernels.ops``;
    direct ``quant.blockwise`` imports are allowed only in ``kernels/``
    (built on the reference), ``quant/`` itself, and ``tests/`` (parity
    suites).  Generalizes the retired ``tools/check_quant_imports.py``.
  * ``bare-assert``   -- no ``assert`` statements in non-test source:
    ``python -O`` strips them, so config/validation paths must raise.
  * ``parity-tags``   -- every wire/kernel primitive declares its parity
    class via a ``PARITY: BITWISE|ALLCLOSE`` docstring tag, and any tag
    whose subject DESIGN.md's §Kernels table also names must agree with
    the table (the doctrine artifact and the code can't drift apart).
  * ``tracked-bytecode`` -- no ``*.pyc`` / ``__pycache__`` tracked by
    git (repo-hygiene regression guard).

Each finding is a ``LintError`` (path, line, rule, message).  The rule
set is a registry: new layering rules subclass nothing -- they are
functions registered in ``RULES`` with a name and a docstring.
"""
from __future__ import annotations

import ast
import dataclasses
import re
import subprocess
import sys
from pathlib import Path
from typing import Callable, Iterable, Iterator, Optional

# --------------------------------------------------------------------------- #
# findings
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class LintError:
    path: str   # repo-relative
    line: int
    rule: str
    msg: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.msg}"


# --------------------------------------------------------------------------- #
# shared AST helpers
# --------------------------------------------------------------------------- #
def _dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` attribute chain as a dotted string (None if the chain
    bottoms out in anything but a Name)."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _imported_dotted(tree: ast.AST) -> Iterator[tuple[int, str]]:
    """Every imported dotted name with its line: ``import a.b`` ->
    ``a.b``; ``from a.b import c`` -> ``a.b.c`` (and ``a.b`` itself);
    relative levels are preserved as leading dots so callers can match
    in-package imports."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield node.lineno, alias.name
        elif isinstance(node, ast.ImportFrom):
            base = "." * node.level + (node.module or "")
            yield node.lineno, base
            for alias in node.names:
                yield node.lineno, f"{base}.{alias.name}" if base else alias.name


# --------------------------------------------------------------------------- #
# rule: compat-only
# --------------------------------------------------------------------------- #
#: dotted-prefix -> the compat entry point to use instead
_VERSIONED = {
    "jax.experimental.shard_map": "repro.compat.shard_map",
    "jax.experimental.mesh_utils": "repro.compat.make_mesh",
    "jax.experimental.pallas": "the kernels/ tier (backend-specific code)",
    "jax.experimental.maps": "repro.compat",
    "jax.core": "repro.compat",
    "jax.extend.core": "repro.compat.ClosedJaxpr / repro.compat.Jaxpr",
    "jax.tree_util.tree_map_with_path": "repro.compat.tree_map_with_path",
    "jax.tree_util.tree_flatten_with_path":
        "repro.compat.tree_flatten_with_path",
    "jax.tree.map_with_path": "repro.compat.tree_map_with_path",
    "jax.tree.flatten_with_path": "repro.compat.tree_flatten_with_path",
    "jax.lax.optimization_barrier": "repro.compat.optimization_barrier",
    "jax.numpy.float8_e4m3fn": "repro.compat.float8_dtypes",
    "jax.numpy.float8_e5m2": "repro.compat.float8_dtypes",
    "jnp.float8_e4m3fn": "repro.compat.float8_dtypes",
    "jnp.float8_e5m2": "repro.compat.float8_dtypes",
}
#: path-prefix exemptions per banned prefix (compat.py is globally exempt)
_VERSIONED_ALLOWED = {
    "jax.experimental.pallas": ("src/repro/kernels/",),
}


def check_compat_only(rel: str, tree: ast.AST, src: str) -> list[LintError]:
    """Version-specific JAX symbols only via repro.compat."""
    if rel == "src/repro/compat.py":
        return []
    errs = []
    seen: set[tuple[int, str]] = set()  # one finding per (line, prefix)

    def hit(line: int, name: str) -> None:
        for banned, repl in _VERSIONED.items():
            if name == banned or name.startswith(banned + "."):
                if any(rel.startswith(p) for p in
                       _VERSIONED_ALLOWED.get(banned, ())):
                    return
                if (line, banned) in seen:
                    return
                seen.add((line, banned))
                errs.append(LintError(
                    rel, line, "compat-only",
                    f"version-specific JAX symbol {banned!r}; use {repl}"))
                return

    for line, name in _imported_dotted(tree):
        hit(line, name)
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute):
            name = _dotted(node)
            if name:
                hit(node.lineno, name)
    return errs


# --------------------------------------------------------------------------- #
# rule: quant-blockwise
# --------------------------------------------------------------------------- #
_QUANT_ALLOWED = ("src/repro/kernels/", "src/repro/quant/", "tests/")


def check_quant_blockwise(rel: str, tree: ast.AST, src: str) -> list[LintError]:
    """Hot paths import repro.kernels.ops, never quant.blockwise."""
    if any(rel.startswith(p) for p in _QUANT_ALLOWED):
        return []
    errs = []
    seen: set[int] = set()  # one finding per import line
    for line, name in _imported_dotted(tree):
        bare = name.lstrip(".")
        if (bare in ("quant", "quant.blockwise", "repro.quant",
                     "repro.quant.blockwise")
                or bare.startswith(("quant.blockwise.",
                                    "repro.quant.blockwise."))):
            if line in seen:
                continue
            seen.add(line)
            errs.append(LintError(
                rel, line, "quant-blockwise",
                f"direct reference-oracle import {name!r}; hot paths go "
                f"through repro.kernels.ops (repro.kernels.ref for "
                f"deliberate unfused ablations)"))
    return errs


# --------------------------------------------------------------------------- #
# rule: bare-assert
# --------------------------------------------------------------------------- #
def check_bare_assert(rel: str, tree: ast.AST, src: str) -> list[LintError]:
    """No ``assert`` in non-test source: ``python -O`` strips them."""
    return [LintError(rel, node.lineno, "bare-assert",
                      "bare assert in non-test code (stripped under "
                      "python -O); raise ValueError/RuntimeError")
            for node in ast.walk(tree) if isinstance(node, ast.Assert)]


# --------------------------------------------------------------------------- #
# rule: parity-tags
# --------------------------------------------------------------------------- #
_PARITY_RE = re.compile(r"PARITY:\s*(\w+)")
_PARITY_CLASSES = ("BITWISE", "ALLCLOSE")
#: modules whose comm/codec primitives MUST carry a tag, and which
#: function names count as primitives there
_PARITY_REQUIRED = {
    "src/repro/core/wire.py": re.compile(
        r"^(_ring_(all_gather|reduce_scatter|acc_reduce_scatter)"
        r"|_q8_(route|ring_acc)_reduce_scatter"
        r"|dtype_reduce_scatter|codec_reduce_scatter"
        r"|payload_all_gather|codec_gather(_ef|_defer_ef)?"
        r"|codec_grad_proxy(_ef|_defer_ef)?|sharded_gather)$"),
    "src/repro/kernels/ops.py": re.compile(r"^[a-z]\w*$"),
}
#: DESIGN.md rows: "| ... `ops.<name>` ... | BITWISE/ALLCLOSE |"
_DESIGN_ROW_RE = re.compile(
    r"`ops\.(\w+)`[^|]*\|\s*(BITWISE|ALLCLOSE)\s*\|")


def _design_parity_table(root: Path) -> dict[str, str]:
    doc = root / "DESIGN.md"
    if not doc.exists():
        return {}
    out: dict[str, str] = {}
    for m in _DESIGN_ROW_RE.finditer(doc.read_text()):
        out[m.group(1)] = m.group(2)
    return out


def make_parity_rule(root: Path) -> Callable:
    design = _design_parity_table(root)

    def check_parity_tags(rel: str, tree: ast.AST, src: str) -> list[LintError]:
        """Wire/kernel primitives declare PARITY class; DESIGN.md agrees."""
        required = _PARITY_REQUIRED.get(rel)
        errs = []
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            doc = ast.get_docstring(node) or ""
            m = _PARITY_RE.search(doc)
            if m is None:
                if required is not None and required.match(node.name):
                    errs.append(LintError(
                        rel, node.lineno, "parity-tags",
                        f"comm/codec primitive {node.name!r} has no "
                        f"'PARITY: BITWISE|ALLCLOSE' docstring tag "
                        f"(DESIGN.md §Static analysis)"))
                continue
            cls = m.group(1)
            if cls not in _PARITY_CLASSES:
                errs.append(LintError(
                    rel, node.lineno, "parity-tags",
                    f"{node.name!r} declares unknown parity class {cls!r} "
                    f"(one of {_PARITY_CLASSES})"))
            elif design.get(node.name, cls) != cls:
                errs.append(LintError(
                    rel, node.lineno, "parity-tags",
                    f"{node.name!r} tagged PARITY: {cls} but DESIGN.md's "
                    f"§Kernels table declares {design[node.name]}"))
        return errs

    return check_parity_tags


# --------------------------------------------------------------------------- #
# rule: tracked-bytecode (repo-level)
# --------------------------------------------------------------------------- #
def check_tracked_bytecode(root: Path) -> list[LintError]:
    """No git-tracked *.pyc / __pycache__ entries."""
    try:
        out = subprocess.run(["git", "ls-files"], cwd=root, check=True,
                             capture_output=True, text=True).stdout
    except (OSError, subprocess.CalledProcessError):
        return []  # not a git checkout (sdist, CI artifact dir): nothing to do
    return [LintError(f, 0, "tracked-bytecode",
                      "compiled bytecode tracked by git; `git rm --cached` "
                      "it (covered by .gitignore)")
            for f in out.splitlines()
            if f.endswith((".pyc", ".pyo")) or "__pycache__" in f]


# --------------------------------------------------------------------------- #
# driver
# --------------------------------------------------------------------------- #
#: file-level rules: name -> factory(root) -> check(rel, tree, src)
RULES: dict[str, Callable[[Path], Callable]] = {
    "compat-only": lambda root: check_compat_only,
    "quant-blockwise": lambda root: check_quant_blockwise,
    "bare-assert": lambda root: check_bare_assert,
    "parity-tags": make_parity_rule,
}
#: repo-level rules: name -> check(root)
REPO_RULES: dict[str, Callable[[Path], list]] = {
    "tracked-bytecode": check_tracked_bytecode,
}

#: default scan surface (tests/ keep their asserts and oracle imports)
DEFAULT_SCAN = ("src", "benchmarks", "tools")


def run_lint(root, paths: Optional[Iterable] = None,
             select: Optional[Iterable[str]] = None) -> list[LintError]:
    """Run the selected rules (default: all) over ``paths`` (default:
    ``DEFAULT_SCAN`` under ``root``); returns all findings sorted by
    location."""
    root = Path(root).resolve()
    names = list(select) if select else [*RULES, *REPO_RULES]
    unknown = set(names) - set(RULES) - set(REPO_RULES)
    if unknown:
        raise ValueError(f"unknown lint rules: {sorted(unknown)}; "
                         f"available: {sorted([*RULES, *REPO_RULES])}")
    checks = [RULES[n](root) for n in names if n in RULES]

    if paths is None:
        files = [p for top in DEFAULT_SCAN
                 for p in sorted((root / top).rglob("*.py"))
                 if (root / top).exists()]
    else:
        files = []
        for p in paths:
            p = Path(p)
            p = p if p.is_absolute() else root / p
            files += sorted(p.rglob("*.py")) if p.is_dir() else [p]

    errs: list[LintError] = []
    for py in files:
        rel = py.resolve().relative_to(root).as_posix()
        src = py.read_text()
        try:
            tree = ast.parse(src, filename=str(py))
        except SyntaxError as e:
            errs.append(LintError(rel, e.lineno or 0, "syntax",
                                  f"unparseable: {e.msg}"))
            continue
        for check in checks:
            errs.extend(check(rel, tree, src))
    for n in names:
        if n in REPO_RULES:
            errs.extend(REPO_RULES[n](root))
    return sorted(errs, key=lambda e: (e.path, e.line, e.rule))


def main(argv: Optional[list[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="layering linter (repro.analysis.lint)")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: src benchmarks tools)")
    ap.add_argument("--root", default=None,
                    help="repo root (default: two levels above this package)")
    ap.add_argument("--select", action="append", default=None,
                    metavar="RULE", help="run only these rules")
    args = ap.parse_args(argv)
    # lint.py sits at <root>/src/repro/analysis/lint.py
    root = Path(args.root) if args.root else \
        Path(__file__).resolve().parents[3]
    errs = run_lint(root, paths=args.paths or None, select=args.select)
    for e in errs:
        print(e)
    rules = ", ".join(args.select or [*RULES, *REPO_RULES])
    if errs:
        print(f"lint: {len(errs)} finding(s) [{rules}]")
        return 1
    print(f"lint ok [{rules}]")
    return 0


if __name__ == "__main__":
    sys.exit(main())
