"""Docs freshness + link integrity + the sparse docstring gate.

These tests keep docs/ honest without a docs build: every module the
architecture guide names must exist, every intra-repo link must resolve
(same checker CI runs), and src/repro/sparse/ + src/repro/launch/ must
stay clean under the missing-docstring pydocstyle subset wired into ruff
(mirrored here in AST form so it is enforced even where ruff isn't
installed).
"""
import ast
import importlib.util
import pathlib
import re

ROOT = pathlib.Path(__file__).resolve().parent.parent
DOCS = ROOT / "docs"


def _load_check_links():
    spec = importlib.util.spec_from_file_location(
        "check_links", ROOT / "tools" / "check_links.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------- #
# Freshness: what architecture.md names must exist.
# --------------------------------------------------------------------- #

def test_docs_exist():
    for name in ("architecture.md", "roofline.md", "serving.md",
                 "serving_engine.md", "sharding.md"):
        assert (DOCS / name).is_file(), f"docs/{name} missing"


def test_architecture_modules_exist():
    """Every backticked repro.* dotted name in docs/architecture.md must
    resolve to a module or package under src/."""
    text = (DOCS / "architecture.md").read_text(encoding="utf-8")
    names = set(re.findall(r"`(repro(?:\.[a-z_0-9]+)+)`", text))
    assert len(names) >= 15, "architecture.md lost its module map"
    missing = []
    for name in sorted(names):
        rel = name.replace(".", "/")
        if not ((ROOT / "src" / f"{rel}.py").is_file()
                or (ROOT / "src" / rel / "__init__.py").is_file()):
            missing.append(name)
    assert not missing, f"architecture.md names missing modules: {missing}"


def test_architecture_file_paths_exist():
    """Backticked repo paths (chipbench/..., tests/, .github/...) too."""
    text = "\n".join((DOCS / d).read_text(encoding="utf-8")
                     for d in ("architecture.md", "serving.md"))
    paths = set(re.findall(r"`([A-Za-z0-9_./-]+\.(?:py|yml|md))`", text))
    missing = [p for p in sorted(paths) if not (ROOT / p).exists()]
    assert not missing, f"docs name missing files: {missing}"


# --------------------------------------------------------------------- #
# Link integrity (the same checker the CI docs job runs).
# --------------------------------------------------------------------- #

def test_repo_markdown_links_resolve():
    cl = _load_check_links()
    broken = {}
    for f in cl.default_files(ROOT):
        b = cl.broken_links(f, ROOT)
        if b:
            broken[str(f.relative_to(ROOT))] = b
    assert not broken, f"broken intra-repo links: {broken}"


def test_link_checker_catches_breaks(tmp_path):
    cl = _load_check_links()
    (tmp_path / "a file.md").write_text("here")
    md = tmp_path / "x.md"
    md.write_text("# Frag\n"
                  "ok [a](https://example.com) [b](#frag)\n"
                  "bad [c](missing.md) img ![d](gone.png)\n"
                  "spaces ok [e](a file.md) [f](a%20file.md)\n"
                  "spaces bad [g](no such.md)\n")
    broken = cl.broken_links(md, tmp_path)
    assert [t for _, t in broken] == ["missing.md", "gone.png",
                                      "no such.md"]
    assert broken[0][0] == 3
    assert cl.main([str(md)]) == 1


# --------------------------------------------------------------------- #
# Anchor fragments: GitHub-style heading slugs (ROADMAP item).
# --------------------------------------------------------------------- #

def test_heading_slugs_match_github_rules():
    cl = _load_check_links()
    assert cl.slugify("Install") == "install"
    assert cl.slugify("The `plan`/`execute` API!") == "the-planexecute-api"
    assert cl.slugify("Ceilings: bandwidth & compute") == \
        "ceilings-bandwidth--compute"
    assert cl.slugify("A [link](x.md) in a heading") == \
        "a-link-in-a-heading"
    text = ("# Usage\n"
            "## Usage\n"          # duplicate -> -1 suffix
            "```\n# not a heading (code fence)\n```\n"
            "### Deep *emphasis* heading\n")
    anchors = cl.heading_anchors(text)
    assert anchors == {"usage", "usage-1", "deep-emphasis-heading"}


def test_anchor_fragments_are_verified(tmp_path):
    cl = _load_check_links()
    (tmp_path / "other.md").write_text("# Real Section\nbody\n")
    md = tmp_path / "x.md"
    md.write_text(
        "# My Title\n"
        "good [a](#my-title) [b](other.md#real-section)\n"
        "bad [c](#no-such-heading) [d](other.md#missing-anchor)\n"
        "external untouched [e](https://x.test/page#frag)\n"
        "non-md target fragment skipped [f](x.py#L10)\n")
    (tmp_path / "x.py").write_text("pass\n")
    broken = cl.broken_links(md, tmp_path)
    assert [t for _, t in broken] == ["#no-such-heading",
                                      "other.md#missing-anchor"]
    assert all(line == 3 for line, _ in broken)
    assert cl.main([str(md)]) == 1


# --------------------------------------------------------------------- #
# Docstring gate: the ruff D subset for src/repro/sparse/, in AST form.
# --------------------------------------------------------------------- #

def _public_defs_missing_docstrings(tree):
    missing = []

    def walk(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                qual = f"{prefix}{child.name}"
                # D100-D104 scope: public names only; _private and dunder
                # defs (D105/D107 territory) are out of the selected set,
                # and so are function-local closures — recurse into class
                # bodies only, matching what ruff checks.
                public = not child.name.startswith("_")
                if public and ast.get_docstring(child) is None:
                    missing.append(qual)
                if isinstance(child, ast.ClassDef):
                    walk(child, f"{qual}.")
            else:
                walk(child, prefix)

    walk(tree, "")
    return missing


def test_sparse_package_docstring_clean():
    """Mirror of the ruff D100-D104 gate on src/repro/sparse/ and
    src/repro/launch/ (CI lints them with ruff; this keeps the gate
    active in ruff-less environments)."""
    failures = []
    for pkg in ("sparse", "launch"):
        for path in sorted((ROOT / "src" / "repro" / pkg).glob("*.py")):
            rel = f"{pkg}/{path.name}"
            tree = ast.parse(path.read_text(encoding="utf-8"))
            if ast.get_docstring(tree) is None:
                failures.append(f"{rel}: module docstring")
            failures += [f"{rel}: {q}"
                         for q in _public_defs_missing_docstrings(tree)]
    assert not failures, f"missing docstrings: {failures}"
