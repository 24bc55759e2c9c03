"""Tier-1 gate: the package itself must stay jaxlint-clean.

Any non-baselined finding fails this test — fix the finding, add a
justified inline suppression, or (for genuine tracked debt only) baseline
it. See docs/linting.md for the workflow.
"""

import os
import re

from bigdl_tpu.lint import DEFAULT_BASELINE_PATH, lint_paths, load_baseline

PACKAGE_DIR = os.path.dirname(
    os.path.abspath(__import__("bigdl_tpu").__file__))


def test_package_has_no_new_findings():
    result = lint_paths([PACKAGE_DIR])
    assert result.errors == []
    assert result.files_checked > 50  # the walker actually saw the package
    msgs = "\n".join(str(f) for f in result.new_findings)
    assert result.new_findings == [], (
        f"jaxlint found new trace-hygiene violations:\n{msgs}\n"
        f"Fix them (preferred), suppress with a justified "
        f"'# jaxlint: disable=<rule>', or baseline genuine debt via "
        f"scripts/lint.sh --write-baseline.")


def test_baseline_carries_no_stale_entries():
    """Every baselined fingerprint still matches a real finding — stale
    entries mean someone fixed the code without shrinking the baseline,
    which would mask one future regression each."""
    result = lint_paths([PACKAGE_DIR], baseline_path=None)
    live = {f.fingerprint for f in result.findings}
    stale = [fp for fp in load_baseline(DEFAULT_BASELINE_PATH)
             if fp not in live]
    assert stale == [], (
        f"baseline entries no longer observed (remove them from "
        f"{DEFAULT_BASELINE_PATH}): {stale}")


def test_interprocedural_rule_catalog_is_registered():
    """The v2 gate runs the FULL rule set: if a rules-list refactor
    drops one of the interprocedural families, the clean-package test
    above would pass vacuously — pin the catalog here."""
    from bigdl_tpu.lint.rules import RULES_BY_NAME

    expected = {
        # donation-ownership family
        "alias-into-donation",
        "use-after-donate",
        "escaping-donated-ref",
        # thread-ownership family
        "unlocked-shared-mutation",
        "foreign-thread-device-access",
        "lock-across-dispatch",
        # v3: mesh/sharding consistency
        "spec-axis-not-in-mesh",
        "collective-axis-undeclared",
        "shardmap-spec-mismatch",
        "jit-missing-out-shardings",
        "silent-replicate",
        # v3: pallas kernel safety
        "pallas-blockspec-arity",
        "pallas-prefetch-arity",
        "pallas-scratch-uninit",
        "pallas-vmem-budget",
        "pallas-missing-interpret",
        # v3: flag registry
        "flag-unregistered",
        "flag-undocumented",
        "raw-environ-read",
    }
    missing = expected - set(RULES_BY_NAME)
    assert missing == set(), f"rules dropped from the catalog: {missing}"


# Records of what the repo was (they name deleted scripts as history) and
# descriptions of the reference upstream (they name its files, not ours).
_HISTORY = {"CHANGES.md", "PERF.md", "ROADMAP.md", "ISSUE.md", "REVIEW.md",
            "SURVEY.md", "PAPER.md", "PAPERS.md", "SNIPPETS.md"}
# Bare names that are files of another project or a document's own example.
_FOREIGN = {"modeling_lfm2_moe.py",      # transformers' file (models/lfm2.py)
            "ckptio.py", "trainer.py"}   # docs/linting.md's worked example
_NOT_THE_TREE = {".git", "_parent", "_archive", "chiprun_out", ".jax_cache",
                 ".bench_scratch", "__pycache__", ".pytest_cache",
                 ".hypothesis"}


def test_documents_and_code_name_no_script_that_is_not_in_the_tree():
    """A ``scripts/<name>.py`` must be that file; a bare ``<name>.py`` (how
    the documents name ``chip_smoke.py`` and the other scripts at the root)
    must be a file somewhere in the tree. A deleted script leaves no
    pointer behind in a guide, the package, an example or a script."""
    root = os.path.dirname(PACKAGE_DIR)
    tree = set()
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x not in _NOT_THE_TREE]
        tree.update(os.path.relpath(os.path.join(d, f), root).replace(
            os.sep, "/") for f in files)
    names = {p.rsplit("/", 1)[-1] for p in tree} | _FOREIGN
    guides = [p for p in tree if p.endswith(".md") and p not in _HISTORY
              and ("/" not in p or p.startswith(("docs/", ".claude/")))]
    code = [p for p in tree if p.endswith(".py")
            and p.startswith(("bigdl_tpu/", "examples/", "scripts/"))]
    assert "README.md" in guides and len(code) > 100
    script = re.compile(r"(?<![\w./-])(scripts/)?([A-Za-z_]\w*\.py)\b")
    dangling = {}
    for p in sorted(guides + code):
        with open(os.path.join(root, p), errors="replace") as f:
            text = f.read()
        bad = sorted({m.group(0) for m in script.finditer(text)
                      if (m.group(0) not in tree if m.group(1) else
                          m.group(2) not in names)})
        if bad:
            dangling[p] = bad
    assert dangling == {}, dangling
