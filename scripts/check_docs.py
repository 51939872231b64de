"""Docs-contract CI gate (ISSUE 5): the §-reference convention, enforced.

Since PR 1 the repo's docstrings cite design rationale as
``DESIGN.md §x.y`` and measured results as ``EXPERIMENTS.md §Name`` —
stable section anchors a reader can follow.  That convention only stays
trustworthy if it cannot rot, so this gate makes three things CI-failing
facts instead of habits:

  1. **Every §-reference resolves.**  Each ``DESIGN.md §x.y`` /
     ``EXPERIMENTS.md §Name`` citation anywhere under ``src/`` must name
     a real heading of the cited document — a renamed or deleted section
     dangles its citations and fails here.
  2. **The README repo map is complete.**  Every ``src/repro/**`` module
     (every ``.py`` except ``__init__.py``) must be named in README.md —
     a new module that nobody added to the map fails here.
  3. **CHANGES.md moves with the PR.**  A line starting ``PR <N>`` must
     exist for the current PR number, so the next session always finds a
     record of this one.
  4. **The representation registry is fully documented and fully
     conformance-tested.**  Every ``name = "..."`` registered in
     ``core/representation.py`` must appear in DESIGN.md §11 and in
     ``tests/test_representations.py`` (whose property grid runs over
     ``registered_names()`` automatically — this check catches the
     suite being bypassed, e.g. a registration moved out of the
     module the tests import).

Pure stdlib; run from anywhere:

    python scripts/check_docs.py            # exit 0 = contract holds
"""
from __future__ import annotations

import pathlib
import re
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]

# The PR this checkout is being built as — bump alongside the CHANGES.md
# entry (the gate exists precisely so forgetting one of the two fails).
CURRENT_PR = 11

DESIGN_HEADING = re.compile(r"^#{2,3} §([0-9]+(?:\.[0-9]+)?)\b",
                            re.MULTILINE)
EXPERIMENTS_HEADING = re.compile(r"^#{2,3} §([A-Za-z][\w-]*)", re.MULTILINE)
DESIGN_REF = re.compile(r"DESIGN\.md\s+§([0-9]+(?:\.[0-9]+)?)")
EXPERIMENTS_REF = re.compile(r"EXPERIMENTS\.md\s+§([A-Za-z][\w-]*)")


def fail(errors: list, msg: str):
    errors.append(msg)
    print(f"[docs] FAIL: {msg}")


def check_section_refs(errors: list):
    design = (REPO / "DESIGN.md").read_text()
    experiments = (REPO / "EXPERIMENTS.md").read_text()
    design_secs = set(DESIGN_HEADING.findall(design))
    exp_secs = set(EXPERIMENTS_HEADING.findall(experiments))
    if not design_secs or not exp_secs:
        fail(errors, "no § headings parsed from DESIGN.md/EXPERIMENTS.md")
        return
    n_refs = 0
    for py in sorted((REPO / "src").rglob("*.py")):
        text = py.read_text()
        rel = py.relative_to(REPO)
        for sec in DESIGN_REF.findall(text):
            n_refs += 1
            if sec not in design_secs:
                fail(errors, f"{rel}: DESIGN.md §{sec} does not resolve "
                             f"(have: {sorted(design_secs)})")
        for sec in EXPERIMENTS_REF.findall(text):
            n_refs += 1
            if sec not in exp_secs:
                fail(errors, f"{rel}: EXPERIMENTS.md §{sec} does not "
                             f"resolve (have: {sorted(exp_secs)})")
    print(f"[docs] {n_refs} §-references checked against "
          f"{len(design_secs)} DESIGN + {len(exp_secs)} EXPERIMENTS "
          f"sections")


def check_repo_map(errors: list):
    readme = (REPO / "README.md").read_text()
    missing = []
    modules = [m for m in sorted((REPO / "src" / "repro").rglob("*.py"))
               if m.name != "__init__.py"]
    for py in modules:
        # A standalone mention is required: 'sax.py' inside 'fastsax.py'
        # must NOT count, or a suffix-named module could silently drop
        # out of the map (the lookbehind rejects any word/path character
        # immediately before the name).
        if not re.search(rf"(?<![\w./-]){re.escape(py.name)}", readme):
            missing.append(str(py.relative_to(REPO / "src")))
    for mod in missing:
        fail(errors, f"{mod}: module not named in the README repo map")
    print(f"[docs] README repo map covers {len(modules)} modules")


REP_NAME = re.compile(r'^\s+name\s*=\s*"([a-z][a-z0-9_]*)"', re.MULTILINE)


def check_registry(errors: list):
    """Every registered representation name must appear in DESIGN.md §11
    and in the conformance suite (tests/test_representations.py)."""
    reg_src = (REPO / "src/repro/core/representation.py").read_text()
    names = REP_NAME.findall(reg_src)
    if not names:
        fail(errors, "no registered representation names parsed from "
                     "core/representation.py")
        return
    design = (REPO / "DESIGN.md").read_text()
    sec11 = design.split("## §11", 1)
    sec11 = sec11[1] if len(sec11) == 2 else ""
    tests_path = REPO / "tests" / "test_representations.py"
    tests = tests_path.read_text() if tests_path.exists() else ""
    if not tests:
        fail(errors, "tests/test_representations.py missing — the "
                     "registry conformance suite is the soundness gate")
    for name in names:
        if f"`{name}`" not in sec11 and name not in sec11:
            fail(errors, f"representation {name!r} not documented in "
                         f"DESIGN.md §11")
        if tests and name not in tests \
                and "registered_names()" not in tests:
            fail(errors, f"representation {name!r} not covered by "
                         f"tests/test_representations.py")
    print(f"[docs] registry complete: {len(names)} representation(s) "
          f"documented in DESIGN.md §11 and conformance-tested")


def check_changes(errors: list):
    changes = (REPO / "CHANGES.md").read_text()
    if not re.search(rf"^PR {CURRENT_PR}\b", changes, re.MULTILINE):
        fail(errors, f"CHANGES.md has no 'PR {CURRENT_PR}' line — record "
                     f"this PR for the next session")
    else:
        print(f"[docs] CHANGES.md records PR {CURRENT_PR}")


def main() -> int:
    errors: list = []
    check_section_refs(errors)
    check_repo_map(errors)
    check_registry(errors)
    check_changes(errors)
    if errors:
        print(f"[docs] {len(errors)} failure(s)")
        return 1
    print("[docs] PASS — §-references resolve, repo map complete, "
          "CHANGES.md current")
    return 0


if __name__ == "__main__":
    sys.exit(main())
