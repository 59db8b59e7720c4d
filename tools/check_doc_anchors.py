"""Fail CI when DESIGN.md cross-references drift.

Every ``DESIGN.md#<anchor>`` markdown link and every textual
``DESIGN.md §N`` section reference found in README.md and docs/API.md -
plus every ``§N`` mention inside DESIGN.md itself, and the ``DESIGN.md
§N`` pointers embedded in source docstrings of the phylint tooling and
the CI workflow - must resolve to a real DESIGN.md heading.  Run by the
``docs`` CI job next to the generated-API staleness gate, and by
tests/test_docs.py, so renaming or
deleting a DESIGN.md section without fixing its referrers fails the
build.

    python tools/check_doc_anchors.py
"""
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

# files scanned for references into DESIGN.md; the source files carry
# rule-catalogue pointers ("DESIGN.md §12") in their docstrings and
# diagnostics, and must not rot when sections are renumbered
REFERRERS = [
    "README.md",
    "docs/API.md",
    "DESIGN.md",
    "src/repro/analysis/__init__.py",
    "src/repro/analysis/lint.py",
    "src/repro/analysis/sanitize.py",
    "tools/phylint.py",
    ".github/workflows/ci.yml",
]


def github_slug(heading: str) -> str:
    """GitHub's heading -> anchor rule: lowercase, drop everything but
    word characters / hyphens / spaces, then spaces -> hyphens."""
    s = heading.strip().lower()
    s = re.sub(r"[^\w\- ]", "", s)
    return s.replace(" ", "-")


def design_targets() -> tuple[set, set]:
    """(anchor slugs, §-section numbers) defined by DESIGN.md headings."""
    text = (ROOT / "DESIGN.md").read_text()
    headings = re.findall(r"^#{1,6}\s+(.+)$", text, re.M)
    slugs = {github_slug(h) for h in headings}
    sections = set(re.findall(r"§(\d+)", " ".join(headings)))
    return slugs, sections


def main() -> int:
    slugs, sections = design_targets()
    bad = []
    for name in REFERRERS:
        path = ROOT / name
        if not path.exists():
            bad.append(f"{name}: referenced file is missing")
            continue
        text = path.read_text()
        for m in re.finditer(r"DESIGN\.md#([A-Za-z0-9_\-]+)", text):
            if m.group(1) not in slugs:
                bad.append(f"{name}: dead anchor DESIGN.md#{m.group(1)}")
        pat = (r"§(\d+)" if name == "DESIGN.md"
               else r"DESIGN\.md\s+§(\d+)")
        for m in re.finditer(pat, text):
            if m.group(1) not in sections:
                bad.append(f"{name}: DESIGN.md §{m.group(1)} does not exist")
    if bad:
        print("DESIGN.md cross-reference check FAILED:")
        for b in bad:
            print(f"  {b}")
        return 1
    span = (f"§{min(sections, key=int)}-§{max(sections, key=int)}"
            if sections else "none")
    print(f"OK: {len(slugs)} anchors / sections {span} cover every "
          f"reference in {', '.join(REFERRERS)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
