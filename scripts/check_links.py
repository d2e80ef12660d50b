#!/usr/bin/env python3
"""Markdown link checker (stdlib only, offline).

    python scripts/check_links.py README.md docs/ARCHITECTURE.md ROADMAP.md

For every ``[text](target)`` and bare ``<path>``-style reference in the given
markdown files, verifies that

- relative file targets exist (resolved against the markdown file's dir,
  ``#fragment`` and query stripped);
- in-page anchors (``#heading``) match a heading's GitHub slug in the target
  file (or the same file for bare ``#...`` links).

``http(s)://`` / ``mailto:`` targets are skipped — CI is offline.  Exits 1
listing every broken link.  Inline code spans and fenced code blocks are
ignored so ``foo(bar)`` examples in backticks never false-positive.
"""

from __future__ import annotations

import os
import re
import sys

LINK_RE = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
HEADING_RE = re.compile(r"^#{1,6}\s+(.*)$")
FENCE_RE = re.compile(r"^(```|~~~)")


def github_slug(heading: str) -> str:
    """GitHub's anchor slug: lowercase, drop non-word chars, spaces -> '-'."""
    text = re.sub(r"[`*_]", "", heading.strip())
    text = re.sub(r"[^\w\- ]", "", text.lower())
    return text.replace(" ", "-")


def strip_code(lines: list) -> list:
    """Blank out fenced code blocks and inline code spans."""
    out, fenced = [], False
    for line in lines:
        if FENCE_RE.match(line.strip()):
            fenced = not fenced
            out.append("")
            continue
        out.append("" if fenced else re.sub(r"`[^`]*`", "", line))
    return out


def heading_slugs(path: str) -> set:
    slugs = set()
    with open(path, encoding="utf-8") as f:
        lines = strip_code(f.read().splitlines())
    for line in lines:
        m = HEADING_RE.match(line)
        if m:
            slugs.add(github_slug(m.group(1)))
    return slugs


def check_file(md_path: str) -> list:
    errors = []
    base = os.path.dirname(os.path.abspath(md_path))
    with open(md_path, encoding="utf-8") as f:
        lines = strip_code(f.read().splitlines())
    for lineno, line in enumerate(lines, 1):
        for target in LINK_RE.findall(line):
            if re.match(r"^[a-z][a-z0-9+.-]*:", target):   # http:, mailto:, ...
                continue
            path_part, _, fragment = target.partition("#")
            if path_part:
                resolved = os.path.normpath(os.path.join(base, path_part))
                if not os.path.exists(resolved):
                    errors.append(f"{md_path}:{lineno}: missing file {target}")
                    continue
                if fragment and resolved.endswith(".md") \
                        and fragment not in heading_slugs(resolved):
                    errors.append(
                        f"{md_path}:{lineno}: missing anchor #{fragment} "
                        f"in {path_part}")
            elif fragment and fragment not in heading_slugs(md_path):
                errors.append(f"{md_path}:{lineno}: missing anchor #{fragment}")
    return errors


def main(argv: list) -> int:
    files = argv or ["README.md"]
    all_errors = []
    for path in files:
        if not os.path.exists(path):
            all_errors.append(f"{path}: file not found")
            continue
        all_errors.extend(check_file(path))
    if all_errors:
        print("broken markdown links:")
        for e in all_errors:
            print("  " + e)
        return 1
    print(f"link check OK ({len(files)} files)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
