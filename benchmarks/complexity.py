"""Software complexity — table 1 of the paper.

The paper's argument: for a comparable feature set, OAR is ~30 source files
/ ~5k lines (25k counting Taktuk) vs 148k lines for OpenPBS — because the
storage/consistency layer is delegated to the database and the executive to
a high-level language. We make the same measurement over this repo: the
control plane (`repro/core`, the paper's scope) vs the whole framework
(which additionally contains a full JAX data plane the 2005 systems never
had)."""

from __future__ import annotations

import os
from dataclasses import dataclass

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PAPER_TABLE1 = [
    ("OpenPBS 2.3.16", "C", 350, "148k"),
    ("Maui (sched only) 3.2.5", "C", 142, "142k"),
    ("Maui Molokini 1.5.2", "Java", 116, "25k"),
    ("Taktuk 3.0", "C++", 120, "20k"),
    ("OAR", "Perl", 30, "5k (25k w/ Taktuk)"),
]


@dataclass
class Count:
    subsystem: str
    files: int
    lines: int
    code_lines: int          # excluding blanks/comments/docstrings


def _count_file(path: str) -> tuple[int, int]:
    total = code = 0
    in_doc = False
    with open(path, encoding="utf-8") as f:
        for line in f:
            total += 1
            s = line.strip()
            if not s:
                continue
            if s.startswith(('"""', "'''")):
                if not (len(s) > 3 and s.endswith(('"""', "'''"))):
                    in_doc = not in_doc
                continue
            if in_doc or s.startswith("#"):
                continue
            code += 1
    return total, code


def count_tree(rel: str) -> Count:
    files = lines = code = 0
    base = os.path.join(ROOT, rel)
    for dirpath, _, names in os.walk(base):
        for n in names:
            if n.endswith(".py"):
                t, c = _count_file(os.path.join(dirpath, n))
                files += 1
                lines += t
                code += c
    return Count(rel, files, lines, code)


def run() -> list[Count]:
    return [count_tree(p) for p in
            ("src/repro/core", "src/repro/kernels", "src/repro/models",
             "src/repro/parallel", "src/repro/train", "src/repro/serve",
             "src/repro/launch", "src/repro/configs", "src/repro/data",
             "src/repro", "tests", "benchmarks", "examples")]


def main() -> None:
    print("# software complexity (table 1 analogue)")
    print(f"{'subsystem':26s} {'files':>6s} {'lines':>7s} {'code':>7s}")
    for c in run():
        print(f"{c.subsystem:26s} {c.files:6d} {c.lines:7d} {c.code_lines:7d}")
    print("\npaper table 1:")
    for name, lang, files, lines in PAPER_TABLE1:
        print(f"  {name:26s} {lang:5s} {files:4d} files  {lines}")


if __name__ == "__main__":
    main()
