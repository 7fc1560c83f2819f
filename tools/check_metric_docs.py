#!/usr/bin/env python
"""Static drift check: every metric the code can emit under the
`serving/`, `resilience/`, `store/`, or `comm/` groups must be named
in docs/OBSERVABILITY.md.

Scans flexflow_tpu/ for registry call sites — `counter("...")` /
`gauge("...")` / `histogram("...")` literals (f-strings included) plus
the per-module `_count("...")` / `_observe_ms("...")` helpers whose
group prefix the module fixes — and fails listing every name the doc
does not mention.  Dynamic name segments (`{...}` in an f-string)
match the docs' `<i>`-placeholder convention
(`serving/replica/<i>/queue_depth`) or a documented wildcard family
(`serving/autoscaler_*`).  Wired in as a tier-1 test
(tests/test_metric_docs.py) so the metric table cannot drift.

Second check, one a document (`documents`): what a document puts
between back-ticks has to exist.  Every `--flag` is one that
`FFConfig.from_args` defines, or the argparse of a script the same
document names; every path under `flexflow_tpu/`, `benchmarks/`,
`tests/`, `scripts/`, `tools/`, `examples/` or `docs/`, and every bare
`*.py` / `*.sh` / `*.json`, is a file of the checkout (`stale_references`).
A document that still cites a deleted file or option fails its case.

Usage: python tools/check_metric_docs.py [--root REPO]   (exit 0/1)
"""
from __future__ import annotations

import argparse
import glob
import itertools
import os
import re
import sys
from typing import Dict, List, Set, Tuple

GROUPS = ("serving/", "resilience/", "store/", "comm/")

#: direct registry call sites; \s* spans the line break of a wrapped
#: call like registry.gauge(\n    f"serving/replica/{id}/queue_depth"
_CALL = re.compile(
    r'\.(?:counter|gauge|histogram)\(\s*(f?)"([^"\n]+)"')

#: module-fixed helper prefix, e.g. `self.registry.counter(
#: f"store/{name}")` inside `def _count` — calls `self._count("hits")`
#: then emit store/hits
_HELPER_DEF = re.compile(
    r'\.(?:counter|histogram)\(\s*f"('
    + "|".join(g.rstrip("/") for g in GROUPS)
    + r')/\{name\}"')

_HELPER_CALL = re.compile(
    r'self\.(_count|_observe_ms)\(\s*"([^"\n]+)"')

#: a dynamic f-string segment
_DYN = re.compile(r"\{[^}]*\}")


def emitted_names(root: str) -> Dict[str, List[str]]:
    """name -> [files emitting it] for every grouped metric name the
    package can emit.  Fully dynamic leaves (`serving/{name}`: the
    helper-def pattern itself) are excluded — their concrete names
    come in through the helper-call scan."""
    out: Dict[str, List[str]] = {}
    pkg = os.path.join(root, "flexflow_tpu")
    for dirpath, _, files in os.walk(pkg):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, root)
            with open(path) as f:
                text = f.read()
            for is_f, name in _CALL.findall(text):
                if not name.startswith(GROUPS):
                    continue
                if is_f and _DYN.sub("", name) in (
                        g for g in GROUPS):
                    continue  # helper def: serving/{name} itself
                out.setdefault(name, []).append(rel)
            prefixes = set(_HELPER_DEF.findall(text))
            if len(prefixes) == 1:
                prefix = next(iter(prefixes))
                for _, leaf in _HELPER_CALL.findall(text):
                    out.setdefault(f"{prefix}/{leaf}",
                                   []).append(rel)
    return out


def documented_forms(doc_text: str) -> Tuple[Set[str], List[str]]:
    """(exact names incl. <i>-placeholder forms, wildcard prefixes).
    A wildcard must extend past its group prefix — the group headers
    (`serving/*`) document the namespace, not any particular metric."""
    names = set(re.findall(
        r"((?:" + "|".join(g.rstrip("/") for g in GROUPS)
        + r")/[A-Za-z0-9_/<>.-]+)", doc_text))
    wild = []
    for m in re.findall(
            r"((?:" + "|".join(g.rstrip("/") for g in GROUPS)
            + r")/[A-Za-z0-9_/<>.-]*)\*", doc_text):
        if m not in GROUPS:  # bare group headers don't count
            wild.append(m)
    return names, wild


def is_documented(name: str, names: Set[str],
                  wild: List[str]) -> bool:
    norm = _DYN.sub("<i>", name)
    if name in names or norm in names:
        return True
    # the literal head of a templated name may fall in a documented
    # wildcard family (serving/autoscaler_{action} ~ autoscaler_*)
    head = name.split("{", 1)[0]
    return any(head.startswith(w) or (("{" in name) and w.startswith(head))
               for w in wild)


TREES = ("flexflow_tpu", "benchmarks", "tests", "scripts", "tools",
         "examples", "docs")
#: flags of programs that are not this repository's: the chip tool's
#: and pytest's, where the documents quote their command lines
OUTSIDE_FLAGS = {"--chips", "--timeout", "--dist", "--durations"}
#: files a run writes, which the documents name and no checkout holds
RUN_OUTPUTS = {"trace.json", "manifest.json", "meta.json", "out.json"}

#: fenced blocks, and inline code (which may wrap inside a paragraph)
_TICKED = re.compile(
    r"```[^\n]*\n(.*?)```|`([^`\n]+(?:\n[^`\n]+)*)`", re.S)
_FLAG = re.compile(r"(?<![\w-])--[a-z][a-z0-9]*(?:-[a-z0-9]+)*(?![\w-])")
_FLAG_DEF = re.compile(r"""["'](--[a-z][a-z0-9-]*)["']""")
_PATH = re.compile(
    r"(?:(?:" + "|".join(TREES) + r")/[\w./<>*{},-]*"
    r"|[\w-]+\.(?:py|sh|json))")


def documents(root: str) -> List[str]:
    """The documents held to the tree."""
    return ["README.md",
            *sorted(os.path.relpath(p, root) for p in
                    glob.glob(os.path.join(root, "docs", "*.md"))),
            ".claude/skills/verify/SKILL.md"]


def _flags_defined(path: str) -> Set[str]:
    with open(path) as f:
        return set(_FLAG_DEF.findall(f.read()))


def _candidates(token: str) -> List[str]:
    """`a/{b,c}/<name>.py` -> [`a/b/*.py`, `a/c/*.py`]: braces are
    alternatives, `<...>` and `*` stand for anything."""
    token = re.sub(r"<[^>]*>", "*", token.split("::")[0])
    parts = re.split(r"\{([^}]*)\}", token)
    choices = [p.split(",") if i % 2 else [p] for i, p in enumerate(parts)]
    return ["".join(c) for c in itertools.product(*choices)]


def _lookup(root: str, pattern: str) -> List[str]:
    """A path is taken from the root; a bare file name is looked for
    at the root and anywhere under TREES, and nowhere else (a scratch
    checkout the tree ignores must not stand in for a deleted file)."""
    if "/" in pattern:
        return glob.glob(os.path.join(root, pattern), recursive=True)
    return glob.glob(os.path.join(root, pattern)) + [
        m for tree in TREES for m in glob.glob(
            os.path.join(root, tree, "**", pattern), recursive=True)]


def stale_references(root: str, doc: str) -> List[str]:
    """What `doc` names between back-ticks that the checkout does not
    have: flags nothing it names defines, paths that are not there."""
    with open(os.path.join(root, doc)) as f:
        ticked = [a or b for a, b in _TICKED.findall(f.read())]
    tokens = {t.strip("()[],;:.\"'") for s in ticked for t in s.split()}
    paths = {t for t in tokens if _PATH.fullmatch(t)}
    found = {p: [m for c in _candidates(p) for m in _lookup(root, c)]
             for p in paths}
    defined = _flags_defined(
        os.path.join(root, "flexflow_tpu", "config.py")) | OUTSIDE_FLAGS
    for hits in found.values():
        for hit in hits:
            if hit.endswith(".py") and os.path.isfile(hit):
                defined |= _flags_defined(hit)
    problems = [f"`{flag}`: no parser of FFConfig or of a script this "
                "document names defines it"
                for flag in sorted({f for s in ticked
                                    for f in _FLAG.findall(s)} - defined)]
    problems += [f"`{p}`: no such file in the checkout"
                 for p in sorted(paths)
                 if not found[p] and p not in RUN_OUTPUTS]
    return problems


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    args = p.parse_args(argv)
    doc_path = os.path.join(args.root, "docs", "OBSERVABILITY.md")
    with open(doc_path) as f:
        doc_text = f.read()
    emitted = emitted_names(args.root)
    names, wild = documented_forms(doc_text)
    missing = {n: files for n, files in sorted(emitted.items())
               if not is_documented(n, names, wild)}
    if missing:
        print(f"{len(missing)} emitted metric name(s) missing from "
              "docs/OBSERVABILITY.md:", file=sys.stderr)
        for n, files in missing.items():
            print(f"  {n}  (emitted by {', '.join(sorted(set(files)))})",
                  file=sys.stderr)
        return 1
    print(f"ok: {len(emitted)} emitted metric name(s) all documented "
          f"({len(names)} doc names, {len(wild)} wildcard families)")
    stale = {doc: problems for doc in documents(args.root)
             if (problems := stale_references(args.root, doc))}
    for doc, problems in stale.items():
        print(f"{doc}:\n  " + "\n  ".join(problems), file=sys.stderr)
    return 1 if stale else 0


if __name__ == "__main__":
    raise SystemExit(main())
