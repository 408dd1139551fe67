#!/usr/bin/env python
"""Dependency-free source linter (reference dev/lint-python role).

The image ships no flake8/pycodestyle, so this is a small AST + text
checker covering the rules that actually catch bugs in this codebase:

- E999 syntax errors
- F401 unused imports (module scope)
- E501 lines over 79 characters
- W191 tabs in indentation, W291 trailing whitespace
- B006 mutable default arguments
- E722 bare except
- JX1–JX5 TPU-correctness rules (hidden host syncs, PRNG key reuse,
  use-after-donation, collective axis names, host-only jax imports) —
  delegated to the jaxlint analyzer in ``dev/analysis/``
- TS1–TS5 concurrency rules (lock-order inversion against declared
  ``# raceguard: order`` annotations, blocking calls under a lock,
  unguarded thread-shared attributes, non-daemon threads/unbounded
  teardown joins, naked ``Condition.wait``) — delegated to the
  raceguard analyzer, which scans the threaded host plane
  (serving/elastic/deploy/observability/prefetch + scripts/)

Both analyzer passes share one suppression syntax
(``# jaxlint: disable=RULE``) and one shrink-only baseline
(``dev/analysis/baseline.txt``); stale baseline entries are findings
too, so the baseline only ever shrinks. See docs/STATIC_ANALYSIS.md.

Run: ``python dev/lint.py`` (exit 1 on findings). Scans bigdl_tpu/,
tests/, dev/, scripts/, bench.py, chip_smoke.py. ``--rules JX``
or ``--rules TS`` runs one analyzer family alone (the classic
E/F/W/B checks always run).

``--update-baseline`` rewrites the baseline from the current findings
(after a refactor that moves grandfathered code; run it with the
default ``--rules JX,TS`` so neither family's entries are dropped);
``--no-baseline`` shows every analyzer finding including
grandfathered ones (burn-down view).
"""
from __future__ import annotations

import argparse
import ast
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from analysis import jaxlint  # noqa: E402
from analysis import raceguard  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TARGETS = ["bigdl_tpu", "tests", "dev", "scripts", "bench.py",
           "chip_smoke.py"]
MAX_LEN = 79


def _files():
    for t in TARGETS:
        path = os.path.join(REPO, t)
        if os.path.isfile(path):
            yield path
        else:
            for root, _, names in os.walk(path):
                for n in sorted(names):
                    if n.endswith(".py"):
                        yield os.path.join(root, n)


def _unused_imports(tree):
    names = {}   # alias -> (line, name)
    # module scope only: function-local imports are deliberate lazy
    # loads here, and a local alias must not mask a dead module-level one
    for node in tree.body:
        if isinstance(node, ast.Import):
            for a in node.names:
                alias = a.asname or a.name.split(".")[0]
                names[alias] = (node.lineno, a.name)
        elif isinstance(node, ast.ImportFrom):
            if node.module == "__future__":
                continue
            for a in node.names:
                if a.name == "*":
                    continue
                alias = a.asname or a.name
                names[alias] = (node.lineno, a.name)
    used = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            v = node
            while isinstance(v, ast.Attribute):
                v = v.value
            if isinstance(v, ast.Name):
                used.add(v.id)
    # names re-exported via __all__ count as used
    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "__all__"):
            for c in ast.walk(node.value):
                if isinstance(c, ast.Constant) and isinstance(c.value, str):
                    used.add(c.value)
    out = []
    for alias, (line, name) in names.items():
        if alias not in used and not alias.startswith("_"):
            out.append((line, f"F401 unused import '{name}'"))
    return out


def lint_file(path):
    rel = os.path.relpath(path, REPO)
    with open(path, encoding="utf-8") as f:
        src = f.read()
    findings = []
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [(rel, e.lineno or 0, f"E999 syntax error: {e.msg}")]
    # package __init__ imports are re-exports (flake8's conventional
    # F401-per-__init__ exemption)
    if os.path.basename(path) != "__init__.py":
        findings += [(rel, ln, msg)
                     for ln, msg in _unused_imports(tree)]
    for i, line in enumerate(src.splitlines(), 1):
        if "# noqa" in line:
            continue
        if len(line) > MAX_LEN and "http://" not in line \
                and "https://" not in line:
            findings.append((rel, i, f"E501 line too long ({len(line)})"))
        if line != line.rstrip():
            findings.append((rel, i, "W291 trailing whitespace"))
        if line.startswith("\t") or (line[:1] == " " and "\t" in
                                     line[:len(line) - len(line.lstrip())]):
            findings.append((rel, i, "W191 tab in indentation"))
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for d in node.args.defaults + node.args.kw_defaults:
                if isinstance(d, (ast.List, ast.Dict, ast.Set)):
                    findings.append(
                        (rel, d.lineno, "B006 mutable default argument"))
        elif isinstance(node, ast.ExceptHandler) and node.type is None:
            findings.append((rel, node.lineno, "E722 bare except"))
    return findings


def run_jaxlint(paths, *, baseline=True, rules=("JX", "TS")):
    """Analyzer findings (JX jaxlint + TS raceguard, per ``rules``)
    over ``paths``, baseline-filtered. Returns
    ``(printable_tuples, raw_findings)``. Baseline entries are
    filtered to the selected rule families, so a ``--rules JX`` run
    never reports the TS entries as stale (or vice versa)."""
    raw = []
    if "JX" in rules:
        for p in paths:
            raw.extend(jaxlint.analyze_file(p, REPO))
    if "TS" in rules:
        raw.extend(raceguard.analyze_files(paths, REPO))
    if baseline:
        fams = {r[:2] for r in rules}
        entries = [e for e in jaxlint.load_baseline()
                   if e[1][:2] in fams]
        new, stale = jaxlint.apply_baseline(raw, entries)
    else:
        new, stale = raw, []
    out = [(f.path, f.line, f"{f.rule} {f.msg}") for f in new]
    out += [(jaxlint.BASELINE_PATH and
             os.path.relpath(jaxlint.BASELINE_PATH, REPO), 0,
             f"JLB stale baseline entry (finding is gone — prune it): "
             f"{e[0]}:{e[1]}:{e[2]}")
            for e in stale]
    return out, raw


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--no-baseline", action="store_true",
                        help="show grandfathered JX findings too")
    parser.add_argument("--update-baseline", action="store_true",
                        help="rewrite dev/analysis/baseline.txt from "
                             "the current analyzer findings")
    parser.add_argument("--rules", default="JX,TS",
                        help="analyzer families to run (JX, TS, or "
                             "JX,TS — default both)")
    args = parser.parse_args(argv)
    rules = tuple(r.strip().upper()
                  for r in args.rules.split(",") if r.strip())
    bad = [r for r in rules if r not in ("JX", "TS")]
    if bad or not rules:
        parser.error(f"--rules takes JX and/or TS, got {args.rules!r}")

    paths = list(_files())
    all_findings = []
    for path in paths:
        all_findings.extend(lint_file(path))
    jx, all_jx = run_jaxlint(paths, baseline=not args.no_baseline,
                             rules=rules)
    if args.update_baseline:
        with open(jaxlint.BASELINE_PATH, "w", encoding="utf-8") as f:
            f.write("# analyzer baseline — grandfathered findings "
                    "(path:RULE:source-line).\n"
                    "# Regenerate: python dev/lint.py "
                    "--update-baseline. Only ever shrink this file.\n")
            for e in sorted({jaxlint.format_baseline_entry(x)
                             for x in all_jx}):
                f.write(e + "\n")
        print(f"baseline rewritten with {len(all_jx)} finding(s)")
        return 0
    all_findings.extend(jx)
    all_findings.sort()
    for rel, line, msg in all_findings:
        print(f"{rel}:{line}: {msg}")
    print(f"{len(all_findings)} finding(s)")
    return 1 if all_findings else 0


if __name__ == "__main__":
    sys.exit(main())
