#!/usr/bin/env python
"""tpu-lint CLI — static trace-safety analysis for Pallas kernels and
traced code (paddle_tpu.analysis; rule catalog in ANALYSIS.md).

Usage:
    python tools/tpu_lint.py [paths...]          # default: paddle_tpu/
    python tools/tpu_lint.py --json paddle_tpu
    python tools/tpu_lint.py --rules A1,A3 paddle_tpu/kernels
    python tools/tpu_lint.py --list-rules

Exit codes: 0 = clean, 1 = findings, 2 = usage error.

The analyzer is loaded straight from paddle_tpu/analysis/ WITHOUT
importing the paddle_tpu package, so no jax import happens: the lint
runs in ~1 s on a cold CPU interpreter and never starts a jax backend.
"""
import argparse
import importlib.util
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_analysis():
    """Import paddle_tpu/analysis as a standalone package (bypassing
    paddle_tpu/__init__.py, which imports jax)."""
    pkg_dir = os.path.join(_REPO, "paddle_tpu", "analysis")
    name = "paddle_tpu_analysis_standalone"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(pkg_dir, "__init__.py"),
        submodule_search_locations=[pkg_dir])
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="tpu_lint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("paths", nargs="*",
                    default=[os.path.join(_REPO, "paddle_tpu")],
                    help="files or directories to lint "
                         "(default: paddle_tpu/)")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON object instead of text")
    ap.add_argument("--rules", default=None, metavar="IDS",
                    help="comma-separated rule ids or slugs "
                         "(e.g. A1,A3 or index-map,vmem)")
    ap.add_argument("--exclude", action="append", default=[],
                    metavar="SUBSTR",
                    help="skip files whose path contains SUBSTR "
                         "(repeatable)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    args = ap.parse_args(argv)

    analysis = load_analysis()
    try:
        rules = analysis.select_rules(
            args.rules.split(",") if args.rules else None)
    except ValueError as e:
        print(f"tpu_lint: {e}", file=sys.stderr)
        return 2

    if args.list_rules:
        for r in analysis.all_rules():
            print(f"{r.id:4} [{', '.join(r.slugs)}] ({r.severity}) "
                  f"{r.summary}")
        return 0

    diags, nfiles = analysis.lint_paths(args.paths, rules=rules,
                                        exclude=tuple(args.exclude))

    def pack_of(rule_id):
        # "A3" -> "A", "B2" -> "B"; parse errors group under "parse"
        head = "".join(c for c in rule_id if c.isalpha())
        return head or rule_id

    packs = {}
    for r in rules:
        packs.setdefault(pack_of(r.id), {"rules": [], "findings": 0})
        packs[pack_of(r.id)]["rules"].append(r.id)
    for d in diags:
        packs.setdefault(pack_of(d.rule), {"rules": [], "findings": 0})
        packs[pack_of(d.rule)]["findings"] += 1
    for name, p in packs.items():
        p["files"] = nfiles
        # one assertable line per pack for the driver gate
        p["summary"] = (f"{p['findings']} findings, {nfiles} files, "
                        f"{len(p['rules'])} rules")

    if args.json:
        print(json.dumps({
            "version": 1,
            "files_scanned": nfiles,
            "rules": [r.id for r in rules],
            "packs": packs,
            "findings": [d.to_dict() for d in diags],
        }, indent=2))
    else:
        if diags:
            print(analysis.format_text(diags))
        print(f"tpu-lint: {len(diags)} finding(s) in {nfiles} file(s) "
              f"[rules: {', '.join(r.id for r in rules)}]")
        for name in sorted(packs):
            print(f"tpu-lint[{name}]: {packs[name]['summary']}")
    return 1 if diags else 0


if __name__ == "__main__":
    sys.exit(main())
