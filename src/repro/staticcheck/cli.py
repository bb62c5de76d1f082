"""``python -m repro.staticcheck`` — run every pass, gate on new findings.

Exit codes: 0 — clean or fully baselined; 1 — at least one finding not in
the baseline; 2 — a pass crashed (an analyzer bug, not a repo finding).

The jaxpr audit traces the whole plan matrix, which costs a few seconds
of JAX tracing; its results are cached in
``results/staticcheck/audit_cache.json`` keyed by a digest of every
source file the traced programs could depend on, so repeated CI runs on
an unchanged tree skip straight to the verdict.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import pathlib
import sys
import traceback

from repro.staticcheck import (deadcode, findings as fmod, jaxpr_audit,
                               kernel_contracts, plan_verify, seed_lint)
from repro.staticcheck.matrix import audit_matrix

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_BASELINE = REPO_ROOT / "results" / "staticcheck" / "baseline.json"
DEFAULT_CACHE = REPO_ROOT / "results" / "staticcheck" / "audit_cache.json"

PASSES = ("seed-lint", "plan-verify", "kernel-contracts", "jaxpr-audit")


def tree_digest(root: pathlib.Path = REPO_ROOT) -> str:
    """Digest of everything the traced plan matrix depends on: the whole
    ``src/repro`` tree."""
    h = hashlib.sha256()
    for p in sorted((root / "src" / "repro").rglob("*.py")):
        h.update(str(p.relative_to(root)).encode())
        h.update(p.read_bytes())
    return h.hexdigest()


def run_jaxpr_audit(cache: pathlib.Path | None) -> list[fmod.Finding]:
    digest = tree_digest()
    if cache is not None and cache.exists():
        try:
            data = json.loads(cache.read_text())
        except ValueError:
            data = {}
        if data.get("digest") == digest:
            results = [jaxpr_audit.AuditResult.from_json(r)
                       for r in data["results"]]
            return [f for r in results for f in r.findings]
    results = jaxpr_audit.run()
    if cache is not None:
        cache.parent.mkdir(parents=True, exist_ok=True)
        cache.write_text(json.dumps(
            {"digest": digest, "results": [r.to_json() for r in results]},
            indent=2) + "\n")
    return [f for r in results for f in r.findings]


def run_pass(name: str, cache: pathlib.Path | None) -> list[fmod.Finding]:
    if name == "seed-lint":
        return seed_lint.run()
    if name == "plan-verify":
        out = []
        for case in audit_matrix():
            out.extend(plan_verify.verify_plan(
                case.plan, case.cfg, case.in_dim, case.n_nodes,
                where=case.key))
        out.extend(plan_verify.verify_kv_matrix())
        return out
    if name == "kernel-contracts":
        return kernel_contracts.run()
    if name == "jaxpr-audit":
        return run_jaxpr_audit(cache)
    if name == "dead-code":
        return deadcode.sweep()
    raise ValueError(f"unknown pass {name!r}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.staticcheck",
        description="compression-invariant static analysis over the repo")
    ap.add_argument("--ci", action="store_true",
                    help="CI mode: plain output, all gating passes")
    ap.add_argument("--baseline", type=pathlib.Path,
                    default=DEFAULT_BASELINE,
                    help="suppression file (default: %(default)s)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="accept the current findings into --baseline")
    ap.add_argument("--dead-code", action="store_true",
                    help="also run the opt-in unused-symbol sweep")
    ap.add_argument("--passes", default=None, metavar="CSV",
                    help=f"subset of passes to run (default: all of "
                         f"{','.join(PASSES)})")
    ap.add_argument("--cache", type=pathlib.Path, default=DEFAULT_CACHE,
                    help="jaxpr-audit result cache (default: %(default)s)")
    ap.add_argument("--no-cache", action="store_true",
                    help="re-trace the plan matrix unconditionally")
    args = ap.parse_args(argv)

    names = (args.passes.split(",") if args.passes
             else list(PASSES) + (["dead-code"] if args.dead_code else []))
    cache = None if args.no_cache else args.cache

    all_findings: list[fmod.Finding] = []
    for name in names:
        try:
            got = run_pass(name.strip(), cache)
        except Exception:
            print(f"[{name}] pass crashed:", file=sys.stderr)
            traceback.print_exc()
            return 2
        print(f"[{name}] {len(got)} finding(s)")
        for f in got:
            print(f"  {f.render()}")
        all_findings.extend(got)

    if args.write_baseline:
        fmod.save_baseline(args.baseline, all_findings)
        print(f"wrote {len(all_findings)} finding(s) to {args.baseline}")
        return 0

    fresh = fmod.new_findings(all_findings, fmod.load_baseline(args.baseline))
    n_old = len(all_findings) - len(fresh)
    if fresh:
        print(f"FAIL: {len(fresh)} new finding(s) "
              f"({n_old} baselined)", file=sys.stderr)
        return 1
    print(f"OK: no new findings ({n_old} baselined)")
    return 0
