#!/usr/bin/env python
"""t2r-check: the spec-flow static checker + custom lints (+ sanitizer).

Runs the four static-analysis passes (docs/static_analysis.md) without
touching an accelerator or real data:

  1. spec-flow — every registered model/preprocessor pairing
     (tensor2robot_tpu/analysis/targets.py) is flowed abstractly from
     its feature/label specs through the preprocessor (including the
     decode-ROI dual-shape contract) into the model signature via
     jax.eval_shape;
  2. lints — AST rules over the package: T2R_* env gates must go
     through the flags registry, no host-numpy materialization inside
     jitted regions, shm-ring/lock discipline in the worker return path;
  3. concurrency — lock-discipline analysis over the threaded fabric
     (serving/, replay/, train/, predictors/): guard-contract
     inference for shared fields, cross-module lock-order cycle
     detection, blocking calls under a held lock
     (analysis/concurrency.py; runtime twin: testing/locksmith.py);
  4. sanitize (opt-in, --sanitize) — builds the native parsers under
     ASan/UBSan, verifies the sanitizer is live (--self-test-oob canary
     must abort), and drives the malformed-record corpus through them.

Exit status: 0 clean, 1 findings, 2 infrastructure failure.

Run with JAX_PLATFORMS=cpu (tools/run_checks.sh does): the passes need no
accelerator and must not take the chip from a running job.

Examples:
  python tools/t2r_check.py                 # passes 1+2+3
  python tools/t2r_check.py --sanitize      # all four
  python tools/t2r_check.py --flags         # print the flag registry
  python tools/t2r_check.py --lint-only path/to/file.py
  python tools/t2r_check.py --concurrency-only   # pass 3 alone
"""

from __future__ import annotations

import argparse
import os
import shutil
import subprocess
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)



def _run_specflow(target_names) -> int:
    from tensor2robot_tpu.analysis.diagnostics import format_diagnostics
    from tensor2robot_tpu.analysis.specflow import check_targets
    from tensor2robot_tpu.analysis.targets import default_targets

    targets = default_targets()
    if target_names:
        wanted = set(target_names)
        unknown = wanted - {t.name for t in targets}
        if unknown:
            print(
                f"[specflow] unknown target(s) {sorted(unknown)}; "
                f"registered: {sorted(t.name for t in targets)}"
            )
            return 2
        targets = [t for t in targets if t.name in wanted]
    failures = 0
    for name, diagnostics in check_targets(targets):
        if diagnostics:
            failures += 1
            print(f"[specflow] {name}: {len(diagnostics)} finding(s)")
            print(format_diagnostics(diagnostics, root=_REPO))
        else:
            print(f"[specflow] {name}: clean")
    return 1 if failures else 0


def _run_lints(paths) -> int:
    from tensor2robot_tpu.analysis.diagnostics import format_diagnostics
    from tensor2robot_tpu.analysis.lints import DEFAULT_LINT_ROOTS, lint_paths

    diagnostics = lint_paths(paths or DEFAULT_LINT_ROOTS, root=_REPO)
    scope = ", ".join(paths or DEFAULT_LINT_ROOTS)
    if diagnostics:
        print(f"[lints] {len(diagnostics)} finding(s) over {scope}")
        print(format_diagnostics(diagnostics, root=_REPO))
        return 1
    print(f"[lints] clean over {scope}")
    return 0


def _run_concurrency(paths) -> int:
    from tensor2robot_tpu.analysis.concurrency import (
        DEFAULT_CONCURRENCY_ROOTS,
        check_paths,
    )
    from tensor2robot_tpu.analysis.diagnostics import format_diagnostics

    try:
        diagnostics = check_paths(paths or None, root=_REPO)
    except OSError as exc:
        print(f"[concurrency] cannot read scope: {exc}")
        return 2
    label = ", ".join(paths or DEFAULT_CONCURRENCY_ROOTS)
    if diagnostics:
        print(f"[concurrency] {len(diagnostics)} finding(s) over {label}")
        print(format_diagnostics(diagnostics, root=_REPO))
        return 1
    print(f"[concurrency] clean over {label}")
    return 0


def _run_sanitize(corpus_dir) -> int:
    native = os.path.join(_REPO, "tensor2robot_tpu", "native")
    fuzz = os.path.join(native, "t2r_fuzz_asan")
    build = subprocess.run(
        ["make", "-C", native, "sanitize"], capture_output=True, text=True
    )
    if build.returncode != 0:
        print("[sanitize] build failed (no ASan toolchain?); pass skipped")
        print(build.stderr.strip()[-2000:])
        return 2
    # The canary MUST abort: a corpus "survived" from an uninstrumented
    # binary is vacuous.
    canary = subprocess.run(
        [fuzz, "--self-test-oob"], capture_output=True, text=True
    )
    if canary.returncode == 0 or canary.returncode == 3:
        print(
            "[sanitize] self-test OOB did NOT abort — sanitizer not "
            "active in the build; failing the pass"
        )
        return 1
    print("[sanitize] sanitizer canary OK (self-test OOB aborted)")
    owns_corpus = corpus_dir is None
    if owns_corpus:
        corpus_dir = tempfile.mkdtemp(prefix="t2r_fuzz_corpus_")
    try:
        populated = os.path.isdir(corpus_dir) and os.listdir(corpus_dir)
        if not populated:
            from tensor2robot_tpu.analysis.corpus import write_corpus

            paths = write_corpus(corpus_dir)
            print(f"[sanitize] wrote {len(paths)} corpus files")
        run = subprocess.run(
            [fuzz, corpus_dir], capture_output=True, text=True
        )
        tail = run.stdout.strip().splitlines()[-1:] or [""]
        if run.returncode != 0:
            print(f"[sanitize] FAILED (exit {run.returncode})")
            print(run.stdout[-4000:])
            print(run.stderr[-4000:])
            return 1
        print(f"[sanitize] {tail[0]}")
        return 0
    finally:
        if owns_corpus:
            shutil.rmtree(corpus_dir, ignore_errors=True)


def main() -> int:
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument(
        "paths", nargs="*",
        help="lint scope override (default: package + bench.py + tools)",
    )
    parser.add_argument(
        "--target", action="append", dest="targets",
        help="spec-flow only these registered targets (repeatable)",
    )
    parser.add_argument(
        "--skip-specflow", action="store_true", help="skip pass 1"
    )
    parser.add_argument(
        "--skip-lints", action="store_true", help="skip pass 2"
    )
    parser.add_argument(
        "--lint-only", action="store_true",
        help="= --skip-specflow --skip-concurrency (lint the given paths)",
    )
    parser.add_argument(
        "--skip-concurrency", action="store_true", help="skip pass 3"
    )
    parser.add_argument(
        "--concurrency-only", action="store_true",
        help="run only the concurrency pass (over the given paths, "
        "default the threaded roots); exit 0 clean / 1 findings / 2 "
        "infrastructure failure",
    )
    parser.add_argument(
        "--sanitize", action="store_true",
        help="also run the ASan/UBSan corpus pass (pass 3)",
    )
    parser.add_argument(
        "--corpus", default=None,
        help="reuse/populate this corpus dir for --sanitize",
    )
    parser.add_argument(
        "--flags", action="store_true",
        help="print the T2R flag registry and exit",
    )
    args = parser.parse_args()

    if args.flags:
        from tensor2robot_tpu import flags

        print(flags.describe())
        return 0

    if args.concurrency_only:
        return _run_concurrency(args.paths)

    status = 0
    if not (args.skip_specflow or args.lint_only):
        status = max(status, _run_specflow(args.targets))
    if not args.skip_lints:
        status = max(status, _run_lints(args.paths))
    if not (args.skip_concurrency or args.lint_only):
        status = max(status, _run_concurrency(None))
    if args.sanitize:
        status = max(status, _run_sanitize(args.corpus))
    if status == 0:
        print("[t2r-check] all passes clean")
    return status


if __name__ == "__main__":
    sys.exit(main())
