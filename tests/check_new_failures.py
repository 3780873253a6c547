#!/usr/bin/env python
"""Tier-1 gate that fails only on NEW test failures.

Runs pytest over the tier-1 suite, collects the set of failed test ids, and
compares it against the checked-in baseline `tests/known_failures.txt` (the
pre-existing seed failures). The job:

  * FAILS (exit 1) if any test outside the baseline fails — a regression is
    caught at PR time instead of silently joining the pile;
  * PASSES if the only failures are baseline entries;
  * FAILS (exit 1) on an UNFILTERED run if a baseline entry now passes — a
    stale entry is a fixed bug whose line was never deleted, i.e. a test
    that could regress without tripping the gate. Delete the line. (With
    -m/-k/path filters stale entries only warn, because a filtered run may
    simply not have collected them.)
  * propagates pytest's own hard errors (collection error, internal error,
    usage error) verbatim.

Usage (what CI runs):

    PYTHONPATH=src python tests/check_new_failures.py [extra pytest args]

Extra args are forwarded to pytest (e.g. `-m "not slow"` or a subset path).
"""

from __future__ import annotations

import re
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BASELINE = HERE / "known_failures.txt"

# pytest summary lines look like:  FAILED tests/test_x.py::test_y[p] - Msg
_FAILED_RE = re.compile(r"^(?:FAILED|ERROR) +(\S+)")


def load_baseline(path: Path = BASELINE) -> set:
    known = set()
    for line in Path(path).read_text().splitlines():
        line = line.strip()
        if line and not line.startswith("#"):
            known.add(line)
    return known


def run_pytest(extra_args) -> tuple:
    cmd = [
        sys.executable, "-m", "pytest", "-q", "--tb=no", "-rfE",
        "--continue-on-collection-errors", *extra_args,
    ]
    print("[check_new_failures] $", " ".join(cmd), flush=True)
    proc = subprocess.run(
        cmd, cwd=HERE.parent, capture_output=True, text=True
    )
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr)
    failed = set()
    for line in proc.stdout.splitlines():
        m = _FAILED_RE.match(line.strip())
        if m:
            failed.add(m.group(1))
    return proc.returncode, failed


_OUTCOME_RE = re.compile(r"^(\S+)\s+(PASSED|XPASS)\b")


def confirm_stale_by_rerun(stale: set) -> set:
    """Re-run the stale candidates alone; return only those that PASS.

    "Did not fail" is not "now passes": an env-gated skipif (e.g. the
    shard_map guards) or a deleted/uncollected test also never appears in
    the failure set. Such entries are NOT provably stale and must keep
    their baseline lines, so only an entry that verifiably runs green here
    may hard-fail the gate.
    """
    print(f"[check_new_failures] confirming {len(stale)} stale candidate(s) "
          "with targeted re-runs", flush=True)
    confirmed = set()
    # one candidate per invocation: a single unknown nodeid (deleted test)
    # in a combined run makes pytest run NOTHING, masking the others
    for t in sorted(stale):
        cmd = [sys.executable, "-m", "pytest", "-v", "--no-header",
               "--tb=no", t]
        proc = subprocess.run(cmd, cwd=HERE.parent, capture_output=True,
                              text=True)
        for line in proc.stdout.splitlines():
            m = _OUTCOME_RE.match(line.strip())
            if m and m.group(1) == t:
                confirmed.add(t)
                break
    return confirmed


def evaluate(known: set, code: int, failed: set, filtered: bool,
             confirm_stale=None) -> int:
    """Pure gate decision: pytest outcome + baseline -> exit code.

    `filtered` means extra pytest args narrowed collection (-m/-k/path), so
    a baseline entry that did not fail may simply not have run.

    `confirm_stale`, when given, maps the stale candidate set to the subset
    proven to actually pass (see confirm_stale_by_rerun); candidates it
    rejects (skipped / uncollected) only warn instead of hard-failing.
    """
    if code == 0:
        stale = known  # everything passed; the whole baseline is stale
        new = set()
    elif code == 1:
        if not failed:
            # exit-code/parse mismatch: pytest reported failures but none
            # were parsed from the -rfE summary (e.g. a flag or plugin
            # suppressed it) — never let a red run pass the gate
            print("[check_new_failures] pytest exited 1 but no FAILED/ERROR "
                  "summary lines were parsed — refusing to pass")
            return 1
        new = failed - known
        stale = known - failed
    else:
        print(f"[check_new_failures] pytest exited {code} (hard error; "
              "collection problem or internal error) — failing outright")
        return code
    if new:
        # report new failures FIRST and skip the stale confirmation below:
        # its per-candidate re-runs could not change this exit code
        print(f"[check_new_failures] {len(new)} NEW failure(s) beyond the "
              "known baseline:")
        for t in sorted(new):
            print(f"  {t}")
        return 1
    rc = 0
    if stale and not filtered and confirm_stale is not None:
        proven = set(confirm_stale(stale))
        unproven = stale - proven
        stale = proven
        if unproven:
            print("[check_new_failures] note: baseline entries did not fail "
                  "but also did not verifiably pass (skipped/uncollected) — "
                  "keeping their lines:")
            for t in sorted(unproven):
                print(f"  {t}")
    if stale:
        if filtered:
            # a filtered run (-m/-k/path) may simply not have collected the
            # baseline entry — stale-ness is only provable unfiltered
            print("[check_new_failures] WARNING: baseline entries did not "
                  "fail under this filtered run (not necessarily stale):")
        else:
            # a baseline entry that PASSES is a fixed bug still allowlisted:
            # it could regress without tripping the gate. Fail until the
            # line is deleted so fixes can never rot in the baseline.
            print("[check_new_failures] STALE: baseline entries now pass — "
                  "delete them from tests/known_failures.txt:")
            rc = 1
        for t in sorted(stale):
            print(f"  {t}")
    if rc:
        return rc
    print(f"[check_new_failures] OK: {len(failed)} failure(s), all in the "
          f"known baseline ({len(known)} entries)")
    return 0


#: long pytest flags under which "baseline entry did not fail" proves
#: nothing: collection filters AND run truncators (--maxfail/--stepwise
#: stop before later baseline entries get a chance to fail)
_FILTER_LONG = ("--ignore", "--ignore-glob", "--deselect", "--last-failed",
                "--lf", "--failed-first", "--ff", "--exitfirst", "--maxfail",
                "--stepwise", "--sw")
#: non-filter long flags that consume the NEXT argv entry as their value (so
#: the value is not mistaken for a positional path); prefer --flag=value
#: form for anything not listed here
_VALUED_LONG = ("--tb", "--durations", "--timeout", "--color", "--junitxml",
                "--junit-xml", "--cov", "--cov-report", "--basetemp",
                "--rootdir", "--html", "--result-log")
#: short options whose value is the remainder of the cluster (or, when the
#: cluster ends there, the next argv entry) — e.g. "-rx" is -r's value "x",
#: NOT -r plus -x
_VALUED_SHORT = "poWcnr"


def _short_cluster(a: str):
    """Classify a combined short-option cluster like "-xq" or "-rfE".

    Returns (narrows, consumes_next): `narrows` if the cluster contains a
    collection filter (-m/-k) or the -x run truncator; `consumes_next` if
    its final option takes a value that must come from the next argv entry.
    """
    i = 1
    while i < len(a):
        ch = a[i]
        if ch in "mk":
            return True, False  # filter; value is the remainder or next arg
        if ch == "x":
            return True, False  # early stop: later entries never ran
        if ch in _VALUED_SHORT:
            return False, i + 1 == len(a)  # remainder is this option's value
        i += 1
    return False, False


def narrows_collection(argv) -> bool:
    """True only for args under which stale-ness is unprovable: anything
    that can shrink the collected test set OR truncate the run early.

    A benign forwarded flag (e.g. `-p no:cacheprovider`, `-q`, `-rfE`) must
    NOT disable the stale-baseline hard failure — only -m/-k/--ignore/
    --deselect-style filters, early-stop flags (-x, --maxfail, --stepwise,
    including combined forms like "-xq") and positional paths/nodeids do.
    """
    skip_next = False
    for a in argv:
        if skip_next:
            skip_next = False
            continue
        if a.startswith("--"):
            if any(a == f or a.startswith(f + "=") for f in _FILTER_LONG):
                return True
            if a in _VALUED_LONG:
                skip_next = True
            continue  # some other long flag (boolean or --flag=value form)
        if a.startswith("-") and len(a) > 1:
            narrows, consumes = _short_cluster(a)
            if narrows:
                return True
            skip_next = consumes
            continue
        return True  # positional path / test id
    return False


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    known = load_baseline()
    code, failed = run_pytest(argv)
    return evaluate(known, code, failed, filtered=narrows_collection(argv),
                    confirm_stale=confirm_stale_by_rerun)


if __name__ == "__main__":
    sys.exit(main())
