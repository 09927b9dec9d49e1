"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  * heartbeat_crossover  — §4.1 footnote 6 (n* ≈ 157)
  * availability         — §5.1 Fig 6 / Table 2 (reduced grid; --full for
                           the paper's n=155, P=4096 sweep)
  * microsim_t3/t4       — §5.2 Tables 3 and 4 (all 24 cells)
  * kernel_*             — Pallas-oracle micro-timings
  * roofline             — per (arch x shape) terms from the dry-run

The same argv goes to every suite, but each suite parses it with
``strict=False`` (parse_known_args), so suite-specific flags like the
sweep's --backend/--trials/--devices pass harmlessly through the suites
that don't know them.  A flag *no* suite recognizes is a typo, not a
pass-through: every suite publishes its option strings via
``cli_options()``, and a token outside the union gets a loud warning
naming the nearest valid flag — or, under $CI (or a --config run, where
a silently-dropped override would corrupt a pinned experiment), a hard
error.  Run a suite standalone to get strict parsing back.

``STANDALONE_TOOLS`` names the benchmarks/ modules that are deliberately
NOT suites: they parse their own argv strictly, emit no ``name,us,...``
CSV, and must be invoked directly (``python -m benchmarks.<tool>``) —
running them under the shared argv would either crash on the sweep's
flags or silently ignore their own.  The exclusion is explicit (and
pinned by tests/test_experiments.py) so a tool documented in
docs/BENCHMARKS.md is always either in the suites tuple or in this list.
"""
from __future__ import annotations

import difflib
import importlib
import os
import sys
import time

#: the run.py suites, in execution order — every one parses the shared
#: argv with strict=False and emits ``name,us,...`` CSV rows
SUITE_NAMES = ("heartbeat_crossover", "kernel_bench",
               "availability_sweep", "microsim_tables", "roofline")

#: benchmarks/ modules that are standalone CLIs, not run.py suites — see
#: the module docstring.  perf_probe re-lowers single cells under
#: config/sharding variants (strict own argv, sets XLA_FLAGS at import);
#: make_experiments_md regenerates EXPERIMENTS.md from committed dry-run
#: artifacts (no flags at all).
STANDALONE_TOOLS = ("perf_probe", "make_experiments_md",
                    "check_regression")


def _unknown_flags(argv, suites):
    """argv tokens that look like flags but appear in no suite's
    cli_options() — each as (token, suggestion-or-None)."""
    known = set()
    for suite in suites:
        known.update(suite.cli_options())
    known.update(("-h", "--help"))
    unknown = []
    for tok in argv:
        if not tok.startswith("-") or tok == "-":
            continue
        flag = tok.split("=", 1)[0]
        if flag in known:
            continue
        close = difflib.get_close_matches(flag, sorted(known), n=1)
        unknown.append((flag, close[0] if close else None))
    return unknown


def main() -> int:
    argv = sys.argv[1:]
    # `python benchmarks/run.py` puts benchmarks/ (not the repo root) on
    # sys.path; add the root so the package import below works either way
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    suites = tuple(importlib.import_module(f"benchmarks.{name}")
                   for name in SUITE_NAMES)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache(root)
    unknown = _unknown_flags(argv, suites)
    if unknown:
        for flag, close in unknown:
            hint = f" (did you mean {close!r}?)" if close else ""
            print(f"run.py: warning: no benchmark suite recognizes "
                  f"{flag!r}{hint} — it would be silently dropped",
                  file=sys.stderr)
        if os.environ.get("CI") or "--config" in {t.split("=", 1)[0]
                                                  for t in argv}:
            print("run.py: error: refusing to run with unrecognized "
                  "flags (CI/spec mode)", file=sys.stderr)
            return 2

    t0 = time.time()
    for suite in suites:
        suite.main(argv, strict=False)
    print(f"benchmarks_total,all,{(time.time()-t0)*1e6:.0f},seconds="
          f"{time.time()-t0:.1f}")
    return 0


if __name__ == '__main__':
    sys.exit(main())
