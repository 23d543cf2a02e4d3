"""repro.check — static contract linter for the repo's own invariants.

The runtime layer carries hard contracts (bitwise-deterministic sampled
streams, masked ragged-boundary stores in Pallas kernels, the PagedKVPool
acquire/copy_page/release_request refcount protocol, shard-local SPMD
dispatch) that example-based tests can only spot-check.  This package
enforces them statically, over the whole tree, on every commit:

    python -m repro.check src tests benchmarks [--format json]

Exit code == number of findings (capped at 255), so CI gates on zero.

Rules (see DESIGN.md Sec. 12 for the catalog and the motivating PRs):

  DET01  nondeterminism reaching traced code (random/time/np.random/set
         iteration, via a module-local call-graph walk from jit entries)
  DET02  PRNG key reuse and hardcoded PRNGKey fallback defaults
  KRN01  Pallas BlockSpec/grid contract: index-map arity and rank,
         out-of-bounds literal blocks, unguarded stores to revisited
         output blocks
  KV01   PagedKVPool protocol: acquire without release, mutation of
         shared pages without copy_page, free on a held request page
  SPMD01 collectives inside shard_map must use mesh-bound axis names;
         ppermute perms must cover the axis without duplicates
  EXC01  broad except that swallows without re-raise or logging
  CHK00  linter hygiene: unparsable file, malformed suppression

Suppressions are inline and must carry a reason:

    # check: disable=EXC01 -- the handler's fallback is the contract

either on the finding's line or on a comment line directly above it.
A suppression without a reason is itself a CHK00 finding.

Directories named ``check_fixtures`` (the known-bad rule fixtures) are
skipped during traversal; explicitly listed files are always checked.
"""

from .driver import run_check, iter_py_files, load_module
from .registry import Rule, all_rules, get_rule, register
from .report import Finding, render_json, render_text

__all__ = [
    "Finding",
    "Rule",
    "all_rules",
    "get_rule",
    "iter_py_files",
    "load_module",
    "register",
    "render_json",
    "render_text",
    "run_check",
]
