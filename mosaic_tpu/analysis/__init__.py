"""Project-aware static analysis (reference analog: the scalastyle gate
wired into the reference's Maven build — here the invariants are
JAX/TPU-specific, so the rules are too).

The framework is AST-based and dependency-free: `engine.analyze` parses
every target file once, runs file-scoped rules per module and
project-scoped rules over the whole tree (plus docs and committed
goldens), applies inline suppressions (``# lint: <rule>-ok (reason)``)
and the committed baseline, and returns typed :class:`Finding` records.
``tools/lint.py`` is the CLI driver; ``tests/test_analysis.py`` holds
the per-rule fixtures and ``tests/test_registry_coverage.py`` pins the
generated registry against ARCHITECTURE.md.

Rules shipped (see ``docs/ARCHITECTURE.md`` "Static analysis"):

- ``jit-purity`` — host side effects inside traced code;
- ``env-read-after-staging`` — env knobs read under jit bake stale
  values into compiled programs (the ``MOSAIC_PROBE_FORCE_LANE``
  lesson: resolve before staging, as ``resolve_probe_mode`` does);
- ``thread-context-adoption`` — worker threads must adopt telemetry
  sinks + trace context + fault plans;
- ``registry-drift`` — fault sites / spans / event stages / env knobs
  vs the committed registry, ARCHITECTURE's span table and the docs;
- ``broad-except`` — ``except Exception`` must re-raise, convert into
  the runtime error taxonomy, or carry a justification;
- ``unbounded-cache`` — ``lru_cache(maxsize=None)`` pins device arrays
  and index objects in HBM for process lifetime;
- hygiene floor carried over from the seed linter: ``syntax``,
  ``unused-import``, ``whitespace``, ``bare-except``, ``print-in-lib``,
  plus ``suppression`` (malformed suppression comments).
"""

from .findings import Finding
from .registry import Rule, all_rules, get_rule, rule
from .engine import AnalysisResult, FileContext, ProjectContext, analyze
from .baseline import load_baseline, save_baseline, split_baselined
from .project_registry import build_registry

# importing the rule modules registers them
from . import rules as _rules  # noqa: F401

__all__ = [
    "AnalysisResult",
    "FileContext",
    "Finding",
    "ProjectContext",
    "Rule",
    "all_rules",
    "analyze",
    "build_registry",
    "get_rule",
    "load_baseline",
    "rule",
    "save_baseline",
    "split_baselined",
]
