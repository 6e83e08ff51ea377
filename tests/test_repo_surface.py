"""What the checkout holds beside the library: the tools that remain
start, and the root carries no record of a run.

One measurement system (ISSUE 46): `benchmark/run.py` on the chip, the
ledger and `PERF.md` for what it read. A tool under ``tools/`` asserts or
reports over trails; none is a bench, and none imports one. The library
reads nothing around its own package: a record file at the root that a
rule could price a device lane from has nowhere to be read.
"""

import fnmatch
import importlib
import re
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
TOOLS = sorted(p.stem for p in (REPO / "tools").glob("*.py"))


@pytest.fixture()
def tools_on_path(monkeypatch):
    monkeypatch.syspath_prepend(str(REPO / "tools"))


def test_the_tools_that_remain_are_the_known_eleven():
    assert TOOLS == [
        "calibrate_margins", "chaos_sweep", "coverage_gate", "doctor",
        "fleet_report", "generate_api_docs", "generate_r_bindings", "lint",
        "probe_smoke", "stall_report", "trace_report",
    ]


@pytest.mark.parametrize("name", TOOLS)
def test_tool_imports_and_its_help_exits_zero(
    name, tools_on_path, monkeypatch, capsys
):
    """No survivor imported a deleted sibling; a tool that parses
    arguments answers ``--help`` and does nothing else."""
    mod = importlib.import_module(name)
    if not (hasattr(mod, "main") and hasattr(mod, "argparse")):
        return
    monkeypatch.setattr(sys, "argv", [f"{name}.py", "--help"])
    with pytest.raises(SystemExit) as done:
        mod.main()
    assert done.value.code == 0
    assert "usage:" in capsys.readouterr().out


def test_the_root_holds_no_record_of_a_run():
    names = [p.name for p in REPO.iterdir() if p.is_file()]
    for pattern in ("*_r[0-9][0-9].json", "TREND.json", "BENCH_*.json"):
        assert not fnmatch.filter(names, pattern), pattern
    for gone in ("VERDICT.md", "ADVICE.md", "traces/archive",
                 "tools/perf_gate.py", "tools/bench_trend.py",
                 "tests/goldens/perf_gate.json"):
        assert not (REPO / gone).exists(), gone
    assert not list((REPO / "tools").glob("*_bench.py"))


#: the two modules that still resolve a path above the package, and what
#: for: a build output and a cache, neither of which decides an answer.
#: Shrink-only (ROADMAP D23): a new entry is a new reach out of the package
CLIMBS_OUT = {
    "mosaic_tpu/core/geometry/hostops.py":
        "builds and loads native/build/libmosaicgeom.so",
    "mosaic_tpu/runtime/platform.py":
        "configure_compile_cache: <checkout>/.jax_cache, executables only",
}


def test_the_library_reads_nothing_around_its_package():
    """No module reads a record from above ``mosaic_tpu/``: none names
    ``parents[2]`` (the checkout's root, which an installed package does
    not have), none globs from its own ``__file__``, and the modules
    that climb at all are the two listed above."""
    climbs = re.compile(
        r"parents\[[2-9]\]|(dirname\(\s*(os\.path\.)?){3}|\.parent\.parent\.parent"
    )
    globs = re.compile(r"\b(glob|rglob|iglob)\(")
    climbing, globbing = set(), []
    for path in sorted((REPO / "mosaic_tpu").rglob("*.py")):
        text = path.read_text(encoding="utf-8")
        rel = str(path.relative_to(REPO))
        assert "parents[2]" not in text, rel
        if climbs.search(text):
            climbing.add(rel)
        globbing += [
            (rel, line.strip()) for line in text.splitlines()
            if globs.search(line) and ("__file__" in line or "_REPO" in line)
        ]
    assert climbing == set(CLIMBS_OUT)
    assert not globbing, globbing
