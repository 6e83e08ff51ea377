"""Generate docs/API.md from the live function registry.

Reference analog: `docs/source/api/*.rst` — the reference documents every
registered SQL function grouped by family. Here the registry itself is the
source of truth: each of the ~126 registered names is emitted with its
signature and docstring summary, so the doc can never list a function that
does not exist (tests/test_api_docs.py regenerates and diffs).

    python tools/generate_api_docs.py
"""

from __future__ import annotations

import inspect
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

FAMILIES = [
    ("Geometry accessors & measures", "st_"),
    ("Grid / index functions", "grid_"),
    ("Raster functions", "rst_"),
    ("Constructors & converters", ("as_", "st_point", "st_geomfrom", "try_")),
    ("Aggregates", ("st_union_agg", "st_intersection_agg", "grid_cell_")),
]


def _summary(fn) -> str:
    doc = inspect.getdoc(fn) or ""
    first = doc.split("\n\n")[0].replace("\n", " ").strip()
    return first if len(first) <= 160 else first[:157] + "..."


def _sig(fn) -> str:
    try:
        s = str(inspect.signature(fn))
    except (TypeError, ValueError):
        return "(...)"
    return s if len(s) <= 90 else "(...)"


def _obs_lines() -> list[str]:
    """The `mosaic_tpu.obs` section: introspected from the package's
    public API so the doc cannot list a name that does not exist."""
    import inspect as _inspect

    import mosaic_tpu.obs as obs

    lines = [
        "## Observability (`mosaic_tpu.obs`)",
        "",
        "Tracing, typed metrics, and exporters over the "
        "`runtime/telemetry.py` event spine (see ARCHITECTURE.md "
        "\"Observability\" for the span taxonomy). CLI companions: "
        "`tools/trace_report.py` (per-stage breakdown + `--against` "
        "diff), `tools/stall_report.py` (a window's wall time by "
        "stall class).",
        "",
        "| Name | Summary |",
        "|---|---|",
    ]
    for n in sorted(obs.__all__):
        o = getattr(obs, n)
        if _inspect.ismodule(o):
            continue
        if _inspect.isfunction(o) or _inspect.isclass(o):
            sig = _sig(o).replace("|", "\\|")
            summ = _summary(o).replace("|", "\\|")
            lines.append(f"| `{n}{sig}` | {summ} |")
        else:
            lines.append(
                f"| `{n}` | The process default metrics registry "
                f"(module-level `counter`/`gauge`/`histogram` target "
                f"it). |"
            )
    lines.append("")
    return lines


def generate() -> str:
    from mosaic_tpu.context import MosaicContext

    reg = MosaicContext.build("H3").register()
    lines = [
        "# API reference",
        "",
        "Generated by `tools/generate_api_docs.py` from the live registry "
        f"({len(reg)} registered names) — regenerate after adding functions. "
        "Reference analog: `docs/source/api/*.rst`.",
        "",
    ]
    used: set[str] = set()

    def family(title, names):
        if not names:
            return
        lines.append(f"## {title}")
        lines.append("")
        lines.append("| Function | Summary |")
        lines.append("|---|---|")
        for n in names:
            fn = reg[n]
            sig = _sig(fn).replace("|", "\\|")
            summ = _summary(fn).replace("|", "\\|")
            lines.append(f"| `{n}{sig}` | {summ} |")
            used.add(n)
        lines.append("")

    st = sorted(n for n in reg if n.startswith("st_"))
    grid = sorted(n for n in reg if n.startswith("grid_"))
    rst = sorted(n for n in reg if n.startswith("rst_"))
    family("ST_ geometry functions", st)
    family("Grid / index functions", grid)
    family("RST_ raster functions", rst)
    rest = sorted(n for n in reg if n not in used)
    family("Constructors, converters, aggregates, utilities", rest)
    lines.extend(_obs_lines())
    return "\n".join(lines) + "\n"


if __name__ == "__main__":
    out = REPO / "docs" / "API.md"
    out.write_text(generate())
    print(f"wrote {out}")
