#!/usr/bin/env python3
"""Metrics-registry lint: every Prometheus series the broker can export
must be documented in README.md.

The exported universe is assembled from the three places a series can be
born (rest/admin.py `_prometheus`):

1. every `Metrics.snapshot()` key — each becomes `chanamq_<key>`;
2. every `Metrics.histograms()` family — `chanamq_<name>` plus the
   derived `_bucket`/`_sum`/`_count` series (the family name documents
   all of them);
3. every literal `chanamq_[a-z0-9_]+` string in `chanamq_tpu/**/*.py`
   (labeled families emitted outside the snapshot loop, e.g.
   `chanamq_queue_messages`, `chanamq_slo_burn_rate`).

A name counts as documented when README.md contains it verbatim, via a
brace group (`chanamq_slo_{budget_remaining,burn_rate}`), or via a
prefix wildcard (`chanamq_stream_*`). Run with no arguments from
anywhere inside the repo; exits 1 listing every undocumented series so
scripts/tier1.sh can gate on it.
"""

from __future__ import annotations

import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

NAME_RE = re.compile(r"chanamq_[a-z0-9_]+")
# `chanamq_foo_{a,b}` in prose documents chanamq_foo_a and chanamq_foo_b;
# label sets like `chanamq_alert_firing{rule,scope}` contain no brace
# directly after an underscore, so the base-name regex handles them
BRACE_RE = re.compile(r"(chanamq_(?:[a-z0-9_]+_)?)\{([a-z0-9_,]+)\}")


def exported_names() -> set[str]:
    from chanamq_tpu.utils.metrics import Metrics

    metrics = Metrics()
    names = {f"chanamq_{key}" for key in metrics.snapshot()}
    names |= {f"chanamq_{name}" for name in metrics.histograms()}
    for path in sorted((ROOT / "chanamq_tpu").rglob("*.py")):
        # a trailing underscore is a docstring wildcard/brace-group stub
        # (`chanamq_forecast_*`, `chanamq_slo_{...}`), not a series — the
        # real names are literal at their emission sites
        names |= {n for n in NAME_RE.findall(path.read_text())
                  if not n.endswith("_")}
    # histogram families document their derived series as one name
    for name in {f"chanamq_{n}" for n in metrics.histograms()}:
        for suffix in ("_bucket", "_sum", "_count"):
            names.discard(name + suffix)
    return names


def documented(readme: str) -> "tuple[set[str], set[str]]":
    """(exact names, prefixes) the README vouches for."""
    # trailing-underscore matches are brace-group stubs, not names
    exact = {n for n in NAME_RE.findall(readme) if not n.endswith("_")}
    for base, group in BRACE_RE.findall(readme):
        exact |= {base + part for part in group.split(",") if part}
    prefixes = {
        m.group(1) for m in re.finditer(r"(chanamq_[a-z0-9_]+_)\*", readme)}
    return exact, prefixes


def exemplar_gaps() -> "tuple[list[str], list[str]]":
    """(uncovered, contradictions): histogram families with neither
    exemplar support nor an explicit exemption, and families listed as
    BOTH supported and exempt. Exemplar support is declared on
    AdminServer (`_EXEMPLAR_FAMILIES` by name, `_EXEMPLAR_PREFIXES` by
    prefix); a family an operator can scrape but never join to a trace
    must be a deliberate decision recorded in `_EXEMPLAR_EXEMPT`."""
    from chanamq_tpu.rest.admin import AdminServer
    from chanamq_tpu.trace.runtime import TraceRuntime
    from chanamq_tpu.utils.metrics import Metrics

    metrics = Metrics()
    # installing a runtime registers the per-stage trace_*_us families,
    # exactly as a tracing-enabled boot does
    TraceRuntime(metrics=metrics)
    covered = set(AdminServer._EXEMPLAR_FAMILIES)
    exempt = set(AdminServer._EXEMPLAR_EXEMPT)
    prefixes = tuple(AdminServer._EXEMPLAR_PREFIXES)
    uncovered, contradictions = [], []
    for name in sorted(metrics.histograms()):
        has_support = name in covered or name.startswith(prefixes)
        if has_support and name in exempt:
            contradictions.append(name)
        elif not has_support and name not in exempt:
            uncovered.append(name)
    return uncovered, contradictions


def main() -> int:
    readme = (ROOT / "README.md").read_text()
    exact, prefixes = documented(readme)
    missing = sorted(
        name for name in exported_names()
        if name not in exact
        and not any(name.startswith(p) for p in prefixes))
    if missing:
        print("metrics lint: undocumented Prometheus series "
              f"({len(missing)}) — add them to a README metric table:")
        for name in missing:
            print(f"  {name}")
        return 1
    uncovered, contradictions = exemplar_gaps()
    if uncovered or contradictions:
        for name in uncovered:
            print(f"metrics lint: histogram {name!r} has no exemplar "
                  "support — add it to AdminServer._EXEMPLAR_FAMILIES "
                  "(or _EXEMPLAR_EXEMPT with a reason)")
        for name in contradictions:
            print(f"metrics lint: histogram {name!r} is both exemplar-"
                  "supported and exempt — pick one")
        return 1
    print("metrics lint: every exported chanamq_* series is documented; "
          "every histogram family has exemplar support or an exemption")
    return 0


if __name__ == "__main__":
    sys.exit(main())
