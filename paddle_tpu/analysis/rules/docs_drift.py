"""``metric-doc-drift`` — port of the ISSUE 7 doc-drift lint.

Every metric/span name LITERAL registered in ``paddle_tpu/`` must appear
in a ``docs/OBSERVABILITY.md`` table first cell, and every non-wildcard
documented name must still be registered — dashboards and scrapers can
trust the doc tables. Dynamic names (f-strings) are documented with
``<...>`` placeholders, which match as wildcards forward and are exempt
from the reverse check.
"""
import re

from ..engine import Finding, rule

#: registration call names whose string first argument is a metric/span
#: name: metrics registry, thread spans, request-trace, frontend families
REG_ATTRS = {"counter", "gauge", "histogram", "bump",
             "span",
             # the step log's spans (ISSUE 25): profiler annotations while
             # the record is built, fanned out as spans when tracing is on
             "annotation", "_phase",
             # the set-up log's phases (ISSUE 37): always-on records,
             # fanned out as spans of the same name when tracing is on
             "setup_phase", "setup_record",
             "child", "event", "begin", "span_at",
             "_class_hist"}

_NAME = re.compile(r"[a-z][a-z0-9_.<>*]*\Z")

DOC = "docs/OBSERVABILITY.md"


def _doc_names(text):
    names, patterns = set(), []
    for line in text.splitlines():
        if not line.startswith("|"):
            continue
        first = line.split("|")[1]
        for tok in re.findall(r"`([^`]+)`", first):
            if not _NAME.match(tok):
                continue
            if "<" in tok or "*" in tok:
                part = re.sub(r"<[^>]+>", "WILDCARDMARK", tok)
                pat = (re.escape(part)
                       .replace("WILDCARDMARK", "[A-Za-z0-9_.]+")
                       .replace(re.escape("*"), "[A-Za-z0-9_.]+"))
                patterns.append(re.compile(pat + r"\Z"))
            else:
                names.add(tok)
    return names, patterns


@rule("metric-doc-drift",
      description="registered metric/span names and the "
                  "docs/OBSERVABILITY.md tables must agree both ways")
def metric_doc_drift(index):
    registered = index.string_call_args(REG_ATTRS, prefix=("paddle_tpu/",))
    doc = index.doc(DOC)
    if doc is None:
        return [Finding(DOC, 0, "metric-doc-drift",
                        "docs/OBSERVABILITY.md is missing")]
    doc_names, doc_patterns = _doc_names(doc)
    findings = []
    for name in sorted(registered):
        if name in doc_names or any(p.match(name) for p in doc_patterns):
            continue
        path, line = sorted(registered[name])[0]
        findings.append(Finding(
            path, line, "metric-doc-drift",
            f"registered name {name!r} is missing from the "
            f"docs/OBSERVABILITY.md tables — add a row"))
    for name in sorted(doc_names):
        if name not in registered:
            findings.append(Finding(
                DOC, 0, "metric-doc-drift",
                f"documented name {name!r} is not registered anywhere in "
                f"paddle_tpu/ — remove the row or fix the name"))
    return findings
