"""obs-contract: metric/span names from the registered catalog, with
bounded cardinality.

The PR 11 telemetry plane merges per-process snapshots by series name
(obs/exporters.py ``merge_snapshots``) — an ad-hoc name in one worker
forks a series the fleet view can't join, and a per-request dynamic
name grows the registry without bound. The contract (obs/names.py):

- a literal name passed to ``obs.count``/``observe``/``span``/
  ``pass_span``/``counter``/``gauge``/``histogram`` must be in ``obs.names.NAMES``
  and follow the dotted lower-case ``layer.stage`` convention (P1 when
  unregistered — add the constant to obs/names.py);
- an f-string name is P2 when its literal prefix starts with a
  registered ``layer.`` (bounded suffix sets like flag-bit names are
  fine — justify with an inline allow), P1 when fully dynamic;
- label kwargs on ``counter``/``gauge``/``histogram``/``observe`` must
  be literal values (P2) — labels are series keys, not payload.

``obs/`` itself is exempt: the registry/exporter plumbing passes names
through by design. Span ``attrs`` kwargs are payload, not series keys,
and are not checked.
"""

from __future__ import annotations

import ast
import re

from spark_bam_tpu.analysis.base import LintContext, Rule, const_str, register
from spark_bam_tpu.obs import names as obs_names

#: obs entry points whose first positional arg is a series/span name
NAME_FNS = {"count", "observe", "span", "pass_span", "counter", "gauge",
            "histogram"}
#: of those, the ones whose kwargs are series labels (span kwargs = attrs)
LABELED_FNS = {"observe", "counter", "gauge", "histogram"}

_NAME_RE = re.compile(r"^[a-z0-9_\-]+(\.[a-z0-9_\-]+)+$")


def _obs_call(node: ast.Call) -> "str | None":
    """The obs entry-point name when this is ``obs.<fn>(...)`` or any
    ``<recv>.emit_span_event(...)``, else None."""
    f = node.func
    if not isinstance(f, ast.Attribute):
        return None
    if f.attr == "emit_span_event":
        return f.attr
    if isinstance(f.value, ast.Name) and f.value.id == "obs" \
            and f.attr in NAME_FNS:
        return f.attr
    return None


@register
class ObsContractRule(Rule):
    id = "obs-contract"
    severity = "P1"
    scope = ()                      # whole package
    exclude = ("obs/",)             # the plumbing layer passes names through
    doc = ("register new metric/span names in obs/names.py; keep "
           "cardinality bounded (docs/observability.md)")

    def check(self, ctx: LintContext):
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = _obs_call(node)
            if fn is None or not node.args:
                continue
            arg = node.args[0]
            lit = const_str(arg)
            if lit is not None:
                if not _NAME_RE.match(lit):
                    yield self.finding(
                        ctx, arg,
                        f"obs name {lit!r} does not follow the dotted "
                        "lower-case `layer.stage` convention",
                        hint="rename and register it in obs/names.py",
                    )
                elif not obs_names.is_registered(lit):
                    layer = obs_names.layer_of(lit)
                    extra = ("" if layer in obs_names.LAYERS else
                             f" (layer {layer!r} is new — add it to LAYERS)")
                    yield self.finding(
                        ctx, arg,
                        f"obs name {lit!r} is not in the registered catalog"
                        f"{extra}",
                        hint="add the constant to obs/names.py NAMES so "
                             "fleet snapshot merges can join the series",
                    )
            elif isinstance(arg, ast.JoinedStr):
                prefix = ""
                if arg.values and isinstance(arg.values[0], ast.Constant):
                    prefix = str(arg.values[0].value)
                layer = prefix.split(".", 1)[0] if "." in prefix else ""
                if layer in obs_names.LAYERS:
                    yield self.finding(
                        ctx, arg,
                        f"dynamic obs name with prefix {prefix!r}: series "
                        "cardinality is only as bounded as the suffix set",
                        hint="justify the bound with an inline "
                             "`# lint: allow[obs-contract] ...`, or "
                             "enumerate the names in obs/names.py",
                        severity="P2",
                    )
                else:
                    yield self.finding(
                        ctx, arg,
                        f"unbounded dynamic obs name in `obs.{fn}` — one "
                        "series per distinct value",
                        hint="use a registered literal name; put the "
                             "varying part in the event payload, not the "
                             "series name",
                    )
            else:
                yield self.finding(
                    ctx, arg,
                    f"non-literal obs name in `obs.{fn}` — the catalog "
                    "cannot vouch for it",
                    hint="pass a literal registered name (obs/names.py)",
                )
            if fn in LABELED_FNS:
                for kw in node.keywords:
                    if kw.arg is None:
                        continue
                    if not isinstance(kw.value, ast.Constant):
                        yield self.finding(
                            ctx, kw.value,
                            f"non-literal label value for {kw.arg!r} on "
                            f"`obs.{fn}` — labels key the series; dynamic "
                            "values explode cardinality",
                            hint="use a bounded literal label, or move the "
                                 "value into a histogram observation",
                            severity="P2",
                        )
