"""``hot-span``: serving hot loops keep their telemetry spans (ported from
tools/check_serving.py, PR 6).

The serving hot paths — the continuous-batching engine's admit/step loop
and the gateway's forward path — must time themselves through
``tel.timed(``/``tel.span(``/``tel.record_span(`` (perf_counter-based;
``record_span`` is the form for an interval that crosses threads): an
uninstrumented hot loop is how the r05 endpoint collapse (14.5 tok/s against a 370k tok/s
chip) stayed invisible until a full bench window. The registry below names
the functions that MUST contain a span call; deleting the instrumentation
— or renaming a registered function/file without updating the registry —
is a finding (silently skipping a stale entry would let a rename drop the
guard).
"""

from __future__ import annotations

import ast
import os

from ..core import Finding, Rule
from ._util import matches_file

#: (serving-relative file, qualified function) -> must contain tel.timed/span
HOT_LOOPS: tuple = (
    ("continuous_batching.py", "PagedContinuousBatchingEngine._admit_all"),
    ("continuous_batching.py", "PagedContinuousBatchingEngine._step_chunk"),
    ("continuous_batching.py", "PagedContinuousBatchingEngine._land_chunk"),
    ("continuous_batching.py", "PagedContinuousBatchingEngine._stage_prefill"),
    ("replica_controller.py", "InferenceGateway.predict"),
)

_SPAN_ATTRS = ("timed", "span", "record_span")
_SERVING_DIR = "fedml_tpu/serving"


def _calls_span(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call) and isinstance(sub.func, ast.Attribute):
            if sub.func.attr in _SPAN_ATTRS:
                return True
    return False


class HotSpanRule(Rule):
    id = "hot-span"
    severity = "error"
    description = ("registered serving hot loop lost its tel.timed()/"
                   "tel.span() instrumentation (or the registry went stale)")

    # Per-file checks live in check_file (not finalize) so the incremental
    # engine can serve them from cache: finalize only sees dirty files.
    def check_file(self, ctx):
        repo_serving = os.path.join(ctx.root, *_SERVING_DIR.split("/"))
        in_repo_layout = os.path.isdir(repo_serving)
        for rel, fn_name in HOT_LOOPS:
            target = f"{_SERVING_DIR}/{rel}" if in_repo_layout else rel
            if matches_file(ctx.relpath, target):
                yield from self._check_fn(ctx, rel, fn_name)

    def finalize(self, run):
        # only the missing-FILE check needs whole-run context, and it must
        # be cache-safe: consult the filesystem, not run.files
        repo_serving = os.path.join(run.root, *_SERVING_DIR.split("/"))
        in_repo_layout = os.path.isdir(repo_serving)
        findings = []
        for rel in sorted({rel for rel, _fn in HOT_LOOPS}):
            missing = (os.path.join(repo_serving, rel) if in_repo_layout
                       else os.path.join(run.root, rel))
            if os.path.exists(missing):
                continue
            findings.append(Finding(
                rule=self.id, severity=self.severity, path=missing,
                relpath=os.path.relpath(missing, run.root).replace(os.sep, "/"),
                line=0, col=0,
                message=f"registry names missing file {rel}"))
        return findings

    def _check_fn(self, ctx, rel, fn_name):
        cls_name, _, meth = fn_name.rpartition(".")
        if cls_name:
            scopes = [n for n in ast.walk(ctx.tree)
                      if isinstance(n, ast.ClassDef) and n.name == cls_name]
        else:
            scopes = [ctx.tree]
        found = False
        for scope in scopes:
            nodes = scope.body if cls_name else ast.walk(scope)
            for node in nodes:
                if (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                        and node.name == meth):
                    found = True
                    if not _calls_span(node):
                        yield self.make(
                            ctx, node,
                            f"hot loop {fn_name}() has no tel.timed()/"
                            "tel.span() — wrap the device-touching section "
                            "in tel.timed('serving....') so TTFT/TPOT "
                            "regressions show up in /metrics, not in bench "
                            "windows")
        if not found:
            yield self.make(
                ctx, 0, f"registry names missing function {fn_name}()")


def _fn_calls(node: ast.AST):
    """Callable names invoked anywhere inside ``node``: bare names and the
    trailing attribute of method calls (``self._admission.check`` -> check)."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            if isinstance(sub.func, ast.Name):
                yield sub.func.id
            elif isinstance(sub.func, ast.Attribute):
                yield sub.func.attr


class AdmissionRejectRule(Rule):
    id = "admission-reject"
    severity = "error"
    description = ("admission-path reject does not emit the labeled "
                   "fedml_serving_admission_rejected_total{tenant=,reason=} "
                   "counter")

    # A reject site is any construction of AdmissionError. The labeled
    # family has exactly one emission helper — admission.count_reject() —
    # and one indirect emitter: AdmissionController.check(), which counts
    # internally before returning the shed reason. Every function that
    # builds an AdmissionError must call one of the two; an uncounted
    # reject is a request that vanished from the tenant's dashboard.
    _EMITTERS = ("count_reject", "check")

    def check_file(self, ctx):
        if "serving" not in ctx.relpath.replace(os.sep, "/").split("/"):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            rejects = [
                sub for sub in ast.walk(node)
                if isinstance(sub, ast.Call)
                and ((isinstance(sub.func, ast.Name)
                      and sub.func.id == "AdmissionError")
                     or (isinstance(sub.func, ast.Attribute)
                         and sub.func.attr == "AdmissionError"))
            ]
            if not rejects:
                continue
            if any(name in self._EMITTERS for name in _fn_calls(node)):
                continue
            for sub in rejects:
                yield self.make(
                    ctx, sub,
                    f"{node.name}() sheds a request (AdmissionError) without "
                    "emitting fedml_serving_admission_rejected_total — route "
                    "the reject through admission.count_reject(tenant, "
                    "reason) (or AdmissionController.check, which counts "
                    "internally) so shed traffic stays visible per tenant")
