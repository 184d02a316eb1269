"""config-gate: every feature-disabling warning must be registered.

Detects *gate-shaped* log calls — ``logger.warning``/``logger.info``/
``warnings.warn`` whose message says a requested feature is being
turned off or downgraded ("... disabled: ...", "... ignored ...",
"run(s) replicated") — and checks each against the reviewed table in
:mod:`parallax_tpu.analysis.gates`:

- a gate site with no matching table ``marker`` is a finding (an
  unregistered silently-off path);
- a table entry whose ``feature`` is not a real ``EngineConfig`` field
  (or a ``flag:--name`` spelling) is a finding against the table
  itself (the field was renamed/removed);
- a table entry whose ``doc`` file is missing or never mentions the
  feature is a finding (operator docs drifted).

Table-level checks run once, attributed to ``gates.py``, so the pass
output stays stable regardless of which file triggered the scan.
"""

from __future__ import annotations

import ast
import os
import re

from parallax_tpu.analysis.checkers import common
from parallax_tpu.analysis.linter import Checker, Finding, Module

GATE_MESSAGE_RE = re.compile(
    r"(disabled[:\s]|\bignored\b|runs? replicated)",
)

LOG_CALLEES = ("warning", "info", "warn")


def _engine_config_fields(engine_path: str) -> set[str]:
    """EngineConfig field names, read from engine.py's AST (no jax
    import needed)."""
    try:
        with open(engine_path, encoding="utf-8") as f:
            tree = ast.parse(f.read())
    except (OSError, SyntaxError):  # pragma: no cover - broken checkout
        return set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == "EngineConfig":
            return {
                stmt.target.id
                for stmt in node.body
                if isinstance(stmt, ast.AnnAssign)
                and isinstance(stmt.target, ast.Name)
            }
    return set()


class ConfigGateChecker(Checker):
    id = "config-gate"
    doc = ("feature-disabling warning not registered in the gate "
           "table, or a gate entry whose config field / doc drifted")

    def __init__(self) -> None:
        self._table_checked = False
        # pkg_root -> normalized concatenation of every package source,
        # built once per run (marker liveness is O(gates) probes on it,
        # not O(gates x files) re-walks).
        self._corpus: dict[str, str] = {}

    def check(self, module: Module) -> list[Finding]:
        from parallax_tpu.analysis.gates import GATE_TABLE

        out: list[Finding] = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if not (isinstance(func, ast.Attribute)
                    and func.attr in LOG_CALLEES):
                continue
            msg = common.call_str_args(node)
            if not msg or not GATE_MESSAGE_RE.search(msg):
                continue
            if not any(g.marker in msg for g in GATE_TABLE):
                out.append(self.finding(
                    module, node.lineno,
                    "feature-gate warning is not registered in "
                    "analysis/gates.py GATE_TABLE — register the gate "
                    "(feature, marker, doc) or reword the message if no "
                    f"feature is being turned off: {msg[:80]!r}",
                ))
        # Table-level validation, once per run, pinned to gates.py so it
        # participates in suppression/baseline like any other finding.
        if module.rel.endswith("analysis/gates.py") and not self._table_checked:
            self._table_checked = True
            out.extend(self._check_table(module, GATE_TABLE))
        return out

    def _check_table(self, module: Module, table) -> list[Finding]:
        pkg_root = os.path.dirname(os.path.dirname(
            os.path.abspath(module.path)))
        repo_root = os.path.dirname(pkg_root)
        fields = _engine_config_fields(
            os.path.join(pkg_root, "runtime", "engine.py"))
        out: list[Finding] = []
        for gate in table:
            if gate.feature.startswith("flag:"):
                pass  # CLI flags are validated by their marker site
            elif fields and gate.feature not in fields:
                out.append(self.finding(
                    module, 1,
                    f"gate table entry {gate.marker!r} names feature "
                    f"{gate.feature!r}, which is not an EngineConfig "
                    "field — update the table to the renamed field",
                ))
            doc_path = os.path.join(repo_root, gate.doc)
            feature_name = gate.feature.removeprefix("flag:")
            if not os.path.exists(doc_path):
                out.append(self.finding(
                    module, 1,
                    f"gate table entry {gate.marker!r} points at missing "
                    f"doc {gate.doc}",
                ))
            else:
                with open(doc_path, encoding="utf-8") as f:
                    doc_text = f.read()
                # Docs may speak the CLI spelling (--sp-threshold) of a
                # config field (sp_threshold) — either counts.
                variants = {feature_name,
                            feature_name.replace("_", "-")}
                if not any(v in doc_text for v in variants):
                    out.append(self.finding(
                        module, 1,
                        f"doc {gate.doc} never mentions "
                        f"{feature_name!r} but the gate table says it "
                        "documents that feature's gate",
                    ))
            # Marker must still exist somewhere in the package (stale
            # entries rot the table) — checked cheaply via grep-on-read.
            if not self._marker_live(pkg_root, gate.marker):
                out.append(self.finding(
                    module, 1,
                    f"gate table marker {gate.marker!r} matches no log "
                    "call in parallax_tpu/ — the gate site was removed; "
                    "drop the entry",
                ))
        return out

    @staticmethod
    def _normalize(text: str) -> str:
        """Fold %-placeholders, adjacent-literal joins and whitespace so
        a marker matches the message however the source wraps it."""
        text = re.sub(r"%[0-9.]*[sdrfx]", "", text)
        text = re.sub(r"\s+", " ", text)
        text = text.replace('" "', "").replace("' '", "")
        return re.sub(r"\s+", " ", text)

    def _marker_live(self, pkg_root: str, marker: str) -> bool:
        probe = self._normalize(marker).strip()
        corpus = self._corpus.get(pkg_root)
        if corpus is None:
            parts: list[str] = []
            for root, dirs, files in os.walk(pkg_root):
                # The analysis package quotes every marker (gates.py,
                # tests, this file) — only real gate sites count.
                dirs[:] = [d for d in dirs
                           if d not in ("__pycache__", "analysis")]
                for fname in files:
                    if not fname.endswith(".py"):
                        continue
                    try:
                        with open(os.path.join(root, fname),
                                  encoding="utf-8") as f:
                            parts.append(self._normalize(f.read()))
                    except OSError:  # pragma: no cover
                        continue
            # \x00 separator: a marker can never match across two files.
            corpus = self._corpus[pkg_root] = "\x00".join(parts)
        return probe in corpus
