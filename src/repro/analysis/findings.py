"""Findings + rule catalog — the analysis subsystem's shared vocabulary.

Every linter in ``repro.analysis`` (contracts / hlo_lint / ast_lint /
manifest_lint) reports through the same machine-readable shape: a
``Finding`` carrying a rule ID from the central ``RULES`` catalog, a
severity, a location, and a free-form ``detail`` payload.  The catalog
is the single source of truth the CLI, the tests, and DESIGN.md §12
enumerate — a linter cannot emit an unregistered rule ID
(``Finding.__post_init__`` refuses), so the documented catalog and the
enforced catalog can never drift.

Severities:

* ``error`` — an invariant the deployment plan promises is violated;
  the CLI exits non-zero (the CI gate).
* ``warn``  — suspicious but not provably wrong (e.g. a copy of a
  donated buffer XLA may have legitimate reasons for).
* ``info``  — a recorded, intentional waiver (e.g. an attention fold a
  family documents as not consumable).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Optional

SEVERITIES = ("error", "warn", "info")


@dataclasses.dataclass(frozen=True)
class Rule:
    """One catalog entry: the invariant a linter enforces."""

    id: str
    layer: str          # contracts | hlo | ast | manifest
    severity: str       # default severity findings of this rule carry
    invariant: str      # one-line statement of what must hold
    caught: str         # which past bug class this rule would have caught


#: the rule catalog — DESIGN.md §12 is generated from this table's
#: fields, and ``Finding`` refuses IDs that are not in it.
RULES: dict[str, Rule] = {r.id: r for r in [
    # ---- contracts.py (abstract interpretation, no FLOPs) -----------------
    Rule("CT001", "contracts", "error",
         "every collective strategy returns the residual-stream input "
         "dtype and the contracted shape under jax.eval_shape at every "
         "TP degree",
         "the 'cast' strategy leaking bf16 into the f32 residual stream "
         "(compounding rounding per layer; fixed in comm/dispatch)"),
    Rule("CT002", "contracts", "error",
         "at TP=1 every collective spec is the identity and its analytic "
         "bytes_on_wire is exactly zero",
         "single-rank deployments paying a quantize/dequantize round "
         "trip (or wire bytes) for a collective that moves nothing"),
    Rule("CT003", "contracts", "error",
         "paged and dense KV caches of a family agree on per-token "
         "geometry (kv-heads, head_dim) and payload dtype",
         "a paged pool allocated with the wrong head grid decoding "
         "garbage only once a sequence crosses its first page boundary"),
    Rule("CT004", "contracts", "error",
         "every registered model family's forward/decode emits f32 "
         "logits from abstract params (jax.eval_shape, zero FLOPs)",
         "low-bit accumulation dtypes escaping through the lm_head and "
         "silently degrading sampling entropy"),
    # ---- hlo_lint.py (compiled-HLO rule engine) ---------------------------
    Rule("HL001", "hlo", "error",
         "collective bytes measured from compiled HLO equal the spec's "
         "analytic bytes_on_wire per resolved site (ring cost model, "
         "rel diff < 1e-6)",
         "the quant-int8/int4 gather fallback burning tp/2 x the "
         "analytic wire bytes before the padded two-phase ring landed"),
    Rule("HL002", "hlo", "error",
         "no dtype-widening float convert in the residual stream whose "
         "matching narrowing convert is absent (an asymmetric widening "
         "means the stream was already narrow), and the program's root "
         "keeps the activation input dtype",
         "the pre-fix 'cast' collective returning its bf16 wire dtype: "
         "the residual add widened it back every layer, visible in HLO "
         "as an unmatched bf16->f32 convert"),
    Rule("HL004", "hlo", "warn",
         "no copy instruction duplicates a donated (input/output "
         "aliased) parameter",
         "donated KV-cache buffers silently copied per decode step, "
         "doubling cache HBM and hiding the donation's benefit"),
    # ---- ast_lint.py (source-tree checks) ---------------------------------
    Rule("AS001", "ast", "error",
         "no raw jax.lax collective (psum/psum_scatter/all_gather/"
         "ppermute/all_to_all/pmean) outside comm/ and dist/",
         "call sites bypassing the comm registry so per-layer plans, "
         "wire accounting, and the dtype contract silently don't apply"),
    Rule("AS002", "ast", "error",
         "no kernel invocation (kernels.ops / kernels.ref entry points) "
         "bypasses the kernels/dispatch.py registry",
         "a call pinned to one backend skipping dispatch's availability "
         "fallback and the policy's backend selection"),
    Rule("AS003", "ast", "error",
         "every dataclass in a spec module (core/policy.py, comm/spec.py"
         ", cache/spec.py, dist/topology.py) is frozen",
         "a mutable spec mutating after being hashed as a jit static "
         "argument — stale compilation caches keyed on the old value"),
    Rule("AS004", "ast", "error",
         "no mutable default argument (list/dict/set literals) in src/",
         "a shared default accumulating state across calls (classic "
         "aliasing bug; none shipped, the rule keeps it that way)"),
    # ---- manifest_lint.py (offline artifact audit) ------------------------
    Rule("MF001", "manifest", "error",
         "every CollectivePlan entry glob matches at least one site the "
         "artifact actually planned (pairs + attention folds)",
         "a tuned plan entry orphaned by a rename resolving every site "
         "to the default psum while the manifest still advertises "
         "quantized epilogues"),
    Rule("MF002", "manifest", "error",
         "no CollectivePlan entry is shadowed (every entry is the first "
         "match for at least one planned site)",
         "an earlier catch-all glob silently overriding a later, more "
         "specific per-layer choice"),
    Rule("MF004", "manifest", "error",
         "the manifest's leaf_shards map matches the rank_NN.npz files "
         "on disk: tp files present, every key in every rank, no "
         "unlisted keys, shard shapes consistent across ranks",
         "a hand-pruned artifact directory serving a rank tree that "
         "silently reassembles the wrong global tensor"),
    Rule("MF005", "manifest", "error",
         "every aux attention V->O fold is either consumed by the "
         "family's runtime (ATTN_VO_PATH) or explicitly waived "
         "(ATTN_VO_WAIVED, reported as info)",
         "whisper's decoder folds riding every artifact as dead weight "
         "while the runtime recomputed the unfolded projections"),
    Rule("MF006", "manifest", "error",
         "the manifest's collective shorthand parses and round-trips, "
         "and the structural collective_plan echo agrees with it",
         "a manifest edited by hand serving a different plan than the "
         "one its provenance block displays"),
    Rule("BN001", "manifest", "error",
         "every committed BENCH_*.json matches benchmarks/snapshot.py's "
         "writer schema: bench name == filename, git_sha, created, "
         "environment{jax, backend, device_count}, config, non-empty "
         "metrics",
         "a stale or hand-edited snapshot anchoring future perf "
         "comparisons to numbers no writer produced"),
]}


@dataclasses.dataclass(frozen=True)
class Finding:
    """One linter result, machine-readable.

    ``location`` is layer-appropriate: ``file:line`` for AST findings,
    a pair path / spec shorthand for plan findings, an HLO instruction
    name for compiled findings.
    """

    rule: str
    message: str
    location: str = ""
    severity: Optional[str] = None      # None -> the rule's default
    detail: Any = None

    def __post_init__(self):
        if self.rule not in RULES:
            raise ValueError(
                f"finding uses unregistered rule id {self.rule!r}; "
                f"catalog: {sorted(RULES)}")
        sev = self.severity or RULES[self.rule].severity
        if sev not in SEVERITIES:
            raise ValueError(f"unknown severity {sev!r}")
        object.__setattr__(self, "severity", sev)

    def to_json(self) -> dict:
        return {
            "rule": self.rule,
            "severity": self.severity,
            "layer": RULES[self.rule].layer,
            "location": self.location,
            "message": self.message,
            "detail": self.detail,
        }

    def __str__(self) -> str:
        loc = f" {self.location}" if self.location else ""
        return f"[{self.rule}/{self.severity}]{loc}: {self.message}"


def summarize(findings) -> dict:
    """The CLI's JSON report: catalog + findings + exit-worthy counts."""
    findings = list(findings)
    return {
        "findings": [f.to_json() for f in findings],
        "counts": {sev: sum(1 for f in findings if f.severity == sev)
                   for sev in SEVERITIES},
        "rules_checked": sorted(RULES),
    }


def to_json_text(findings) -> str:
    return json.dumps(summarize(findings), indent=1, sort_keys=True)


def has_errors(findings) -> bool:
    return any(f.severity == "error" for f in findings)
