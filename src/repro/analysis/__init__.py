"""repro.analysis — static verification of deployment invariants.

Four linters, one CLI (``python -m repro.analysis``), machine-readable
findings with stable rule IDs (``findings.RULES`` is the catalog,
DESIGN.md §12 the prose):

* ``contracts``  — abstract interpretation (``jax.eval_shape``) of every
  collective strategy and model family: dtype/shape contracts proven
  with zero FLOPs (CT rules).
* ``hlo_lint``   — compiled-HLO rule engine grown out of
  ``launch/roofline.py``: measured collective bytes must equal the
  analytic ring model, no widening converts in the residual stream
  (HL rules).
* ``ast_lint``   — source hygiene: raw ``lax`` collectives outside
  comm/+dist/, kernel calls bypassing the dispatch registry, unfrozen
  spec dataclasses, mutable defaults (AS rules).
* ``manifest_lint`` — offline ``DeploymentArtifact`` audit: plan-glob
  reachability, rank shards on disk, fold coverage, BENCH snapshot
  schema (MF/BN rules).

None of these runs the model; all of them fail CI when an invariant
the serving stack depends on stops holding.
"""

from repro.analysis.findings import (Finding, Rule, RULES, has_errors,
                                     summarize, to_json_text)

__all__ = ["Finding", "Rule", "RULES", "has_errors", "summarize",
           "to_json_text"]
