"""Manifest lint — offline audit of a ``DeploymentArtifact`` directory.

Everything here reads ``manifest.json`` + the ``rank_NN.npz`` /
``aux.npz`` files on disk; no mesh, no model build, no FLOPs.  The
invariants are the ones a *served* deployment would otherwise discover
at forward time (or worse, never):

* MF001 — every ``collective_plan`` entry glob resolves at least one
  real pair/fold site; an unreachable glob is a typo'd plan.
* MF002 — no entry is fully shadowed by earlier entries (matches sites,
  wins none) — shadowed entries silently serve a different collective
  than the plan text suggests.
* MF004 — the manifest's ``leaf_shards`` map and the ``rank_NN.npz``
  files agree: all TP files present, identical key sets, consistent
  per-rank shapes, no stray rank files beyond the TP degree.
* MF005 — every aux attention V->O fold is either consumed by the
  family's attention runtime (``SUPPORTS_ATTN_VO`` + matching
  ``ATTN_VO_PATH``) or explicitly waived (``ATTN_VO_WAIVED``) with a
  reason; folds that are neither are dead weight shipped as if live.
* MF006 — the policy's collective shorthand round-trips through
  ``parse_collective`` and agrees with the structural
  ``collective_plan`` echo.
* BN001 — committed ``BENCH_*.json`` snapshots carry the
  ``benchmarks/snapshot.py`` writer schema (git SHA, env block,
  non-empty metrics) so perf re-anchors stay machine-comparable.
"""

from __future__ import annotations

import glob as globlib
import json
import os
from typing import Optional, Sequence

from repro.analysis.findings import Finding

#: environment keys ``benchmarks.snapshot._environment`` always writes
BENCH_ENV_KEYS = ("jax", "backend", "device_count")

#: top-level keys ``benchmarks.snapshot.write`` always writes
BENCH_KEYS = ("bench", "git_sha", "created", "environment", "config",
              "metrics")


def _site_paths(manifest: dict, aux: Optional[dict]) -> list[str]:
    """Every dotted path the plan can resolve: planned MLP pairs plus
    aux attention V->O fold sites."""
    paths = [m["path"] for m in manifest.get("pairs", ())]
    for path in sorted((aux or {}).get("attn_plans", {})):
        if path not in paths:
            paths.append(path)
    return paths


def _parse_plan(manifest: dict, location: str):
    """(parsed collective, findings) from the manifest policy field."""
    from repro.comm.spec import parse_collective

    short = manifest.get("policy", {}).get("collective", "psum")
    try:
        coll = parse_collective(short)
    except ValueError as e:
        return None, [Finding(
            "MF006", f"policy collective {short!r} does not parse: {e}",
            location=location)]
    out = []
    if coll.shorthand() != short:
        out.append(Finding(
            "MF006",
            f"collective shorthand does not round-trip: {short!r} "
            f"re-serializes as {coll.shorthand()!r}",
            location=location))
    return coll, out


def lint_manifest_dict(manifest: dict, aux: Optional[dict] = None, *,
                       location: str = "manifest") -> list[Finding]:
    """Pure-dict checks (MF001/MF002/MF006) — no disk."""
    from repro.comm.spec import CollectivePlan, _match

    coll, out = _parse_plan(manifest, location)
    sites = _site_paths(manifest, aux)

    # MF006: structural echo must agree with the authoritative shorthand
    echo = manifest.get("collective_plan")
    if echo is not None:
        if not isinstance(coll, CollectivePlan):
            out.append(Finding(
                "MF006",
                "manifest carries a collective_plan echo but the policy "
                "collective is a bare spec",
                location=location))
        else:
            want = {"entries": [[pat, spec.shorthand()]
                                for pat, spec in coll.entries],
                    "default": coll.default.shorthand()}
            if echo != want:
                out.append(Finding(
                    "MF006",
                    "collective_plan echo disagrees with the policy "
                    "collective shorthand",
                    location=location,
                    detail={"echo": echo, "policy": want}))

    # MF001 / MF002: glob reachability over the real site list
    if isinstance(coll, CollectivePlan) and sites:
        winners: set[int] = set()
        for site in sites:
            for i, (pat, _) in enumerate(coll.entries):
                if _match(site, pat):
                    winners.add(i)
                    break
        for i, (pat, spec) in enumerate(coll.entries):
            if not any(_match(s, pat) for s in sites):
                out.append(Finding(
                    "MF001",
                    f"plan entry {pat!r} ({spec.shorthand()}) matches no "
                    f"pair or fold site — unreachable",
                    location=location, detail={"sites": sites}))
            elif i not in winners:
                out.append(Finding(
                    "MF002",
                    f"plan entry {pat!r} ({spec.shorthand()}) is fully "
                    f"shadowed by earlier entries — it never resolves",
                    location=location))

    return out


# ---------------------------------------------------------------------------
# on-disk checks
# ---------------------------------------------------------------------------

def _rank_files(dirpath: str, tp: int):
    have = sorted(globlib.glob(os.path.join(dirpath, "rank_*.npz")))
    want = [os.path.join(dirpath, f"rank_{r:02d}.npz") for r in range(tp)]
    return have, want


def _lint_rank_shards(dirpath: str, manifest: dict) -> list[Finding]:
    """MF004: leaf_shards vs what is actually on disk."""
    from repro.train import checkpoint

    out: list[Finding] = []
    tp = int(manifest["tp"])
    shards = manifest.get("leaf_shards", {})
    have, want = _rank_files(dirpath, tp)
    for path in want:
        if path not in have:
            out.append(Finding(
                "MF004",
                f"missing rank shard {os.path.basename(path)} "
                f"(manifest tp={tp})", location=dirpath))
    for path in have:
        if path not in want:
            out.append(Finding(
                "MF004",
                f"stray rank shard {os.path.basename(path)} beyond the "
                f"manifest's tp={tp} — a stale or foreign file",
                location=dirpath))
    flats = {}
    for path in want:
        if path not in have:
            continue
        r = int(os.path.basename(path)[5:7])
        flats[r] = checkpoint.flatten_keys(checkpoint.load(path))
    if not flats:
        return out
    want_keys = set(shards)
    for r, flat in sorted(flats.items()):
        keys = set(flat)
        if want_keys and keys != want_keys:
            missing = sorted(want_keys - keys)[:5]
            extra = sorted(keys - want_keys)[:5]
            out.append(Finding(
                "MF004",
                f"rank_{r:02d}.npz keys disagree with the manifest's "
                f"leaf_shards map (missing {missing}, extra {extra})",
                location=dirpath))
    ranks = sorted(flats)
    base = flats[ranks[0]]
    for r in ranks[1:]:
        for key in set(base) & set(flats[r]):
            if getattr(base[key], "shape", None) != getattr(
                    flats[r][key], "shape", None):
                out.append(Finding(
                    "MF004",
                    f"leaf {key!r} has shape {flats[r][key].shape} on "
                    f"rank {r} but {base[key].shape} on rank "
                    f"{ranks[0]} — uneven shards",
                    location=dirpath))
    return out


def _lint_fold_coverage(manifest: dict, aux: Optional[dict], *,
                        location: str) -> list[Finding]:
    """MF005: every shipped V->O fold is consumed or explicitly waived."""
    from repro.configs import get_smoke_config
    from repro.models import registry

    out: list[Finding] = []
    plans = (aux or {}).get("attn_plans") or {}
    if not plans:
        return out
    try:
        family = get_smoke_config(manifest["arch_id"]).family
        module = registry._FAMILY_MODULES[family]
    except Exception as e:
        out.append(Finding(
            "MF005",
            f"cannot resolve family module for arch "
            f"{manifest.get('arch_id')!r}: {e}", location=location))
        return out
    consumed = (getattr(module, "ATTN_VO_PATH", None)
                if getattr(module, "SUPPORTS_ATTN_VO", False) else None)
    waived = getattr(module, "ATTN_VO_WAIVED", {})
    for path in sorted(plans):
        if path == consumed:
            continue
        if path in waived:
            out.append(Finding(
                "MF005",
                f"fold {path!r} is waived by the {family} runtime: "
                f"{waived[path]}", location=location, severity="info"))
        else:
            out.append(Finding(
                "MF005",
                f"artifact ships a V->O fold at {path!r} the {family} "
                f"attention runtime neither consumes nor waives — dead "
                f"aux weight shipped as if live",
                location=location,
                detail={"consumed": consumed,
                        "waived": sorted(waived)}))
    return out


def lint_artifact(dirpath: str) -> list[Finding]:
    """Full offline audit of one artifact directory (MF rules)."""
    from repro.plan.artifact import DeploymentArtifact
    from repro.train import checkpoint

    try:
        manifest = DeploymentArtifact.load_manifest(dirpath)
    except Exception as e:
        return [Finding("MF004", f"unloadable artifact: {e}",
                        location=dirpath)]
    aux_path = os.path.join(dirpath, "aux.npz")
    aux = checkpoint.load(aux_path) if os.path.exists(aux_path) else None
    out = lint_manifest_dict(manifest, aux, location=dirpath)
    out += _lint_rank_shards(dirpath, manifest)
    out += _lint_fold_coverage(manifest, aux, location=dirpath)
    return out


# ---------------------------------------------------------------------------
# BENCH snapshot schema (BN001)
# ---------------------------------------------------------------------------

def lint_bench_snapshots(root: Optional[str] = None,
                         paths: Optional[Sequence[str]] = None
                         ) -> list[Finding]:
    """Validate committed ``BENCH_*.json`` files against the writer."""
    if paths is None:
        if root is None:
            here = os.path.dirname(os.path.abspath(__file__))
            root = os.path.dirname(os.path.dirname(os.path.dirname(here)))
        paths = sorted(globlib.glob(os.path.join(root, "BENCH_*.json")))
    out: list[Finding] = []
    for path in paths:
        name = os.path.basename(path)
        try:
            with open(path) as f:
                snap = json.load(f)
        except Exception as e:
            out.append(Finding("BN001", f"unreadable snapshot: {e}",
                               location=name))
            continue
        missing = [k for k in BENCH_KEYS if k not in snap]
        if missing:
            out.append(Finding(
                "BN001", f"snapshot is missing writer keys {missing}",
                location=name))
            continue
        stem = name[len("BENCH_"):-len(".json")]
        if snap["bench"] != stem:
            out.append(Finding(
                "BN001",
                f"snapshot 'bench' field {snap['bench']!r} does not "
                f"match its filename stem {stem!r}", location=name))
        if not snap["git_sha"]:
            out.append(Finding(
                "BN001", "snapshot carries an empty git_sha",
                location=name))
        env = snap["environment"]
        env_missing = [k for k in BENCH_ENV_KEYS if k not in env]
        if env_missing:
            out.append(Finding(
                "BN001",
                f"snapshot environment block is missing {env_missing}",
                location=name))
        if not isinstance(snap["metrics"], dict) or not snap["metrics"]:
            out.append(Finding(
                "BN001", "snapshot has no metrics", location=name))
    return out


def run(artifact: Optional[str] = None,
        root: Optional[str] = None) -> list[Finding]:
    """Entry point the CLI calls: BENCH schema + optional artifact audit."""
    out = lint_bench_snapshots(root=root)
    if artifact:
        out += lint_artifact(artifact)
    return out
