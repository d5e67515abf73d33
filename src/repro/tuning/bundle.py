"""Portable tuning bundles — ship a site's tuned state as one artifact.

The paper's whole thesis is that software validated on commodity
hardware ships to a supercomputer as a portable artifact and adapts to
site resources at deploy time.  The tuning subsystem's state — cache
entries, workload profile, kernel ABI manifest — is the exact analogue
of that artifact, but until this module it was site-local: a laptop
could warm a cache, and a cluster could not use it.  A *tuning bundle*
packages one site's artifacts into a single checksummed tarball that a
different site imports through the same tombstone-clean merge path
deploys use, **revalidating every entry against the target platform**
instead of trusting foreign measurements or cold-searching from scratch:

  export   package the tuning cache (one platform fingerprint's worth),
           the workload profile, and the kernel ABI manifest into
           ``<out>.tgz`` with a versioned, checksummed ``manifest.json``.
  import   merge into the target site's cache atomically.  Per entry:
             * ``tuner.feasible`` re-passes on the TARGET platform
                        -> imported first-class ("bundle-imported" at bind)
             * structurally matched but infeasible here, or tuned on a
               drifted (minor) kernel revision
                        -> demoted: a near-config candidate the dispatch
                           may lend out at DEMOTED_PENALTY distance after
                           re-validating it for the borrowing call
                           ("bundle-demoted"), exactly like the near-dtype
                           borrow — never bound raw
             * bucket foreign to the op's signature
                        -> rejected per entry ("bundle-rejected"; reported,
                           not imported)
           Checksum/truncation/schema defects and ABI major or signature
           mismatches reject the WHOLE bundle with `BundleFormatError`
           before anything touches the cache — never a partial write; the
           target cache file stays byte-identical.
  verify   import into a scratch cache, replay the bundled profile
           through a bind, and assert zero-search exact dispatch for
           every imported bucket (and that demoted entries never bound
           first-class) — the conformance gate CI runs on pod-sim.

CLI:

    python -m repro.tuning.bundle export --out site.tgz [--cache PATH]
                                         [--profile PATH] [--platform NAME]
                                         [--ops a,b]
    python -m repro.tuning.bundle import site.tgz [--cache PATH]
                                         [--platform NAME]
    python -m repro.tuning.bundle verify site.tgz [--platform NAME] [--top K]

Deploy-side wiring: ``Runtime.deploy(tuning_bundle=PATH)`` (or
``REPRO_TUNING_BUNDLE``, or a ``Bundle.tuning_bundle`` reference baked
into the run bundle) auto-imports before binding, and the SwapReport's
geometries carry the bundle-imported/demoted/rejected provenance.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import io
import json
import logging
import os
import sys
import tarfile
import tempfile
from pathlib import Path
from typing import Any, Iterable, Mapping

from repro.core.abi import AbiError, parse_abi
from repro.tuning.cache import (
    SCHEMA_VERSION,
    CacheKey,
    TuningCache,
    platform_fingerprint,
    resolve_cache_path,
)
from repro.tuning.profile import (
    PROFILE_SCHEMA_VERSION,
    WorkloadProfile,
    resolve_profile_path,
)
from repro.tuning.tuner import bucket_validator

__all__ = [
    "BUNDLE_SCHEMA_VERSION",
    "ENV_TUNING_BUNDLE",
    "BundleFormatError",
    "SiteFingerprint",
    "EntryImport",
    "ImportReport",
    "export_bundle",
    "import_bundle",
    "verify_bundle",
    "KVHandoff",
    "KV_HANDOFF_SCHEMA_VERSION",
    "main",
]

log = logging.getLogger("repro.tuning")

BUNDLE_SCHEMA_VERSION = 1
ENV_TUNING_BUNDLE = "REPRO_TUNING_BUNDLE"
_KIND = "repro-tuning-bundle"
_MANIFEST = "manifest.json"
_CACHE_MEMBER = "tuning.json"
_PROFILE_MEMBER = "workload.json"


class BundleFormatError(ValueError):
    """The artifact is unusable as a whole: truncated, tampered (checksum
    mismatch), unknown schema, internally inconsistent, or ABI-incompatible
    with the target site.  Raised BEFORE any cache write — an import that
    sees this leaves the target byte-identical."""


def _default_registry():
    """The fully-populated global registry (same lazy import warm uses)."""
    from repro.core.registry import global_registry
    from repro.kernels.ops import register_all

    return register_all(global_registry)


def _vmem_budget() -> int:
    """The site's kernel-tile VMEM budget, for the fingerprint record."""
    try:
        from repro.kernels.ops import _VMEM_BUDGET

        return int(_VMEM_BUDGET)
    except ImportError:  # pragma: no cover - kernels always present here
        return 0


# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class SiteFingerprint:
    """Identity of the site an artifact was tuned on.

    ``key`` is the exact string `platform_fingerprint` derives (and cache
    keys embed); the extra fields — device kind actually backing the JAX
    backend, and the VMEM budget feasibility was checked against — make
    the manifest self-describing for humans and for future stricter
    revalidation policies.
    """

    platform: str
    hardware: str
    backend: str
    device_kind: str
    vmem_budget: int

    @property
    def key(self) -> str:
        return f"{self.platform}/{self.hardware}/{self.backend}"

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "SiteFingerprint":
        try:
            return cls(platform=str(d["platform"]), hardware=str(d["hardware"]),
                       backend=str(d["backend"]),
                       device_kind=str(d.get("device_kind", "")),
                       vmem_budget=int(d.get("vmem_budget", 0)))
        except (KeyError, TypeError, ValueError) as e:
            raise BundleFormatError(f"malformed fingerprint: {e}") from e

    @classmethod
    def capture(cls, platform: Any) -> "SiteFingerprint":
        import jax

        devices = jax.devices()
        return cls(
            platform=platform.name,
            hardware=platform.hardware.name,
            backend=jax.default_backend(),
            device_kind=devices[0].device_kind if devices else "",
            vmem_budget=_vmem_budget(),
        )


@dataclasses.dataclass(frozen=True)
class EntryImport:
    """Outcome of importing one bundled cache entry onto the target."""

    op: str
    shapes: str
    dtype: str
    status: str       # imported / demoted / rejected / already-present / skipped
    reason: str = ""
    key: str = ""     # encoded target cache key ("" when nothing was written)


@dataclasses.dataclass(frozen=True)
class ImportReport:
    """One bundle import, end to end: where from, where to, what happened."""

    source: str                          # bundle fingerprint key
    target: str                          # target fingerprint key
    results: tuple[EntryImport, ...]
    saved: bool                          # whether the cache file was written

    @property
    def cross_site(self) -> bool:
        return self.source != self.target

    def counts(self) -> dict[str, int]:
        out = {"imported": 0, "demoted": 0, "rejected": 0,
               "already-present": 0, "skipped": 0}
        for r in self.results:
            out[r.status] = out.get(r.status, 0) + 1
        return out

    def __len__(self) -> int:
        return len(self.results)

    def describe(self) -> str:
        c = self.counts()
        head = (f"bundle import {self.source} -> {self.target} "
                f"({'cross-site, revalidated' if self.cross_site else 'same site'}): "
                f"{c['imported']} imported, {c['demoted']} demoted, "
                f"{c['rejected']} rejected, {c['already-present']} already present"
                + (f", {c['skipped']} skipped" if c["skipped"] else ""))
        lines = [head]
        for r in self.results:
            note = f" ({r.reason})" if r.reason else ""
            lines.append(f"  {r.op:<18} {r.shapes or '<scalar>':<28} "
                         f"{r.dtype:<10} {r.status}{note}")
        return "\n".join(lines)


# ------------------------------------------------------------------ export --
def _sha256(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def export_bundle(
    out_path: str | os.PathLike,
    *,
    cache_path: str | os.PathLike,
    platform: Any,
    profile_path: str | os.PathLike | None = None,
    ops: Iterable[str] | None = None,
) -> tuple[Path, dict[str, Any]]:
    """Package this site's tuned state into a checksummed tarball.

    Only entries under the exporting platform's fingerprint travel (a
    bundle is ONE site's artifact; foreign-fingerprint entries in a
    shared cache file stay home).  Returns (path, manifest).  Raises
    ValueError when there is nothing to export, and BundleFormatError if
    the cache holds one op's entries under two different ABI strings (a
    malformed cache must not become a malformed artifact).
    """
    cache = TuningCache.load(cache_path)
    fp = SiteFingerprint.capture(platform)
    selected = None if ops is None else frozenset(ops)

    entries: dict[str, dict] = {}
    abis: dict[str, str] = {}
    for encoded in cache.raw_keys():
        parts = encoded.split("|")
        if len(parts) != 4 or parts[1] != fp.key:
            continue
        try:
            abi = parse_abi(parts[0])
        except AbiError:
            continue
        if selected is not None and abi.name not in selected:
            continue
        if abis.setdefault(abi.name, parts[0]) != parts[0]:
            raise BundleFormatError(
                f"cache holds op '{abi.name}' under two ABI strings "
                f"({abis[abi.name]} and {parts[0]}); expire before exporting"
            )
        entries[encoded] = cache.raw_entry(encoded)
    if not entries:
        raise ValueError(
            f"nothing to export: cache {cache_path} has no entries under "
            f"fingerprint {fp.key}"
        )

    cache_blob = json.dumps(
        {"schema": SCHEMA_VERSION, "entries": entries},
        indent=1, sort_keys=True,
    ).encode()

    profile_blob = None
    if profile_path is not None:
        profile = WorkloadProfile.load(profile_path)
        if len(profile):
            counts = {k: n for k, n in profile.counts().items()
                      if selected is None or k.split("|", 1)[0] in selected}
            if counts:
                profile_blob = json.dumps(
                    {"schema": PROFILE_SCHEMA_VERSION, "counts": counts},
                    indent=1, sort_keys=True,
                ).encode()

    # size accounting via the cache's own accessor, so the manifest number
    # can never diverge from what describe()/warm --compact report
    total_bytes = sum(cache.entry_bytes(k) for k in entries)
    checksums = {_CACHE_MEMBER: _sha256(cache_blob)}
    if profile_blob is not None:
        checksums[_PROFILE_MEMBER] = _sha256(profile_blob)
    manifest = {
        "schema": BUNDLE_SCHEMA_VERSION,
        "kind": _KIND,
        "fingerprint": fp.to_dict(),
        "abis": abis,
        "entries": {"count": len(entries), "total_bytes": total_bytes},
        "cache_schema": SCHEMA_VERSION,
        "checksums": checksums,
    }
    if profile_blob is not None:
        manifest["profile_schema"] = PROFILE_SCHEMA_VERSION
    manifest_blob = json.dumps(manifest, indent=1, sort_keys=True).encode()

    out = Path(out_path)
    out.parent.mkdir(parents=True, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=out.parent, prefix=out.name, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as raw, tarfile.open(fileobj=raw, mode="w:gz") as tar:
            for name, blob in ((_MANIFEST, manifest_blob),
                               (_CACHE_MEMBER, cache_blob),
                               (_PROFILE_MEMBER, profile_blob)):
                if blob is None:
                    continue
                info = tarfile.TarInfo(name)
                info.size = len(blob)
                tar.addfile(info, io.BytesIO(blob))
        os.replace(tmp, out)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    log.info("exported tuning bundle %s: %d entr%s (~%dB) under %s",
             out, len(entries), "y" if len(entries) == 1 else "ies",
             total_bytes, fp.key)
    return out, manifest


# ------------------------------------------------------------------ reading --
def _read_bundle(path: str | os.PathLike
                 ) -> tuple[dict, dict[str, dict], dict[str, float]]:
    """Read + fully verify a bundle file in memory.

    Returns (manifest, entries, profile counts).  Every defect — a
    truncated tarball, a member whose bytes don't match the manifest
    checksum, an unknown schema version, an internally inconsistent
    entry set — raises BundleFormatError; nothing is trusted past its
    checksum.
    """
    p = Path(path)
    members: dict[str, bytes] = {}
    try:
        with tarfile.open(p, mode="r:gz") as tar:
            for name in (_MANIFEST, _CACHE_MEMBER, _PROFILE_MEMBER):
                try:
                    fh = tar.extractfile(name)
                except KeyError:
                    fh = None
                if fh is not None:
                    members[name] = fh.read()
    except (OSError, EOFError, tarfile.TarError) as e:
        raise BundleFormatError(f"unreadable bundle {p}: {e}") from e

    if _MANIFEST not in members:
        raise BundleFormatError(f"bundle {p} has no {_MANIFEST}")
    try:
        manifest = json.loads(members[_MANIFEST])
    except (json.JSONDecodeError, UnicodeDecodeError) as e:
        raise BundleFormatError(f"bundle {p}: malformed manifest: {e}") from e
    if not isinstance(manifest, dict) or manifest.get("kind") != _KIND:
        raise BundleFormatError(f"bundle {p} is not a {_KIND} artifact")
    if manifest.get("schema") != BUNDLE_SCHEMA_VERSION:
        raise BundleFormatError(
            f"bundle {p} has schema {manifest.get('schema')!r} "
            f"(this runtime understands {BUNDLE_SCHEMA_VERSION})"
        )

    checksums = manifest.get("checksums") or {}
    for name in (_CACHE_MEMBER, _PROFILE_MEMBER):
        want = checksums.get(name)
        have = members.get(name)
        if have is None and want is None:
            continue
        if have is None or want is None or _sha256(have) != want:
            raise BundleFormatError(
                f"bundle {p}: checksum mismatch on {name} "
                f"(corrupt or tampered artifact)"
            )
    if _CACHE_MEMBER not in members:
        raise BundleFormatError(f"bundle {p} carries no {_CACHE_MEMBER}")

    fp = SiteFingerprint.from_dict(manifest.get("fingerprint") or {})
    abis = manifest.get("abis")
    if not isinstance(abis, dict) or not abis:
        raise BundleFormatError(f"bundle {p}: manifest has no ABI table")

    try:
        raw_cache = json.loads(members[_CACHE_MEMBER])
    except (json.JSONDecodeError, UnicodeDecodeError) as e:
        raise BundleFormatError(f"bundle {p}: malformed cache member: {e}") from e
    if not isinstance(raw_cache, dict) \
            or raw_cache.get("schema") != SCHEMA_VERSION:
        raise BundleFormatError(
            f"bundle {p}: cache member has schema "
            f"{raw_cache.get('schema') if isinstance(raw_cache, dict) else None!r} "
            f"(want {SCHEMA_VERSION})"
        )
    from repro.tuning.config import BlockConfig

    entries: dict[str, dict] = {}
    for encoded, entry in (raw_cache.get("entries") or {}).items():
        parts = encoded.split("|")
        if len(parts) != 4:
            raise BundleFormatError(f"bundle {p}: malformed entry key {encoded!r}")
        if parts[1] != fp.key:
            raise BundleFormatError(
                f"bundle {p}: entry {encoded!r} is not under the manifest "
                f"fingerprint {fp.key}"
            )
        try:
            abi = parse_abi(parts[0])
            BlockConfig.from_dict(entry["config"])
        except (AbiError, KeyError, TypeError, ValueError) as e:
            raise BundleFormatError(
                f"bundle {p}: malformed entry {encoded!r}: {e}") from e
        if abis.get(abi.name) != parts[0]:
            raise BundleFormatError(
                f"bundle {p}: entry {encoded!r} disagrees with the manifest "
                f"ABI table ({abis.get(abi.name)!r})"
            )
        entries[encoded] = dict(entry)

    counts: dict[str, float] = {}
    if _PROFILE_MEMBER in members:
        try:
            raw_profile = json.loads(members[_PROFILE_MEMBER])
        except (json.JSONDecodeError, UnicodeDecodeError) as e:
            raise BundleFormatError(
                f"bundle {p}: malformed profile member: {e}") from e
        if not isinstance(raw_profile, dict) \
                or raw_profile.get("schema") != PROFILE_SCHEMA_VERSION:
            raise BundleFormatError(
                f"bundle {p}: profile member has an unknown schema")
        for key, n in (raw_profile.get("counts") or {}).items():
            try:
                counts[str(key)] = float(n)
            except (TypeError, ValueError) as e:
                raise BundleFormatError(
                    f"bundle {p}: malformed profile count {key!r}") from e
    return manifest, entries, counts


# ------------------------------------------------------------------ import --
def import_bundle(
    path: str | os.PathLike,
    *,
    cache_path: str | os.PathLike,
    platform: Any,
    registry: Any = None,
    _prefetched: tuple[dict, dict, dict] | None = None,
) -> ImportReport:
    """Merge a bundle into the target site's cache, revalidating per entry.

    All validation — artifact integrity, ABI compatibility, per-entry
    feasibility on the TARGET platform — happens on in-memory data before
    the first cache write, and the write itself is the cache's atomic
    load-merge-replace: a rejection at any stage leaves the target file
    byte-identical, and a crash mid-save leaves the previous file (never
    a torn one).  Re-importing the same bundle is a no-op (entries the
    target already holds are skipped, and an untouched cache is not even
    rewritten).
    """
    # _prefetched lets verify_bundle reuse its own _read_bundle result
    # instead of decompressing and checksumming the artifact a second time
    manifest, entries, _ = (_prefetched if _prefetched is not None
                            else _read_bundle(path))
    reg = registry if registry is not None else _default_registry()
    source_fp = SiteFingerprint.from_dict(manifest["fingerprint"])
    target_fp = platform_fingerprint(platform)
    same_site = source_fp.key == target_fp

    # -- ABI gate (whole-bundle): resolve each op's target impl ------------
    per_op: dict[str, tuple[Any, bool]] = {}   # op -> (impl | None, minor_drift)
    for op, abi_text in sorted(manifest["abis"].items()):
        try:
            got = parse_abi(abi_text)
        except AbiError as e:
            # the manifest has no self-checksum, and _read_bundle only
            # cross-checks abis entries that back cache entries — a
            # hand-edited table must reject the artifact, not crash the
            # deploy that promised to degrade cold
            raise BundleFormatError(
                f"manifest ABI table is malformed for op '{op}': {e}") from e
        try:
            impl = reg.decl(op).tunable_native(platform)
        except KeyError:
            impl = None
        if impl is None:
            per_op[op] = (None, False)
            continue
        want = parse_abi(str(impl.abi))
        if (got.name, got.major, got.digest) != (want.name, want.major,
                                                 want.digest):
            raise BundleFormatError(
                f"ABI incompatibility for op '{op}': bundle tuned against "
                f"{got}, site declares {want} (major/signature mismatch)"
            )
        per_op[op] = (impl, got.minor != want.minor)

    # -- per-entry revalidation (in memory, no writes yet) -----------------
    plan: list[tuple[float, CacheKey, Any, dict, bool, EntryImport]] = []
    results: list[EntryImport] = []
    for encoded, entry in sorted(entries.items()):
        parts = encoded.split("|")
        op, shapes, dtype = parse_abi(parts[0]).name, parts[2], parts[3]
        impl, minor_drift = per_op[op]
        if impl is None:
            results.append(EntryImport(op, shapes, dtype, "skipped",
                                       "no tunable native on target"))
            continue
        tuner = impl.tuner
        synth = tuner.args_from_shapes
        if synth is not None and synth(platform, shapes, dtype) is None:
            results.append(EntryImport(op, shapes, dtype, "rejected",
                                       "bucket does not match op signature"))
            continue
        from repro.tuning.config import BlockConfig

        config = BlockConfig.from_dict(entry["config"])
        demote, reason = False, ""
        if minor_drift:
            demote, reason = True, "tuned on a drifted kernel revision"
        elif not same_site:
            validator = bucket_validator(tuner, platform)
            if validator is not None and not validator(config, shapes, dtype):
                demote, reason = True, "infeasible on target platform"
        new_key = CacheKey(abi=str(impl.abi), platform=target_fp,
                           shapes=shapes, dtype=dtype)
        metrics = dict(entry.get("metrics") or {})
        metrics["bundle_origin"] = source_fp.key   # provenance: the bind
        # labels hits on this entry "bundle-imported" until a local search
        # re-measures the key
        if demote:
            metrics["bundle_demoted_reason"] = reason
        status = "demoted" if demote else "imported"
        plan.append((float(entry.get("last_used", 0.0)), new_key, config,
                     metrics, demote,
                     EntryImport(op, shapes, dtype, status, reason,
                                 new_key.encode())))

    # -- apply: oldest bundled recency first, so relative LRU order holds --
    target = TuningCache.load(cache_path)
    wrote = False
    for _, key, config, metrics, demote, record in sorted(
            plan, key=lambda t: (t[0], t[1].encode())):
        live = target.get(key, touch=False) is not None
        if live or (demote and target.is_demoted(key)):
            results.append(dataclasses.replace(
                record, status="already-present",
                reason="target already holds this key"))
            continue
        target.put(key, config, metrics=metrics, demoted=demote)
        results.append(record)
        wrote = True
    if wrote:
        target.save()
    report = ImportReport(source=source_fp.key, target=target_fp,
                          results=tuple(results), saved=wrote)
    log.info(report.describe())
    return report


# ------------------------------------------------------------------ verify --
def verify_bundle(
    path: str | os.PathLike,
    *,
    platform: Any,
    registry: Any = None,
    top_k: int = 3,
) -> tuple[int, list[str]]:
    """Conformance check: does this bundle actually save the target work?

    Imports into a scratch cache and replays the bundled profile through
    a *read-only* bind (zero searches by construction — the point is to
    prove none would be NEEDED), then asserts:

      * every imported bucket dispatches exactly (its own entry, not a
        neighbour or the shipped default);
      * no demoted entry bound first-class (demoted buckets legitimately
        re-search-and-upgrade on a real deploy; that is the designed
        adaptation cost, not a conformance failure);
      * no *coverage gap*: a profiled bucket that is neither imported,
        demoted, nor rejected would force a cold search at deploy time —
        the exact cost a bundle exists to eliminate.

    Returns (exit code, report lines); 0 iff every assertion held.
    """
    from repro.tuning.tuner import TuningContext

    prefetched = _read_bundle(path)
    manifest, _, counts = prefetched
    reg = registry if registry is not None else _default_registry()
    tmp = Path(tempfile.mkdtemp(prefix="repro-bundle-verify-"))
    report = import_bundle(path, cache_path=tmp / "tuning.json",
                           platform=platform, registry=reg,
                           _prefetched=prefetched)
    lines = [report.describe()]

    profile = WorkloadProfile(tmp / "workload.json", counts=counts)
    cache = TuningCache.load(tmp / "tuning.json")
    ops = [op for op in sorted(manifest["abis"])
           if per_op_ok(reg, op, platform)]
    if not ops:
        return 1, lines + ["FAIL: target site binds no tunable native for "
                           "any bundled op"]
    ctx = TuningContext(cache, platform, search_on_miss=False,
                        profile=profile if len(profile) else None,
                        top_k=top_k, bundle_report=report)
    binding = reg.bind(ops, platform, native=True, freeze=False, tuning=ctx)

    failures: list[str] = []
    by_status: dict[tuple[str, str, str], str] = {
        (r.op, r.shapes, r.dtype): r.status for r in report.results
    }
    reports = {r.op: r for r in binding.reports}
    for r in report.results:
        if r.op not in reports:
            continue          # 'skipped' entries: op not bound on this site
        table = binding.impl(r.op).config
        if r.status == "imported":
            cfg, how = table.resolve(shapes=r.shapes, dtype=r.dtype)
            if how != "exact":
                failures.append(
                    f"FAIL: imported bucket {r.op}[{r.shapes}/{r.dtype}] "
                    f"dispatches '{how}', want exact")
            else:
                lines.append(f"  ok {r.op:<18} {r.shapes or '<scalar>':<28} "
                             f"exact ({cfg})")
        elif r.status == "demoted":
            geoms = {(g.shapes, g.dtype): g.status
                     for g in reports[r.op].geometries}
            bound = geoms.get((r.shapes, r.dtype))
            if bound not in (None, "bundle-demoted", "bundle-rejected"):
                failures.append(
                    f"FAIL: demoted bucket {r.op}[{r.shapes}/{r.dtype}] "
                    f"bound as {bound!r}")
            cfg, how = table.resolve(shapes=r.shapes, dtype=r.dtype)
            if how == "exact":
                failures.append(
                    f"FAIL: demoted bucket {r.op}[{r.shapes}/{r.dtype}] "
                    f"resolves exact — it must never bind raw")
            else:
                lines.append(f"  ok {r.op:<18} {r.shapes or '<scalar>':<28} "
                             f"demoted -> '{how}'")
    # coverage gaps: a profiled bucket the bundle says nothing about will
    # cold-search at deploy time — exactly what a shipped artifact is
    # supposed to have paid for already
    for op in ops:
        for geo, n in profile.top(op=op, k=top_k):
            if (op, geo.shapes, geo.dtype) not in by_status:
                failures.append(
                    f"FAIL: profiled bucket {op}[{geo.shapes}/{geo.dtype}] "
                    f"(x{n:g}) is not covered by the bundle — a target "
                    f"deploy would pay a cold search for it")
    if ctx.searches_spent:   # read-only bind: impossible by construction
        failures.append(f"FAIL: replay paid {ctx.searches_spent} search(es)")
    if failures:
        return 1, lines + failures
    c = report.counts()
    lines.append(f"OK: {c['imported']} imported bucket(s) dispatch exactly, "
                 f"zero searches paid or needed"
                 + (f"; {c['demoted']} demoted entr"
                    f"{'y' if c['demoted'] == 1 else 'ies'} held back"
                    if c["demoted"] else ""))
    return 0, lines


def per_op_ok(reg: Any, op: str, platform: Any) -> bool:
    """True iff the target site binds a tunable native for `op`."""
    try:
        return reg.decl(op).tunable_native(platform) is not None
    except KeyError:
        return False


# -------------------------------------------------------------- KV handoff --
KV_HANDOFF_SCHEMA_VERSION = 1
_HANDOFF_KIND = "repro-kv-handoff"
_HANDOFF_STATE = "state.npz"


@dataclasses.dataclass(frozen=True)
class KVHandoff:
    """One slot's KV/SSM state in flight between serving replicas.

    The disaggregated fleet (repro.serving) migrates a finished prefill
    slot to a decode replica as an *artifact*, not a pointer: the pages
    the sender's ``PagedPool`` held for the slot (gathered in block-table
    order) plus the slot's SSM rows, serialized through the same
    checksummed-manifest path the tuning bundles use.  The receiver
    leases fresh pages from its own ``BlockAllocator`` and scatters the
    arrays in; nothing about the sender's page numbering survives the
    trip, which is exactly what makes the handoff portable between
    replicas with different pool occupancy.

    ``arrays`` maps cache-tree leaves to numpy arrays: ``"p{j}/k"`` /
    ``"p{j}/v"`` are ``(layers_in_part, pages_used, page_size, KV, Dh)``
    page stacks, ``"p{j}/state"`` / ``"p{j}/conv"`` are the slot's SSM
    rows.  ``next_pos`` counts tokens whose KV the pages hold (prompt
    plus any decoded-so-far tokens on a mid-decode migration).
    """

    rid: int
    source: str
    next_pos: int
    pages_used: int
    page_size: int
    arrays: Mapping[str, Any]

    def to_bytes(self) -> bytes:
        """Serialize as an in-memory tar.gz: manifest.json + state.npz.

        Same trust conventions as export_bundle: the manifest carries a
        sha256 per member plus per-array shape/dtype, so the receiver
        verifies everything before leasing a single page.
        """
        import numpy as np

        state = io.BytesIO()
        np.savez(state, **{k: np.asarray(v) for k, v in self.arrays.items()})
        state_blob = state.getvalue()
        manifest = {
            "schema": KV_HANDOFF_SCHEMA_VERSION,
            "kind": _HANDOFF_KIND,
            "rid": int(self.rid),
            "source": str(self.source),
            "next_pos": int(self.next_pos),
            "pages_used": int(self.pages_used),
            "page_size": int(self.page_size),
            "arrays": {k: [list(np.asarray(v).shape),
                           str(np.asarray(v).dtype)]
                       for k, v in self.arrays.items()},
            "checksums": {_HANDOFF_STATE: _sha256(state_blob)},
        }
        manifest_blob = json.dumps(manifest, indent=1, sort_keys=True).encode()
        out = io.BytesIO()
        with tarfile.open(fileobj=out, mode="w:gz") as tar:
            for name, blob in ((_MANIFEST, manifest_blob),
                               (_HANDOFF_STATE, state_blob)):
                info = tarfile.TarInfo(name)
                info.size = len(blob)
                tar.addfile(info, io.BytesIO(blob))
        return out.getvalue()

    @classmethod
    def from_bytes(cls, data: bytes) -> "KVHandoff":
        """Parse + fully verify a handoff artifact.

        Every defect — truncation, checksum mismatch, unknown schema,
        an array whose shape/dtype disagrees with the manifest — raises
        BundleFormatError before the receiver touches its pool.
        """
        import numpy as np

        members: dict[str, bytes] = {}
        try:
            with tarfile.open(fileobj=io.BytesIO(data), mode="r:gz") as tar:
                for name in (_MANIFEST, _HANDOFF_STATE):
                    try:
                        fh = tar.extractfile(name)
                    except KeyError:
                        fh = None
                    if fh is not None:
                        members[name] = fh.read()
        except (OSError, EOFError, tarfile.TarError) as e:
            raise BundleFormatError(f"unreadable KV handoff: {e}") from e
        if _MANIFEST not in members:
            raise BundleFormatError(f"KV handoff has no {_MANIFEST}")
        try:
            manifest = json.loads(members[_MANIFEST])
        except (json.JSONDecodeError, UnicodeDecodeError) as e:
            raise BundleFormatError(f"KV handoff: malformed manifest: {e}") from e
        if not isinstance(manifest, dict) \
                or manifest.get("kind") != _HANDOFF_KIND:
            raise BundleFormatError(f"not a {_HANDOFF_KIND} artifact")
        if manifest.get("schema") != KV_HANDOFF_SCHEMA_VERSION:
            raise BundleFormatError(
                f"KV handoff schema {manifest.get('schema')!r} "
                f"(this runtime understands {KV_HANDOFF_SCHEMA_VERSION})"
            )
        blob = members.get(_HANDOFF_STATE)
        want = (manifest.get("checksums") or {}).get(_HANDOFF_STATE)
        if blob is None or want is None or _sha256(blob) != want:
            raise BundleFormatError(
                "KV handoff: checksum mismatch on state.npz "
                "(corrupt or tampered artifact)"
            )
        try:
            with np.load(io.BytesIO(blob)) as npz:
                arrays = {k: npz[k] for k in npz.files}
        except Exception as e:
            raise BundleFormatError(f"KV handoff: unreadable state.npz: {e}") from e
        declared = manifest.get("arrays")
        if not isinstance(declared, dict) or set(declared) != set(arrays):
            raise BundleFormatError(
                "KV handoff: state.npz members disagree with the manifest"
            )
        for name, (shape, dtype) in declared.items():
            arr = arrays[name]
            if arr.dtype.kind == "V":
                # npz keeps extension dtypes (bfloat16, fp8) as raw bytes:
                # read them back as the dtype the manifest declares
                try:
                    arr = arrays[name] = arr.view(np.dtype(dtype))
                except (TypeError, ValueError):
                    pass   # unknown or mismatched: the check below raises
            if list(arr.shape) != list(shape) or str(arr.dtype) != dtype:
                raise BundleFormatError(
                    f"KV handoff: array {name} is {arr.shape}/{arr.dtype}, "
                    f"manifest declares {shape}/{dtype}"
                )
        try:
            meta = {k: int(manifest[k]) for k in
                    ("rid", "next_pos", "pages_used", "page_size")}
        except (KeyError, TypeError, ValueError) as e:
            raise BundleFormatError(f"KV handoff: malformed metadata: {e}") from e
        if meta["page_size"] < 1 or meta["pages_used"] < 1 \
                or meta["next_pos"] < 1 \
                or meta["pages_used"] * meta["page_size"] < meta["next_pos"]:
            raise BundleFormatError(
                f"KV handoff: inconsistent geometry {meta!r} "
                f"(pages cannot hold the declared positions)"
            )
        return cls(rid=meta["rid"], source=str(manifest.get("source", "?")),
                   next_pos=meta["next_pos"], pages_used=meta["pages_used"],
                   page_size=meta["page_size"], arrays=arrays)


# --------------------------------------------------------------------- CLI --
def _resolve_platform(name: str | None):
    from repro.core.env import resolve_platform
    from repro.core.platform import PLATFORMS

    return PLATFORMS[name] if name else resolve_platform()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Export/import/verify portable tuning bundles.")
    sub = ap.add_subparsers(dest="cmd", required=True)

    ex = sub.add_parser("export", help="package this site's tuned state")
    ex.add_argument("--out", required=True, help="bundle path to write (.tgz)")
    ex.add_argument("--cache", default=None,
                    help="tuning cache path (default: REPRO_TUNING_CACHE)")
    ex.add_argument("--profile", default=None,
                    help="workload profile path (default: "
                         "REPRO_WORKLOAD_PROFILE)")
    ex.add_argument("--platform", default=None,
                    help="platform name (default: REPRO_PLATFORM / detection)")
    ex.add_argument("--ops", default=None,
                    help="comma-separated op filter (default: every op with "
                         "entries)")

    im = sub.add_parser("import", help="merge a bundle into the site cache")
    im.add_argument("bundle", help="bundle path")
    im.add_argument("--cache", default=None,
                    help="tuning cache path (default: REPRO_TUNING_CACHE)")
    im.add_argument("--platform", default=None,
                    help="platform name (default: REPRO_PLATFORM / detection)")

    ve = sub.add_parser("verify", help="conformance-check a bundle "
                                       "(scratch import + zero-search replay)")
    ve.add_argument("bundle", help="bundle path")
    ve.add_argument("--platform", default=None,
                    help="platform name (default: REPRO_PLATFORM / detection)")
    ve.add_argument("--top", type=int, default=3,
                    help="profile geometries per op to replay")
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO, format="%(message)s")
    platform = _resolve_platform(args.platform)

    if args.cmd == "export":
        cache_path = Path(args.cache) if args.cache else resolve_cache_path()
        profile_path = (Path(args.profile) if args.profile
                        else resolve_profile_path())
        ops = [o.strip() for o in args.ops.split(",")] if args.ops else None
        try:
            out, manifest = export_bundle(
                args.out, cache_path=cache_path, platform=platform,
                profile_path=profile_path, ops=ops)
        except (ValueError, OSError) as e:
            print(f"export failed: {e}")
            return 1
        e = manifest["entries"]
        print(f"exported {out}: {e['count']} entr"
              f"{'y' if e['count'] == 1 else 'ies'} (~{e['total_bytes']}B) "
              f"under {SiteFingerprint.from_dict(manifest['fingerprint']).key}"
              f"{' + workload profile' if 'profile_schema' in manifest else ''}")
        return 0

    if args.cmd == "import":
        cache_path = Path(args.cache) if args.cache else resolve_cache_path()
        try:
            report = import_bundle(args.bundle, cache_path=cache_path,
                                   platform=platform)
        except (BundleFormatError, OSError) as e:
            print(f"import rejected: {e}")
            print("the target cache was not modified")
            return 1
        print(report.describe())
        print(f"cache {cache_path}: "
              f"{'updated' if report.saved else 'unchanged (no-op import)'}")
        return 0

    # verify
    try:
        code, lines = verify_bundle(args.bundle, platform=platform,
                                    top_k=args.top)
    except (BundleFormatError, OSError) as e:
        print(f"verify rejected the bundle outright: {e}")
        return 1
    print("\n".join(lines))
    return code


if __name__ == "__main__":
    sys.exit(main())
